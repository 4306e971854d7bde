"""Golden regression: pinned Fig. 7 / Fig. 8 reproduction statistics.

`sim.golden_stats` reduces a fixed-seed ensemble to a handful of floats
(beamspace kurtosis, NMSE curve endpoints, the bitwidth gap).  The values
below come from the CPU ref path; kernel or format-layer
refactors that change quantization numerics move them by far more than
the tolerance, while backend/BLAS noise stays well inside it.

If a change moves these numbers ON PURPOSE (e.g. a channel-model fix),
re-pin them in the same commit and say why in its message.
"""
import numpy as np
import pytest

from repro.mimo.sim import golden_stats

# Pinned on JAX 0.9.0, whose default `jax_threefry_partitionable=True`
# draws different random streams from the same seeds than the JAX that
# first produced these statistics.
GOLDEN = {
    "kurtosis_y_beam": 10.330327033996582,
    "kurtosis_w_beam": 121.90330505371094,
    "kurtosis_y_ant": -0.14002108573913574,
    "nmse_ant_w6": 0.004508119546871076,
    "nmse_ant_w10": 1.750279394894674e-05,
    "nmse_beam_w6": 0.009514461511552032,
    "nmse_beam_w10": 7.845114741362175e-05,
    "bit_gap": 0.8990435616484849,
}


@pytest.fixture(scope="module")
def stats():
    return golden_stats(seed=0, n=128)


def test_golden_keys(stats):
    assert set(stats) == set(GOLDEN)


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_golden_value(stats, key):
    got, want = stats[key], GOLDEN[key]
    np.testing.assert_allclose(
        got, want, rtol=2e-3, atol=1e-8,
        err_msg=f"{key} drifted from the pinned Fig. 7/8 reproduction")


def test_golden_orderings(stats):
    """Structural claims that must survive any re-pin: beamspace is
    spikier than antenna domain (Fig. 7) and needs more bits at equal
    NMSE (Fig. 8)."""
    assert stats["kurtosis_y_beam"] > stats["kurtosis_y_ant"] + 1.0
    assert stats["kurtosis_w_beam"] > stats["kurtosis_y_beam"]
    assert stats["nmse_beam_w6"] > stats["nmse_ant_w6"]
    assert stats["nmse_ant_w10"] < stats["nmse_ant_w6"]
    assert stats["bit_gap"] > 0.0
