"""The references' own arithmetic: the VP grid from the paper's
definition, the power-of-two scales, and the fp8 rounding of the
control, each against values worked out by hand or by another path."""
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from bench.reference import qwen3, uplink, vp

W12 = (12, 11, 7, (11, 9, 8, 6))        # the served words: FXP(12,11), M=7


@pytest.mark.parametrize("x, want", [
    (0.0, 0.0),
    (0.9999, 63 / 64),            # saturates to 2047, kept at f = 6
    (-1.0, -1.0),                 # -2048 >> 5 = -64 fits 7 bits
    (0.0003, 1 / 2048),           # round(0.61) = 1 at f = 11
    (0.03125, 0.03125),           # 64 does not fit at f = 11: 16 / 2^9
    (0.031, 63 / 2048),           # round(63.49) = 63 fits at f = 11
    (0.49, 31 / 64),              # round(1003.52) = 1004, >> 5 at f = 6
    (-0.0312, -64 / 2048),        # -63.9 -> -64 fits at f = 11
])
def test_vp_grid_by_hand(x, want):
    assert float(vp.grid(jnp.float32(x), *W12)) == want


def test_vp_grid_is_on_the_grid_and_monotone():
    x = jnp.linspace(-1.2, 1.2, 20001)
    g = np.asarray(vp.grid(x, *W12))
    assert np.all(np.diff(g) >= 0)
    assert np.all(np.abs(g) <= 1.0)
    # every value is m 2^-f with |m| < 64 for one of the fractions
    ok = np.zeros_like(g, bool)
    for f in W12[3]:
        m = g * 2.0 ** f
        ok |= (m == np.round(m)) & (np.abs(m) <= 64)
    assert ok.all()


def test_default_fractions_follow_the_paper():
    assert vp.default_fractions(12, 11, 7, 2) == (11, 9, 8, 6)
    assert vp.default_fractions(9, 1, 7, 1) == (1, -1)


def test_pow2_ceil_keeps_powers_of_two():
    got = vp.pow2_ceil(jnp.asarray([0.125, 0.1251, 0.1249, 3.0, 4.0, 0.0]))
    assert np.asarray(got).tolist() == [0.125, 0.25, 0.125, 4.0, 4.0, 1.0]


def test_e4m3_matches_the_float8_type():
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((64, 256))
         * np.exp(rng.uniform(-6, 3, (64, 1)))).astype(np.float32)
    got = np.asarray(qwen3.e4m3(jnp.asarray(x)))
    s = 448.0 / np.abs(x).max(-1, keepdims=True)
    want = (x * s).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) / s
    np.testing.assert_array_equal(got, want)


def test_served_weights_round_each_layer_on_its_own_scale():
    cfg = {"serving": {"quant": {"W": 12, "M": 7, "E": 2}}}
    w = {k: jnp.zeros((2, 4, 4), jnp.bfloat16) for k in qwen3.MATRICES}
    w["wq"] = jnp.asarray([np.full((4, 4), 0.3), np.full((4, 4), 3.0)],
                          jnp.bfloat16)
    got = np.asarray(qwen3.served_weights(cfg, w)["wq"])
    # 0.3 / 0.5 = 0.6 -> 1229 at FXP(12,11) -> 1229 >> 5 = 38 at f = 6
    assert got[0, 0, 0] == 38 / 64 * 0.5
    # 3.0 / 4 = 0.75 -> 1536 -> 48 at f = 6: exact
    assert got[1, 0, 0] == 3.0


def test_equalizer_reference_is_exact_on_grid_values():
    # operands already on their grids at gain 1: the estimate is W y
    w = jnp.asarray([[[0.5 + 0.25j, -0.125j]]], jnp.complex64)   # (1,1,2)
    y = jnp.asarray([[[2.0 - 1.0j, 4.0 + 0.5j]]], jnp.complex64)  # (1,1,2)
    one = jnp.ones((1,), jnp.float32)
    wf, yf = (12, 11, 7, (11, 9, 7, 6)), (9, 1, 7, (1, -1))
    s = np.asarray(uplink.vp_estimate(w, y, one, one, wf, yf))
    want = (0.5 + 0.25j) * (2.0 - 1.0j) + (-0.125j) * (4.0 + 0.5j)
    assert s[0, 0, 0] == pytest.approx(want, abs=0)
