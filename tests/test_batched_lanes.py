"""The lane body of the batched fused kernel, and the op's choice of body.

Products narrower than an MXU tile ride the lanes, one product a lane
(`kernels/vp_quant_matmul.vp_quant_matmul_batched_lanes_pallas`); the
rest keep one tile program per product.  Both bodies run the same
quantize cascades and accumulate in f32, so every output here is pinned
BIT-IDENTICAL to the ref oracle and to the tile body: no tolerance.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro import tracing
from repro.core import FXPFormat, VPFormat
from repro.kernels import ops, ref
from repro.kernels.vp_quant_matmul import LANE_BLOCK

# The slot's B-VP formats (bench/configs/nr-fr2-400mhz.json).
W_FXP, W_VP = FXPFormat(12, 11), VPFormat(7, (11, 9, 7, 6))
Y_FXP, Y_VP = FXPFormat(9, 1), VPFormat(7, (1, -1))
FORMATS = (W_FXP, W_VP, Y_FXP, Y_VP)


def _operands(G, M, K, N, seed=0, a_scale=0.01, b_scale=1.0):
    rng = np.random.default_rng(seed)
    a = rng.standard_t(2, (G, M, K)).clip(-8, 8) * a_scale
    b = rng.standard_t(2, (G, K, N)).clip(-8, 8) * b_scale
    return jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)


@pytest.fixture
def counts():
    """Record `repro.tracing` counters over one test; yield a reader of
    the two path counters."""
    tracing.drain()
    tracing.enable()

    def read():
        got = tracing.counters()
        return (got.get("kernels.vp_quant_matmul_batched.lanes", 0),
                got.get("kernels.vp_quant_matmul_batched.tiles", 0))
    try:
        yield read
    finally:
        tracing.disable()
        tracing.drain()


# The slot's (2U, B) x (B, 2) products at small G: one product, a ragged
# lane block, one block under LANE_BLOCK, and more than one block with a
# ragged tail; then ragged M, K and N.
@pytest.mark.parametrize("shape", [
    (1, 16, 64, 2),
    (127, 16, 64, 2),
    (300, 16, 64, 2),
    (LANE_BLOCK + 88, 16, 64, 2),
    (5, 13, 50, 1),
])
# Operands near the formats' ranges too: coarser exponents, and sums of
# 64 products into the hundreds.
@pytest.mark.parametrize("scale", [(0.01, 1.0), (0.12, 16.0)],
                         ids=["small", "wide"])
def test_lanes_bit_identical_to_ref_and_tiles(shape, scale, counts):
    G, M, K, N = shape
    a, b = _operands(G, M, K, N, seed=G, a_scale=scale[0], b_scale=scale[1])
    lanes = ops.vp_quant_matmul_batched(a, b, *FORMATS, interpret=True)
    assert counts() == (1, 0)
    tiles = ops.vp_quant_matmul_batched(
        a, b, *FORMATS, blocks=(M, K, N), interpret=True)
    assert counts() == (1, 1)
    oracle = ref.vp_quant_matmul_batched_ref(a, b, *FORMATS)
    assert lanes.shape == (G, M, N)
    np.testing.assert_array_equal(np.asarray(lanes), np.asarray(oracle))
    np.testing.assert_array_equal(np.asarray(lanes), np.asarray(tiles))


@pytest.mark.parametrize("shape,want", [
    ((44352, 16, 64, 2), LANE_BLOCK),   # the slot: full lane blocks
    ((1, 16, 64, 2), 128),              # never past G rounded to 128
    ((300, 16, 64, 2), 384),
    ((64, 16, 256, 2), 128),            # K past a tile, N not: lanes
    ((8, 256, 64, 2), None),            # 128 products overflow VMEM
    ((8, 256, 256, 256), None),         # MXU-sized products: tiles
])
def test_lane_block_keys_on_shape(shape, want):
    assert ops._lane_block(*shape, FORMATS) == want


def test_tile_sized_products_take_tiles(counts):
    a, b = _operands(1, 256, 256, 256, seed=1)
    out = ops.vp_quant_matmul_batched(a, b, *FORMATS, interpret=True)
    assert counts() == (0, 1)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(ref.vp_quant_matmul_batched_ref(a, b, *FORMATS)))


def test_explicit_blocks_take_tiles(counts):
    a, b = _operands(3, 16, 64, 2, seed=2)
    ops.vp_quant_matmul_batched(
        a, b, *FORMATS, blocks=(16, 64, 2), interpret=True)
    assert counts() == (0, 1)


def test_cspade_masks_take_tiles(counts):
    G = 4
    a, b = _operands(G, 16, 64, 2, seed=3)
    a_act = jnp.asarray([[[1]], [[0]], [[1]], [[0]]], jnp.int32)
    b_act = jnp.zeros((G, 1, 1), jnp.int32)
    out = ops.vp_quant_matmul_batched(
        a, b, *FORMATS, a_act=a_act, b_act=b_act, interpret=True)
    assert counts() == (0, 1)
    want = ref.vp_quant_matmul_batched_ref(
        a, b, *FORMATS, a_act=a_act, b_act=b_act, tiles=(16, 64, 2))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_ref_backend_counts_no_kernel(counts):
    a, b = _operands(3, 16, 64, 2, seed=4)
    ops.vp_quant_matmul_batched(a, b, *FORMATS, interpret=None)
    assert counts() == (0, 0)


@pytest.mark.parametrize("how", ["flat", "vmap"])
def test_wideband_lanes_bit_identical_to_ref(how, counts):
    """A small band through `equalize_wideband` on the lane body, flat
    and under `jax.vmap` over the subcarriers, against the ref path."""
    import jax

    from repro.mimo import (ChannelConfig, OFDMConfig, WidebandCalibrator,
                            equalize_wideband, make_wideband_ensemble,
                            table1_specs)

    ens = make_wideband_ensemble(jax.random.PRNGKey(0), ChannelConfig(),
                                 OFDMConfig(n_subcarriers=4, n_taps=3), 6,
                                 20.0)
    base = next(s for s in table1_specs() if s.name == "B-VP")
    specs = WidebandCalibrator(base).specs_for(ens)
    want = equalize_wideband(specs, ens.w_beam, ens.y_beam, how=how)
    assert counts() == (0, 0)
    got = equalize_wideband(specs, ens.w_beam, ens.y_beam, how=how,
                            interpret=True)
    assert counts() == (1, 0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
