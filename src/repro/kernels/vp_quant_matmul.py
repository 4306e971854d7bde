"""Pallas TPU kernel: fused FXP2VP quantize + VP matmul (float in, f32 out).

The unfused path materializes (significand, index) planes in HBM between
`vp_quant` and `vp_matmul`; serving MVMs quantize operands immediately
before the product, so the extra round-trip is pure HBM traffic.  This
kernel folds the Fig. 3 quantize cascade into the matmul's VMEM tiles:
each float operand tile is quantized in-register, pushed straight through
the scale-LUT dequant (so the MXU sees exactly the VP-rounded reals the
unfused path would), and accumulated — one `pallas_call`, no quantized
plane ever touching HBM.

The tradeoff: each A tile is visited (and re-quantized) once per n-step
and each B tile once per m-step, so the cascade work scales with the grid
fan-out while the saved HBM traffic is fixed — fusion wins when the
output grid is a few tiles per axis (the serving-MVM shape), not for
huge square matmuls.  Callers that reuse quantized operands across many
products (or large grids) should prefer vp_quant + vp_matmul;
mvm_engine gates its fused default on exactly this.

CSPADE tile-activity masks work exactly as in `vp_matmul` (flat
scalar-prefetch flags + `pl.when` skip).  Numerics are bit-identical to
`vp_quant` -> `vp_matmul`, which is what tests/test_substrate_kernels.py
asserts against the ref oracles.

Batches of products smaller than one MXU tile (the equalizer's
(2U, B) x (B, 2) = (16, 64) x (64, 2)) take a second batched body,
`vp_quant_matmul_batched_lanes_pallas`: the batch rides the 128 lanes,
one product a lane, and a grid step covers a block of `LANE_BLOCK`
products.  The operands arrive as (M·K, G) and (N·K, G) planes, the
cascade runs on them as on any tile, and each output row is K f32
products summed over the sublanes on the VPU — the paper's MAC array,
one lane a product.  Nothing is padded to a 128 x 128 tile: where the
tile grid would run each (16, 64) x (64, 2) product as one 16 x 128 x
128 MXU step, with b padded 64x in HBM, the lane body reads only the
operands' own elements (and G rounded up to the lane block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import FXPFormat, VPFormat
from . import substrate as sub

BM, BK, BN = 256, 256, 256
LANES = 128            # the TPU's lane count
LANE_BLOCK = 512       # products a grid step of the lane body, at most


def _vp_quant_matmul_kernel(
    # scalar-prefetch operands (SMEM)
    a_act_ref, b_act_ref,
    # tensor operands (VMEM tiles, float)
    a_ref, b_ref,
    # outputs / scratch
    o_ref, acc_ref,
    *, a_fxp: FXPFormat, a_vp: VPFormat, b_fxp: FXPFormat, b_vp: VPFormat,
    nm: int, nn: int, nk: int, cspade: bool, dtype,
):
    ki = pl.program_id(2)
    sub.accum_init(acc_ref, ki)

    def _compute():
        a = sub.quantize_dequant_cascade(a_ref[...], a_fxp, a_vp, dtype)
        b = sub.quantize_dequant_cascade(b_ref[...], b_fxp, b_vp, dtype)
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if cspade:
        mi, ni = pl.program_id(0), pl.program_id(1)
        active = (a_act_ref[mi * nk + ki] | b_act_ref[ki * nn + ni]) != 0
        pl.when(active)(_compute)
    else:
        _compute()

    sub.accum_flush(o_ref, acc_ref, ki, nk)


def _vp_quant_matmul_batched_kernel(
    # scalar-prefetch operands (SMEM)
    a_act_ref, b_act_ref,
    # tensor operands (VMEM tiles, float)
    a_ref, b_ref,
    # outputs / scratch
    o_ref, acc_ref,
    *, a_fxp: FXPFormat, a_vp: VPFormat, b_fxp: FXPFormat, b_vp: VPFormat,
    nm: int, nn: int, nk: int, cspade: bool, dtype,
):
    ki = pl.program_id(3)
    sub.accum_init(acc_ref, ki)

    def _compute():
        a = sub.quantize_dequant_cascade(a_ref[0], a_fxp, a_vp, dtype)
        b = sub.quantize_dequant_cascade(b_ref[0], b_fxp, b_vp, dtype)
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if cspade:
        gi, mi, ni = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        active = (a_act_ref[(gi * nm + mi) * nk + ki]
                  | b_act_ref[(gi * nk + ki) * nn + ni]) != 0
        pl.when(active)(_compute)
    else:
        _compute()

    sub.accum_flush(o_ref, acc_ref, ki, nk)


@functools.partial(
    jax.jit,
    static_argnames=(
        "a_fxp", "a_vp", "b_fxp", "b_vp", "interpret", "blocks", "out_dtype"),
)
def vp_quant_matmul_batched_pallas(
    a, b,
    a_fxp: FXPFormat, a_vp: VPFormat,
    b_fxp: FXPFormat, b_vp: VPFormat,
    a_act=None, b_act=None,
    interpret: bool = False,
    blocks=(BM, BK, BN),
    out_dtype=jnp.float32,
):
    """Truly-batched fused quantize+matmul: (G, M, K) x (G, K, N) floats.

    Each batch element runs its own tile program on the (batch, m, n, k)
    grid; the Fig. 3 quantize cascade runs in-register on every operand
    tile exactly as in the unbatched fused kernel, so numerics are
    bit-identical to `vp_quant` -> `vp_matmul_batched` per batch element.
    `a_act` (G, M/bm, K/bk) / `b_act` (G, K/bk, N/bn) CSPADE flags.
    """
    (bm, bk, bn) = blocks
    G, M, K = a.shape
    _, _, N = b.shape
    nm, nk, nn = M // bm, K // bk, N // bn
    kernel, masks = sub.cspade_launch(
        _vp_quant_matmul_batched_kernel, a_act, b_act,
        a_fxp=a_fxp, a_vp=a_vp, b_fxp=b_fxp, b_vp=b_vp,
        nm=nm, nn=nn, nk=nk, dtype=jnp.float32,
    )
    grid, in_specs, out_specs, semantics = sub.batched_matmul_grid(
        G, nm, nn, nk, bm, bk, bn, a_copies=1, b_copies=1)
    return sub.vp_pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct((G, M, N), out_dtype),
        scratch_shapes=[sub.vmem((bm, bn), jnp.float32)],
        num_scalar_prefetch=len(masks),
        dimension_semantics=semantics,
        interpret=interpret,
    )(*masks, a, b)


def _vp_quant_matmul_lanes_kernel(
    a_ref, b_ref, o_ref,
    *, a_fxp: FXPFormat, a_vp: VPFormat, b_fxp: FXPFormat, b_vp: VPFormat,
    m: int, k: int, n: int,
):
    """One lane block of products: a (m·k, gl), b (n·k, gl) -> (m·n, gl).

    Row i·k + kk of a holds a[:, i, kk] and row j·k + kk of b holds
    b[:, kk, j], one product a lane.  b is quantized once; each of the
    m (k, gl) slabs of a is quantized in turn, multiplied by each of
    the n slabs of b and summed over its k sublanes, in f32.
    """
    f32 = jnp.float32
    b = sub.quantize_dequant_cascade(b_ref[...], b_fxp, b_vp, f32)
    for i in range(m):
        a = sub.quantize_dequant_cascade(
            a_ref[i * k:(i + 1) * k, :], a_fxp, a_vp, f32)
        for j in range(n):
            r = i * n + j
            o_ref[r:r + 1, :] = jnp.sum(
                a * b[j * k:(j + 1) * k, :], axis=0,
                keepdims=True).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "a_fxp", "a_vp", "b_fxp", "b_vp", "m", "n", "lane_block",
        "interpret", "out_dtype"),
)
def vp_quant_matmul_batched_lanes_pallas(
    a, b,
    a_fxp: FXPFormat, a_vp: VPFormat,
    b_fxp: FXPFormat, b_vp: VPFormat,
    m: int, n: int, lane_block: int,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Batched fused quantize+matmul with the batch on the lanes.

    a (m·K, G) and b (n·K, G) floats, laid out as the lane kernel reads
    them (row i·K + k of a is a[:, i, k], row j·K + k of b is
    b[:, k, j]), give (m·n, G), row i·n + j the (i, j) output of each
    product.  G is a multiple of `lane_block`, itself a multiple of 128;
    K is a multiple of 8.  Same cascades and formats as the tile body.
    """
    rows_a, G = a.shape
    k = rows_a // m
    kernel = functools.partial(
        _vp_quant_matmul_lanes_kernel,
        a_fxp=a_fxp, a_vp=a_vp, b_fxp=b_fxp, b_vp=b_vp, m=m, k=k, n=n)
    return sub.vp_pallas_call(
        kernel,
        grid=(G // lane_block,),
        in_specs=[
            pl.BlockSpec((m * k, lane_block), lambda g: (0, g)),
            pl.BlockSpec((n * k, lane_block), lambda g: (0, g)),
        ],
        out_specs=pl.BlockSpec((m * n, lane_block), lambda g: (0, g)),
        out_shape=jax.ShapeDtypeStruct((m * n, G), out_dtype),
        dimension_semantics=("parallel",),
        interpret=interpret,
    )(a, b)


@functools.partial(
    jax.jit,
    static_argnames=(
        "a_fxp", "a_vp", "b_fxp", "b_vp", "interpret", "blocks", "out_dtype"),
)
def vp_quant_matmul_pallas(
    a, b,
    a_fxp: FXPFormat, a_vp: VPFormat,
    b_fxp: FXPFormat, b_vp: VPFormat,
    a_act=None, b_act=None,
    interpret: bool = False,
    blocks=(BM, BK, BN),
    out_dtype=jnp.float32,
):
    """Fused quantize+matmul: float a (M, K) x float b (K, N) -> (M, N).

    `a_act` (M/bm, K/bk) / `b_act` (K/bk, N/bn) int32 CSPADE tile-activity
    flags (None disables the skip logic).  Shapes must be tile-multiples
    (ops.py pads; zero padding quantizes to (m=0, i=0) and contributes 0).
    """
    (bm, bk, bn) = blocks
    M, K = a.shape
    _, N = b.shape
    nm, nk, nn = M // bm, K // bk, N // bn
    kernel, masks = sub.cspade_launch(
        _vp_quant_matmul_kernel, a_act, b_act,
        a_fxp=a_fxp, a_vp=a_vp, b_fxp=b_fxp, b_vp=b_vp,
        nm=nm, nn=nn, nk=nk, dtype=jnp.float32,
    )
    return sub.vp_pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki, *_: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki, *_: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki, *_: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[sub.vmem((bm, bn), jnp.float32)],
        num_scalar_prefetch=len(masks),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*masks, a, b)
