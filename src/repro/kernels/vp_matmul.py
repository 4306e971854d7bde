"""Pallas TPU kernel: VP matrix-multiply engine (the paper's MVM, Sec. IV).

TPU adaptation of the B-VP design:
  * operands arrive either as VP planes (int8 significand + uint8 exponent
    index — 16 HBM bits/element) or, preferably, as PACKED VP words
    (`core.packing`: sign+significand+index in one int8/int16 — 8 bits for
    the Table-I y format, halving HBM traffic);
  * each VMEM tile is dequantized in-register — packed tiles through the
    substrate's `dequant_packed` (shift/mask unpack + O(1) bit-assembled
    scale), plane tiles through `dequant_cascade` — and fed to the MXU in
    f32/bf16;
  * CSPADE is tile-granular: per-tile activity flags are scalar-prefetched
    into SMEM (flattened to 1-D, since SMEM pads every trailing axis) and
    `pl.when` skips the MXU op when BOTH operand tiles are quiet (the
    systolic-array analogue of partial-product muting).  Unmasked launches
    prefetch nothing.

Grid is (m, n, k) with k innermost; a VMEM f32 scratch accumulates across
the k steps and is flushed to the output on the last step.  Launch plumbing
(compat shims, grid-spec construction) lives in `substrate.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import VPFormat
from . import substrate as sub

BM, BK, BN = 256, 256, 256


def _vp_matmul_kernel(
    # scalar-prefetch operands (SMEM)
    a_act_ref, b_act_ref,
    # tensor operands (VMEM tiles): 2 plane refs per operand, or 1 packed
    *refs,
    a_fmt: VPFormat, b_fmt: VPFormat, nm: int, nn: int, nk: int,
    cspade: bool, dtype, packed: bool, batched: bool,
):
    o_ref, acc_ref = refs[-2], refs[-1]
    ki = pl.program_id(3 if batched else 2)
    sub.accum_init(acc_ref, ki)

    def _tile(r):
        return r[0] if batched else r[...]

    def _compute():
        if packed:
            a_ref, b_ref = refs[0], refs[1]
            a = sub.dequant_packed(_tile(a_ref), a_fmt, dtype)
            b = sub.dequant_packed(_tile(b_ref), b_fmt, dtype)
        else:
            a_m_ref, a_i_ref, b_m_ref, b_i_ref = refs[0], refs[1], refs[2], refs[3]
            a = sub.dequant_cascade(_tile(a_m_ref), _tile(a_i_ref), a_fmt, dtype)
            b = sub.dequant_cascade(_tile(b_m_ref), _tile(b_i_ref), b_fmt, dtype)
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if cspade:
        if batched:
            gi, mi, ni = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        else:
            gi, mi, ni = 0, pl.program_id(0), pl.program_id(1)
        active = (a_act_ref[(gi * nm + mi) * nk + ki]
                  | b_act_ref[(gi * nk + ki) * nn + ni]) != 0
        pl.when(active)(_compute)
    else:
        _compute()

    sub.accum_flush(o_ref, acc_ref, ki, nk)


@functools.partial(
    jax.jit,
    static_argnames=(
        "a_fmt", "b_fmt", "interpret", "blocks", "out_dtype", "packed"),
)
def vp_matmul_batched_pallas(
    a_m, a_i, b_m, b_i,
    a_fmt: VPFormat, b_fmt: VPFormat,
    a_act=None, b_act=None,
    interpret: bool = False,
    blocks=(BM, BK, BN),
    out_dtype=jnp.float32,
    packed: bool = False,
):
    """Truly-batched VP x VP -> f32 matmul over a leading batch grid dim.

    a: (G, M, K) planes, b: (G, K, N) planes -> (G, M, N).  Every batch
    element g runs its own (M, K) x (K, N) tile program on the
    (batch, m, n, k) grid — the batch is never folded into the row axis,
    so there is no masked-diagonal FLOP waste (see mimo/mvm_engine.py).

    With ``packed=True`` the operands are packed VP word planes
    (`core.packing.pack_vp`); `a_i` / `b_i` must be None and HBM moves ONE
    word per element instead of two planes.

    `a_act` (G, M/bm, K/bk) / `b_act` (G, K/bk, N/bn) int32 CSPADE
    tile-activity flags (None disables the skip).  M/K/N must be
    tile-multiples (ops.py pads); G is the grid's leading axis and needs
    no padding.
    """
    (bm, bk, bn) = blocks
    G, M, K = a_m.shape
    _, _, N = b_m.shape
    nm, nk, nn = M // bm, K // bk, N // bn
    kernel, masks = sub.cspade_launch(
        _vp_matmul_kernel, a_act, b_act,
        a_fmt=a_fmt, b_fmt=b_fmt, nm=nm, nn=nn, nk=nk, dtype=jnp.float32,
        packed=packed, batched=True,
    )
    copies = 1 if packed else 2
    grid, in_specs, out_specs, semantics = sub.batched_matmul_grid(
        G, nm, nn, nk, bm, bk, bn, a_copies=copies, b_copies=copies)
    operands = (a_m, b_m) if packed else (a_m, a_i, b_m, b_i)
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
    )(*masks, *operands)


@functools.partial(
    jax.jit,
    static_argnames=(
        "a_fmt", "b_fmt", "interpret", "blocks", "out_dtype", "packed"),
)
def vp_matmul_pallas(
    a_m, a_i, b_m, b_i,
    a_fmt: VPFormat, b_fmt: VPFormat,
    a_act=None, b_act=None,
    interpret: bool = False,
    blocks=(BM, BK, BN),
    out_dtype=jnp.float32,
    packed: bool = False,
):
    """VP x VP -> f32 matmul.  a: (M, K) planes, b: (K, N) planes.

    With ``packed=True`` each operand is ONE packed VP word plane
    (`a_i` / `b_i` None) — half the HBM traffic of the two-plane layout.
    `a_act` (M/bm, K/bk) / `b_act` (K/bk, N/bn) int32 CSPADE tile-activity
    flags (None disables the skip logic entirely).
    Shapes must be tile-multiples (ops.py pads).
    """
    (bm, bk, bn) = blocks
    M, K = a_m.shape
    _, N = b_m.shape
    nm, nk, nn = M // bm, K // bk, N // bn
    kernel, masks = sub.cspade_launch(
        _vp_matmul_kernel, a_act, b_act,
        a_fmt=a_fmt, b_fmt=b_fmt, nm=nm, nn=nn, nk=nk, dtype=jnp.float32,
        packed=packed, batched=False,
    )
    a_spec = pl.BlockSpec((bm, bk), lambda mi, ni, ki, *_: (mi, ki))
    b_spec = pl.BlockSpec((bk, bn), lambda mi, ni, ki, *_: (ki, ni))
    copies = 1 if packed else 2
    operands = (a_m, b_m) if packed else (a_m, a_i, b_m, b_i)
    return sub.vp_pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[a_spec] * copies + [b_spec] * copies,
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki, *_: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[sub.vmem((bm, bn), jnp.float32)],
        num_scalar_prefetch=len(masks),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*masks, *operands)
