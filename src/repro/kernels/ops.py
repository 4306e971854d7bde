"""Public ops: padding, backend dispatch (TPU kernel vs CPU ref), reshaping.

Models and the MIMO application call these; they never touch pallas_call
directly.  Dispatch is `substrate.resolve_backend` in every op: on a TPU
backend the Pallas kernels run natively; elsewhere the pure-jnp refs run
(same math — the refs ARE the oracles the kernels are tested against), so
the dry-run lowers a graph with identical FLOP/byte structure.
`interpret=True` forces the Pallas kernel body through the interpreter on
any backend (used by the kernel tests); an explicit `interpret=False`
means "don't interpret" and still falls back to the refs off-TPU.

Two PR-3 layers live here:

  * PACKED operands.  `vp_quant(..., packed=True)` emits one packed VP
    word plane (`core.packing`) instead of the two-plane layout; the
    matmul/dequant ops accept EITHER layout — pass the packed plane as
    the significand argument with the index argument None.  Packed kernels
    move half the HBM bytes; outputs are bit-identical (the unpack +
    bit-assembled dequant reproduce the plane path exactly;
    tests/test_packing.py pins it).
  * AUTOTUNED blocks.  Every matmul op takes `blocks=None` by default and
    resolves it through `kernels.autotune`: a persisted measured-best
    tiling when one is cached for (kernel, shape, formats, backend), else
    a shape-clamped heuristic that never tiles beyond the padded operand
    shape — so small operands (the MVM engine's (2U, B) x (B, 2)) stop
    padding up to 256^3 tiles.  CSPADE masks pin their grid: pass
    explicit `blocks` alongside masks.

The PR-9 layer: the packed matmul ops are DIFFERENTIABLE.  Each carries
a `jax.custom_vjp` rule whose backward passes are themselves Pallas
kernels over packed words (`vp_bwd_matmul`): dL/dx comes from the
transposed unpack-cascade kernel (`vp_matmul_dx`) without ever
materializing the f32 weight plane; packed-word operands get symbolic
`float0` cotangents (frozen integer storage); the float operands of
`vp_quant_matmul` / `vp_qat_matmul` get straight-through-estimator
gradients, with the quantized residuals saved as PACKED words
(`storage_bits` per element instead of a float plane).  The rules are
grad-checked bit-identical to autodiff through the dequant oracles on
the ref backend (tests/test_train_vjp.py) and linted by JX-BWDMAT.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.analysis import contracts, vmem
from repro.core.formats import FXPFormat, VPFormat
from repro.core import packing as pk
from . import autotune, ref, substrate
from .vp_attention import (
    flash_prefill_pallas,
    vp_decode_attention_pallas,
    vp_paged_decode_attention_pallas,
)
from .vp_quant import vp_quant_pallas, vp_quant_packed_pallas
from .vp_dequant import vp_dequant_pallas, vp_dequant_packed_pallas
from .vp_dequant_matmul import vp_dequant_matmul_pallas
from .vp_bwd_matmul import vp_matmul_dx_pallas, vp_matmul_dw_pallas
from .vp_matmul import vp_matmul_pallas, vp_matmul_batched_pallas
from .vp_block_matmul import block_vp_matmul_pallas
from .vp_quant_matmul import (
    LANE_BLOCK,
    LANES,
    vp_quant_matmul_pallas,
    vp_quant_matmul_batched_lanes_pallas,
    vp_quant_matmul_batched_pallas,
)


def _float0_zeros(x):
    """Symbolic-zero cotangent for an integer primal (packed VP words are
    frozen storage: there is no meaningful gradient w.r.t. bit patterns)."""
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


def _static_blocks(blocks):
    """Hashable `blocks` for custom_vjp nondiff argnums."""
    return None if blocks is None else tuple(int(b) for b in blocks)


def _static_dtype(dtype):
    """Canonical dtype NAME for custom_vjp nondiff argnums — `np.dtype`
    instances are rejected by the custom_vjp arg flattener ("not a valid
    JAX type"), strings pass through and every consumer re-canonicalizes.
    """
    return jnp.dtype(dtype).name


def _pad2(x, br, bc, value=0):
    R, C = x.shape
    pr, pc = (-R) % br, (-C) % bc
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)), constant_values=value)
    return x


def _pad3(x, br, bc, value=0):
    """Pad the trailing two dims of a (G, R, C) batch to tile multiples."""
    _, R, C = x.shape
    pr, pc = (-R) % br, (-C) % bc
    if pr or pc:
        x = jnp.pad(x, ((0, 0), (0, pr), (0, pc)), constant_values=value)
    return x


def _elementwise_block(R: int, C: int, backend: str) -> Tuple[int, int]:
    """Shape-clamped tile for the elementwise (quant/dequant) kernels —
    same policy as `autotune.heuristic_blocks`, two axes.  On the
    TPU-native backend the tile is floored to the int8-plane Mosaic
    minimum (32 sublanes, 128 lanes); interpret keeps the snug clamp.
    """
    b = autotune.heuristic_blocks(R, C, 1)
    if backend == "native":
        return max(b[0], 32), max(b[1], 128)
    return b[0], b[1]


def _resolve_blocks(kernel, shape, formats, backend, blocks, masks):
    """Autotune-resolve `blocks=None`.

    CSPADE masks pin their tile grid, so masked calls with `blocks=None`
    resolve with `use_cache=False` — the deterministic heuristic (+
    native floor) only, never a tuned cache entry, whose grid the masks
    were not built on; `_check_masks` then validates the grid loudly
    either way.

    Mesh awareness rides the cache key (`autotune.make_key` appends the
    active `mesh_scope` segment) and the VMEM contract: `shape` here is
    whatever the op was CALLED with, which under shard_map is the
    per-shard local operand — so both the cache lookup and the
    feasibility proof reason about the tile each device actually
    launches, never the unsharded logical shape.
    """
    resolved = autotune.resolve_blocks(
        kernel, shape, formats, backend, blocks, use_cache=masks is None)
    if backend == "native":
        # Off-TPU backends stage no VMEM; on TPU an over-budget tile
        # dies at Mosaic lowering, so fail it here with the accounting.
        contracts.require_vmem_feasible(
            kernel, tuple(resolved), tuple(formats),
            tuple(int(d) for d in shape), what=kernel)
    return resolved


def _check_masks(a_act, b_act, M, K, N, blocks):
    """Validate optional CSPADE masks against the kernel tile grid.

    Out-of-grid masks would be silently mis-indexed in the kernel (Pallas
    clamps out-of-bounds scalar reads), so mismatches must fail loudly."""
    if (a_act is None) != (b_act is None):
        raise ValueError(
            "CSPADE masks come in pairs: pass both a_act and b_act or neither")
    if a_act is None:
        return
    bm, bk, bn = blocks
    if M % bm or K % bk or N % bn:
        raise ValueError("CSPADE masks require tile-aligned operand shapes")
    want_a, want_b = (M // bm, K // bk), (K // bk, N // bn)
    if tuple(a_act.shape) != want_a or tuple(b_act.shape) != want_b:
        raise ValueError(
            f"CSPADE mask shapes {tuple(a_act.shape)}/{tuple(b_act.shape)} "
            f"do not match the blocks={blocks} tile grid "
            f"(want {want_a}/{want_b}); rebuild the masks on this grid")


def _check_masks_batched(a_act, b_act, G, M, K, N, blocks):
    """Validate optional batched CSPADE masks against the (G, tile) grid."""
    if (a_act is None) != (b_act is None):
        raise ValueError(
            "CSPADE masks come in pairs: pass both a_act and b_act or neither")
    if a_act is None:
        return
    bm, bk, bn = blocks
    if M % bm or K % bk or N % bn:
        raise ValueError("CSPADE masks require tile-aligned operand shapes")
    want_a = (G, M // bm, K // bk)
    want_b = (G, K // bk, N // bn)
    if tuple(a_act.shape) != want_a or tuple(b_act.shape) != want_b:
        raise ValueError(
            f"batched CSPADE mask shapes {tuple(a_act.shape)}/"
            f"{tuple(b_act.shape)} do not match the blocks={blocks} grid "
            f"(want {want_a}/{want_b}); rebuild the masks on this grid")


def _unpack_pair(x_m, x_i, fmt: VPFormat):
    """Either-layout normalization: (packed, None) -> planes, else pass."""
    if x_i is None:
        return pk.unpack_vp(x_m, fmt)
    return x_m, x_i


def vp_quant(x, fxp: FXPFormat, vp: VPFormat,
             interpret: Optional[bool] = None, packed: bool = False):
    """float tensor (any rank) -> VP-quantized planes, same shape.

    ``packed=False``: (significand, index) two-plane layout.
    ``packed=True``: ONE packed word plane (`core.packing` layout,
    `vp.storage_bits` bits/element) — the layout every matmul op accepts
    as (plane, None).
    """
    contracts.require_quant_safe(fxp, vp, "vp_quant")
    backend = substrate.resolve_backend(interpret)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1]) if x.ndim != 2 else x
    if backend == "ref":
        if packed:
            return ref.vp_quant_packed_ref(x2, fxp, vp).reshape(shape)
        m, i = ref.vp_quant_ref(x2, fxp, vp)
    else:
        R, C = x2.shape
        blk = _elementwise_block(R, C, backend)
        xp = _pad2(x2, *blk)
        if packed:
            w = vp_quant_packed_pallas(
                xp, fxp, vp, interpret=(backend == "interpret"), block=blk)
            return w[:R, :C].reshape(shape)
        m, i = vp_quant_pallas(
            xp, fxp, vp, interpret=(backend == "interpret"), block=blk)
        m, i = m[:R, :C], i[:R, :C]
    return m.reshape(shape), i.reshape(shape)


def vp_dequant(m, i=None, vp: VPFormat = None, dtype=jnp.float32,
               interpret: Optional[bool] = None):
    """(significand, index) planes — or packed words with ``i=None`` —
    back to real values: ``vp_dequant(m, i, fmt)`` or
    ``vp_dequant(w, None, fmt)``."""
    if isinstance(i, VPFormat) or vp is None:
        raise TypeError(
            "vp_dequant takes (m, i, vp) for planes or (w, None, vp) for "
            "packed words — the format is always the THIRD argument")
    contracts.require_format_serviceable(vp, "vp_dequant")
    backend = substrate.resolve_backend(interpret)
    packed = i is None
    shape = m.shape
    m2 = m.reshape(-1, shape[-1]) if m.ndim != 2 else m
    if backend == "ref":
        if packed:
            out = ref.vp_dequant_packed_ref(m2, vp, dtype)
        else:
            i2 = i.reshape(-1, shape[-1]) if i.ndim != 2 else i
            out = ref.vp_dequant_ref(m2, i2, vp, dtype)
    else:
        R, C = m2.shape
        blk = _elementwise_block(R, C, backend)
        if packed:
            out = vp_dequant_packed_pallas(
                _pad2(m2, *blk), vp, dtype,
                interpret=(backend == "interpret"), block=blk)
        else:
            i2 = i.reshape(-1, shape[-1]) if i.ndim != 2 else i
            out = vp_dequant_pallas(
                _pad2(m2, *blk), _pad2(i2, *blk), vp, dtype,
                interpret=(backend == "interpret"), block=blk)
        out = out[:R, :C]
    return out.reshape(shape)


def vp_matmul(
    a_m, a_i, b_m, b_i,
    a_fmt: VPFormat, b_fmt: VPFormat,
    a_act=None, b_act=None,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
):
    """(M,K) x (K,N) VP matmul; CSPADE masks optional (tile grid = blocks).

    Operands may be two-plane (m, i) pairs OR packed word planes (pass
    the packed plane as `a_m`/`b_m` with `a_i`/`b_i` None); the packed
    kernel path moves one HBM word per element.  `blocks=None` resolves
    through the autotuner (cache, else shape-clamped heuristic).
    """
    contracts.check_formats(a_fmt, b_fmt, what="vp_matmul")
    if a_i is None and b_i is None and a_act is None and b_act is None:
        # Packed unmasked path carries the custom-VJP rule (float0
        # cotangents for the frozen word planes); forward is unchanged.
        return _vp_matmul_packed_vjp(
            a_m, b_m, a_fmt, b_fmt, _static_dtype(out_dtype),
            _static_blocks(blocks), interpret)
    M, K = a_m.shape
    _, N = b_m.shape
    backend = substrate.resolve_backend(interpret)
    packed = a_i is None and b_i is None
    # The operand layout changes the kernel body (and its HBM traffic),
    # so packed and plane launches tune/cache independently.
    blocks = _resolve_blocks(
        "vp_matmul_packed" if packed else "vp_matmul",
        (M, K, N), (a_fmt, b_fmt), backend, blocks, a_act)
    _check_masks(a_act, b_act, M, K, N, blocks)
    if backend == "ref":
        if packed:
            return ref.vp_matmul_packed_ref(
                a_m, b_m, a_fmt, b_fmt,
                a_act=a_act, b_act=b_act, tiles=blocks, out_dtype=out_dtype)
        a_m, a_i = _unpack_pair(a_m, a_i, a_fmt)
        b_m, b_i = _unpack_pair(b_m, b_i, b_fmt)
        return ref.vp_matmul_ref(
            a_m, a_i, b_m, b_i, a_fmt, b_fmt,
            a_act=a_act, b_act=b_act, tiles=blocks, out_dtype=out_dtype)
    if (a_i is None) != (b_i is None):
        # Mixed layouts: normalize to planes (no kernel for the mix).
        a_m, a_i = _unpack_pair(a_m, a_i, a_fmt)
        b_m, b_i = _unpack_pair(b_m, b_i, b_fmt)
        packed = False
    bm, bk, bn = blocks
    if packed:
        ap, bp = _pad2(a_m, bm, bk), _pad2(b_m, bk, bn)
        out = vp_matmul_pallas(
            ap, None, bp, None, a_fmt, b_fmt,
            a_act=a_act, b_act=b_act,
            interpret=(backend == "interpret"), blocks=blocks,
            out_dtype=out_dtype, packed=True)
        return out[:M, :N]
    am, ai = _pad2(a_m, bm, bk), _pad2(a_i, bm, bk)
    bm_, bi = _pad2(b_m, bk, bn), _pad2(b_i, bk, bn)
    out = vp_matmul_pallas(
        am, ai, bm_, bi, a_fmt, b_fmt,
        a_act=a_act, b_act=b_act,
        interpret=(backend == "interpret"), blocks=blocks,
        out_dtype=out_dtype)
    return out[:M, :N]


def vp_matmul_dx(
    g, w,
    w_fmt: VPFormat,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
):
    """Backward op: upstream cotangent g (M, N) @ dequant(w (K, N))^T.

    The TRANSPOSED serving matmul — the dL/dx half of every packed-weight
    VJP.  The same packed word plane the forward read is consumed
    directly by the kernel (unpack + bit-assembled scale in VMEM,
    contracted over its OUTPUT dim via `dot_general`), so the backward
    pass moves the same `storage_bits`-per-element HBM traffic as the
    forward and never materializes the f32 weight plane.
    """
    contracts.require_format_serviceable(w_fmt, "vp_matmul_dx")
    M, N = g.shape
    K, _ = w.shape
    backend = substrate.resolve_backend(interpret)
    if backend == "ref":
        # Tile-independent oracle: exactly the dot_general XLA's
        # transpose rule emits for the forward, so VJP grad checks are
        # bit-identical against autodiff-through-dequant on this backend.
        return ref.vp_matmul_dx_ref(g, w, w_fmt, out_dtype=out_dtype)
    blocks = _resolve_blocks(
        "vp_matmul_dx", (M, K, N), (w_fmt,), backend, blocks, None)
    bm, bk, bn = blocks
    gp, wp = _pad2(g, bm, bn), _pad2(w, bk, bn)
    out = vp_matmul_dx_pallas(
        gp, wp, w_fmt,
        interpret=(backend == "interpret"), blocks=blocks,
        out_dtype=out_dtype)
    return out[:M, :K]


def vp_matmul_dw(
    a_w, g,
    a_fmt: VPFormat,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
):
    """Backward op: dequant(a_w (M, K) packed words)^T @ g (M, N).

    The dL/dB half of the fused quantize-matmul VJP under the
    straight-through estimator: `a_w` is the QUANTIZED first operand
    saved as the VJP residual in packed form (`storage_bits` per element
    instead of a float activation plane), unpacked per tile and reduced
    over the batch dim into an f32 accumulator.
    """
    contracts.require_format_serviceable(a_fmt, "vp_matmul_dw")
    M, K = a_w.shape
    _, N = g.shape
    backend = substrate.resolve_backend(interpret)
    if backend == "ref":
        return ref.vp_matmul_dw_ref(a_w, g, a_fmt, out_dtype=out_dtype)
    blocks = _resolve_blocks(
        "vp_matmul_dw", (M, K, N), (a_fmt,), backend, blocks, None)
    bm, bk, bn = blocks
    ap, gp = _pad2(a_w, bm, bk), _pad2(g, bm, bn)
    out = vp_matmul_dw_pallas(
        ap, gp, a_fmt,
        interpret=(backend == "interpret"), blocks=blocks,
        out_dtype=out_dtype)
    return out[:K, :N]


def _vp_dequant_matmul_impl(x, w, w_fmt, out_dtype, blocks, interpret):
    M, K = x.shape
    _, N = w.shape
    backend = substrate.resolve_backend(interpret)
    if backend == "ref":
        # The ref's math is tile-independent: skip block resolution
        # entirely (no cache reads, no per-tiling jit signatures).
        return ref.vp_dequant_matmul_ref(x, w, w_fmt, out_dtype=out_dtype)
    blocks = _resolve_blocks(
        "vp_dequant_matmul", (M, K, N), (w_fmt,), backend, blocks, None)
    bm, bk, bn = blocks
    xp, wp = _pad2(x, bm, bk), _pad2(w, bk, bn)
    out = vp_dequant_matmul_pallas(
        xp, wp, w_fmt,
        interpret=(backend == "interpret"), blocks=blocks,
        out_dtype=out_dtype)
    return out[:M, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _vp_dequant_matmul_vjp(x, w, w_fmt, out_dtype, x_dtype, blocks,
                           interpret):
    return _vp_dequant_matmul_impl(
        x, w, w_fmt, np.dtype(out_dtype), blocks, interpret)


def _vp_dequant_matmul_fwd(x, w, w_fmt, out_dtype, x_dtype, blocks,
                           interpret):
    out = _vp_dequant_matmul_impl(
        x, w, w_fmt, np.dtype(out_dtype), blocks, interpret)
    # The packed words ARE the residual — `storage_bits` per element,
    # where autodiff through a dequant would have checkpointed the f32
    # weight plane.
    return out, (w,)


def _vp_dequant_matmul_bwd(w_fmt, out_dtype, x_dtype, blocks, interpret,
                           res, g):
    (w,) = res
    dx = vp_matmul_dx(
        g, w, w_fmt, blocks=blocks, interpret=interpret,
        out_dtype=np.dtype(x_dtype))
    # Packed words are frozen integer storage: symbolic-zero cotangent.
    return dx, _float0_zeros(w)


_vp_dequant_matmul_vjp.defvjp(_vp_dequant_matmul_fwd, _vp_dequant_matmul_bwd)


def vp_dequant_matmul(
    x, w,
    w_fmt: VPFormat,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
    out_dtype=None,
):
    """Serving matmul: real x (M, K) @ dequant(w (K, N) packed VP words).

    THE model-zoo decode/prefill hot path (`models.layers.qdot`, mode
    "vp"): one real operand, one packed-word operand consumed directly by
    the kernel — no f32 weight plane in HBM.  `blocks=None` resolves
    through the autotuner, so skinny decode shapes (M = batch) launch the
    tuned/clamped tiling instead of padding up to 256^3 (see
    `autotune.tune_serving_decode` for the M=1..B profile).  `out_dtype`
    defaults to the activation dtype (the models' compute dtype).

    DIFFERENTIABLE in x: the custom VJP computes dL/dx with the
    transposed packed-word kernel (`vp_matmul_dx`) from the same word
    plane, and gives the frozen integer words a symbolic `float0`
    cotangent — so QAT/fine-tune graphs backprop through the serving
    path without an f32 weight plane in either direction.
    """
    contracts.require_format_serviceable(w_fmt, "vp_dequant_matmul")
    out_dtype = x.dtype if out_dtype is None else out_dtype
    return _vp_dequant_matmul_vjp(
        x, w, w_fmt, _static_dtype(out_dtype), _static_dtype(x.dtype),
        _static_blocks(blocks), interpret)


def _vp_quant_matmul_impl(
        a, b, a_fxp, a_vp, b_fxp, b_vp, out_dtype, blocks, interpret):
    M, K = a.shape
    _, N = b.shape
    backend = substrate.resolve_backend(interpret)
    blocks = _resolve_blocks(
        "vp_quant_matmul", (M, K, N), (a_fxp, a_vp, b_fxp, b_vp),
        backend, blocks, None)
    if backend == "ref":
        return ref.vp_quant_matmul_ref(
            a, b, a_fxp, a_vp, b_fxp, b_vp,
            tiles=blocks, out_dtype=out_dtype)
    bm, bk, bn = blocks
    ap, bp = _pad2(a, bm, bk), _pad2(b, bk, bn)
    out = vp_quant_matmul_pallas(
        ap, bp, a_fxp, a_vp, b_fxp, b_vp,
        interpret=(backend == "interpret"), blocks=blocks,
        out_dtype=out_dtype)
    return out[:M, :N]


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
def _vp_quant_matmul_vjp(
        a, b, a_fxp, a_vp, b_fxp, b_vp, out_dtype, a_dtype, b_dtype,
        blocks, interpret):
    return _vp_quant_matmul_impl(
        a, b, a_fxp, a_vp, b_fxp, b_vp, np.dtype(out_dtype), blocks,
        interpret)


def _vp_quant_matmul_fwd(
        a, b, a_fxp, a_vp, b_fxp, b_vp, out_dtype, a_dtype, b_dtype,
        blocks, interpret):
    out = _vp_quant_matmul_impl(
        a, b, a_fxp, a_vp, b_fxp, b_vp, np.dtype(out_dtype), blocks,
        interpret)
    # STE residuals are the QUANTIZED operands saved as PACKED words —
    # `storage_bits` per element each, where autodiff through a fake
    # quant would checkpoint both float planes.
    a_w = vp_quant(a, a_fxp, a_vp, interpret=interpret, packed=True)
    b_w = vp_quant(b, b_fxp, b_vp, interpret=interpret, packed=True)
    return out, (a_w, b_w)


def _vp_quant_matmul_bwd(
        a_fxp, a_vp, b_fxp, b_vp, out_dtype, a_dtype, b_dtype, blocks,
        interpret, res, g):
    a_w, b_w = res
    # Straight-through estimator: the quantizer Jacobians are taken as
    # identity, so both grads are packed-word matmuls over the quantized
    # residuals — da = g qb^T by the transposed unpack-cascade kernel,
    # db = qa^T g by the second-operand kernel, both reduced in f32.
    da = vp_matmul_dx(
        g, b_w, b_vp, interpret=interpret, out_dtype=np.dtype(a_dtype))
    db = vp_matmul_dw(
        a_w, g, a_vp, interpret=interpret, out_dtype=np.dtype(b_dtype))
    return da, db


_vp_quant_matmul_vjp.defvjp(_vp_quant_matmul_fwd, _vp_quant_matmul_bwd)


def vp_quant_matmul(
    a, b,
    a_fxp: FXPFormat, a_vp: VPFormat,
    b_fxp: FXPFormat, b_vp: VPFormat,
    a_act=None, b_act=None,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
):
    """Fused float->VP quantize + matmul: a (M,K) x b (K,N) floats -> (M,N).

    Numerically identical to `vp_quant` on each operand followed by
    `vp_matmul`, without materializing the quantized planes in HBM.
    CSPADE masks follow the `blocks` tile grid and require tile-aligned
    operands (mask calibration needs the planes anyway — see mvm_engine).

    DIFFERENTIABLE (unmasked path) under the straight-through estimator:
    both cotangents come from packed-word Pallas kernels over the
    quantized residuals (see `_vp_quant_matmul_bwd`).  The CSPADE-masked
    path stays forward-only — masks are calibration-time artifacts.
    """
    contracts.require_quant_safe(a_fxp, a_vp, "vp_quant_matmul")
    contracts.require_quant_safe(b_fxp, b_vp, "vp_quant_matmul")
    if a_act is None and b_act is None:
        return _vp_quant_matmul_vjp(
            a, b, a_fxp, a_vp, b_fxp, b_vp, _static_dtype(out_dtype),
            _static_dtype(a.dtype), _static_dtype(b.dtype),
            _static_blocks(blocks), interpret)
    M, K = a.shape
    _, N = b.shape
    backend = substrate.resolve_backend(interpret)
    blocks = _resolve_blocks(
        "vp_quant_matmul", (M, K, N), (a_fxp, a_vp, b_fxp, b_vp),
        backend, blocks, a_act)
    _check_masks(a_act, b_act, M, K, N, blocks)
    if backend == "ref":
        return ref.vp_quant_matmul_ref(
            a, b, a_fxp, a_vp, b_fxp, b_vp,
            a_act=a_act, b_act=b_act, tiles=blocks, out_dtype=out_dtype)
    bm, bk, bn = blocks
    ap, bp = _pad2(a, bm, bk), _pad2(b, bk, bn)
    out = vp_quant_matmul_pallas(
        ap, bp, a_fxp, a_vp, b_fxp, b_vp,
        a_act=a_act, b_act=b_act,
        interpret=(backend == "interpret"), blocks=blocks,
        out_dtype=out_dtype)
    return out[:M, :N]


def _vp_matmul_packed_impl(a_w, b_w, a_fmt, b_fmt, out_dtype, blocks,
                           interpret):
    M, K = a_w.shape
    _, N = b_w.shape
    backend = substrate.resolve_backend(interpret)
    blocks = _resolve_blocks(
        "vp_matmul_packed", (M, K, N), (a_fmt, b_fmt), backend, blocks, None)
    if backend == "ref":
        return ref.vp_matmul_packed_ref(
            a_w, b_w, a_fmt, b_fmt, tiles=blocks, out_dtype=out_dtype)
    bm, bk, bn = blocks
    ap, bp = _pad2(a_w, bm, bk), _pad2(b_w, bk, bn)
    out = vp_matmul_pallas(
        ap, None, bp, None, a_fmt, b_fmt,
        interpret=(backend == "interpret"), blocks=blocks,
        out_dtype=out_dtype, packed=True)
    return out[:M, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _vp_matmul_packed_vjp(a_w, b_w, a_fmt, b_fmt, out_dtype, blocks,
                          interpret):
    return _vp_matmul_packed_impl(
        a_w, b_w, a_fmt, b_fmt, np.dtype(out_dtype), blocks, interpret)


def _vp_matmul_packed_fwd(a_w, b_w, a_fmt, b_fmt, out_dtype, blocks,
                          interpret):
    out = _vp_matmul_packed_impl(
        a_w, b_w, a_fmt, b_fmt, np.dtype(out_dtype), blocks, interpret)
    return out, (a_w, b_w)


def _vp_matmul_packed_bwd(a_fmt, b_fmt, out_dtype, blocks, interpret,
                          res, g):
    # Both operands are frozen integer word planes — there is no
    # gradient w.r.t. bit patterns, only the explicit statement that the
    # rule exists (so traced training graphs do not die trying to
    # transpose through pallas_call).
    a_w, b_w = res
    return _float0_zeros(a_w), _float0_zeros(b_w)


_vp_matmul_packed_vjp.defvjp(_vp_matmul_packed_fwd, _vp_matmul_packed_bwd)


def _vp_qat_matmul_impl(x, w, fxp, vp, blocks, interpret):
    w_q = vp_quant(w.astype(jnp.float32), fxp, vp,
                   interpret=interpret, packed=True)
    out = _vp_dequant_matmul_impl(
        x, w_q, vp, np.dtype(x.dtype), blocks, interpret)
    return out, w_q


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _vp_qat_matmul_vjp(x, w, fxp, vp, w_dtype, blocks, interpret):
    out, _ = _vp_qat_matmul_impl(x, w, fxp, vp, blocks, interpret)
    return out


def _vp_qat_matmul_fwd(x, w, fxp, vp, w_dtype, blocks, interpret):
    out, w_q = _vp_qat_matmul_impl(x, w, fxp, vp, blocks, interpret)
    # Residual = activations + the PACKED quantized weight (what the
    # forward actually multiplied by) — never the f32 weight plane.
    return out, (x, w_q)


def _vp_qat_matmul_bwd(fxp, vp, w_dtype, blocks, interpret, res, g):
    x, w_q = res
    dx = vp_matmul_dx(
        g, w_q, vp, blocks=blocks, interpret=interpret, out_dtype=x.dtype)
    # STE on the master weight: the quantizer's Jacobian is identity, so
    # dW = x^T g reduced in f32 — a plain dense contraction (x is real;
    # no packed operand exists on this side), handed back in the master
    # dtype for the optimizer to step and the next fwd to re-quantize.
    dw = jax.lax.dot_general(
        x.astype(jnp.float32), g.astype(jnp.float32),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(np.dtype(w_dtype))
    return dx, dw


_vp_qat_matmul_vjp.defvjp(_vp_qat_matmul_fwd, _vp_qat_matmul_bwd)


def vp_qat_matmul(
    x, w,
    fxp: FXPFormat, vp: VPFormat,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
):
    """QAT matmul: x (M, K) reals @ quantize-then-dequant(w (K, N) float
    master weights) — the trainable twin of `vp_dequant_matmul`.

    Forward quantizes the float master weight into ONE packed word plane
    (`vp_quant(..., packed=True)`) and runs the packed serving kernel on
    it, so training sees bit-identical numerics to what serving will run.
    Backward is straight-through: dL/dx comes from the transposed
    packed-word kernel over the SAME quantized words (never the float
    plane), dL/dW = x^T g in f32 as if the quantizer were identity.
    `models.layers._qdot_local` rides this when `QuantConfig.qat_mode ==
    "packed"`.
    """
    contracts.require_quant_safe(fxp, vp, "vp_qat_matmul")
    return _vp_qat_matmul_vjp(
        x, w, fxp, vp, _static_dtype(w.dtype), _static_blocks(blocks),
        interpret)


def vp_matmul_batched(
    a_m, a_i, b_m, b_i,
    a_fmt: VPFormat, b_fmt: VPFormat,
    a_act=None, b_act=None,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
):
    """(G,M,K) x (G,K,N) truly-batched VP matmul.

    Each batch element runs its own tile program on the kernel's leading
    batch grid dimension — the scalable replacement for folding G into the
    row axis and discarding off-diagonal columns.  CSPADE masks are per
    (batch, tile): a_act (G, M/bm, K/bk), b_act (G, K/bk, N/bn).
    Packed-word operands: pass the packed planes with `a_i`/`b_i` None.
    """
    contracts.check_formats(a_fmt, b_fmt, what="vp_matmul_batched")
    G, M, K = a_m.shape
    _, _, N = b_m.shape
    backend = substrate.resolve_backend(interpret)
    packed = a_i is None and b_i is None
    blocks = _resolve_blocks(
        "vp_matmul_batched_packed" if packed else "vp_matmul_batched",
        (G, M, K, N), (a_fmt, b_fmt), backend, blocks, a_act)
    _check_masks_batched(a_act, b_act, G, M, K, N, blocks)
    if backend == "ref":
        if packed:
            return ref.vp_matmul_batched_packed_ref(
                a_m, b_m, a_fmt, b_fmt,
                a_act=a_act, b_act=b_act, tiles=blocks, out_dtype=out_dtype)
        a_m, a_i = _unpack_pair(a_m, a_i, a_fmt)
        b_m, b_i = _unpack_pair(b_m, b_i, b_fmt)
        return ref.vp_matmul_batched_ref(
            a_m, a_i, b_m, b_i, a_fmt, b_fmt,
            a_act=a_act, b_act=b_act, tiles=blocks, out_dtype=out_dtype)
    if (a_i is None) != (b_i is None):
        a_m, a_i = _unpack_pair(a_m, a_i, a_fmt)
        b_m, b_i = _unpack_pair(b_m, b_i, b_fmt)
        packed = False
    bm, bk, bn = blocks
    if packed:
        ap, bp = _pad3(a_m, bm, bk), _pad3(b_m, bk, bn)
        out = vp_matmul_batched_pallas(
            ap, None, bp, None, a_fmt, b_fmt,
            a_act=a_act, b_act=b_act,
            interpret=(backend == "interpret"), blocks=blocks,
            out_dtype=out_dtype, packed=True)
        return out[:, :M, :N]
    am, ai = _pad3(a_m, bm, bk), _pad3(a_i, bm, bk)
    bm_, bi = _pad3(b_m, bk, bn), _pad3(b_i, bk, bn)
    out = vp_matmul_batched_pallas(
        am, ai, bm_, bi, a_fmt, b_fmt,
        a_act=a_act, b_act=b_act,
        interpret=(backend == "interpret"), blocks=blocks,
        out_dtype=out_dtype)
    return out[:, :M, :N]


def _lane_block(G: int, M: int, K: int, N: int, formats) -> Optional[int]:
    """Products a step of the lane body for a (G, M, K) x (G, K, N)
    batch, or None where each product keeps its own tile program.

    Products narrower than an MXU tile (N or K under 128 lanes) ride the
    lanes: `LANE_BLOCK` products a step, fewer where G is smaller (a
    multiple of 128 still) or the VMEM model wants fewer; None where even
    128 products a step overflow it.
    """
    if N >= LANES and K >= LANES:
        return None
    shape = (G, M, K, N)
    gl = min(LANE_BLOCK, -(-G // LANES) * LANES)
    while not vmem.vmem_feasible(
            "vp_quant_matmul_batched_lanes", (gl,), formats, shape)[0]:
        if gl == LANES:
            return None
        gl = max(LANES, gl // 2 // LANES * LANES)
    return gl


def _vp_quant_matmul_lanes(a, b, a_fxp, a_vp, b_fxp, b_vp, gl,
                           interpret, out_dtype):
    """(G, M, K) x (G, K, N) through the lane body: the batch moves to
    the lanes, G rounds up to the lane block and K to 8 sublanes (zeros
    quantize to zero and add nothing), and the (M·N, Gp) result moves
    back.  The moves are plain XLA ops the compiler fuses into the
    operands' producers and the result's consumer."""
    G, M, K = a.shape
    N = b.shape[-1]
    Kp, Gp = -(-K // 8) * 8, -(-G // gl) * gl
    with jax.named_scope("pad"):
        at = jnp.pad(a, ((0, Gp - G), (0, 0), (0, Kp - K)))
        at = at.transpose(1, 2, 0).reshape(M * Kp, Gp)
        bt = jnp.pad(b, ((0, Gp - G), (0, Kp - K), (0, 0)))
        bt = bt.transpose(2, 1, 0).reshape(N * Kp, Gp)
    out = vp_quant_matmul_batched_lanes_pallas(
        at, bt, a_fxp, a_vp, b_fxp, b_vp, m=M, n=N, lane_block=gl,
        interpret=interpret, out_dtype=out_dtype)
    with jax.named_scope("pad"):
        return out[:, :G].reshape(M, N, G).transpose(2, 0, 1)


def vp_quant_matmul_batched(
    a, b,
    a_fxp: FXPFormat, a_vp: VPFormat,
    b_fxp: FXPFormat, b_vp: VPFormat,
    a_act=None, b_act=None,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
):
    """Truly-batched fused float->VP quantize + matmul over (G, M, K) x
    (G, K, N) floats.

    Numerically identical to `vp_quant` on each operand followed by
    `vp_matmul_batched`, with no quantized-plane HBM round-trip — ONE
    pallas_call for the whole batch.

    Two bodies, chosen by the shape alone: products narrower than an MXU
    tile (N or K under 128) whose lane block fits VMEM ride the lanes
    (`_lane_block`); larger products, explicit `blocks` and CSPADE masks
    (built on the tile grid) keep one tile program per product.  Each
    kernel lowering counts in `kernels.vp_quant_matmul_batched.lanes` or
    `.tiles` (`repro.tracing`).
    """
    contracts.require_quant_safe(a_fxp, a_vp, "vp_quant_matmul_batched")
    contracts.require_quant_safe(b_fxp, b_vp, "vp_quant_matmul_batched")
    G, M, K = a.shape
    _, _, N = b.shape
    backend = substrate.resolve_backend(interpret)
    gl = None
    if blocks is None and a_act is None:
        gl = _lane_block(G, M, K, N, (a_fxp, a_vp, b_fxp, b_vp))
    if gl is not None and backend != "ref":
        tracing.count("kernels.vp_quant_matmul_batched.lanes")
        return _vp_quant_matmul_lanes(
            a, b, a_fxp, a_vp, b_fxp, b_vp, gl,
            interpret=(backend == "interpret"), out_dtype=out_dtype)
    blocks = _resolve_blocks(
        "vp_quant_matmul_batched", (G, M, K, N),
        (a_fxp, a_vp, b_fxp, b_vp), backend, blocks, a_act)
    _check_masks_batched(a_act, b_act, G, M, K, N, blocks)
    if backend == "ref":
        return ref.vp_quant_matmul_batched_ref(
            a, b, a_fxp, a_vp, b_fxp, b_vp,
            a_act=a_act, b_act=b_act, tiles=blocks, out_dtype=out_dtype)
    tracing.count("kernels.vp_quant_matmul_batched.tiles")
    bm, bk, bn = blocks
    with jax.named_scope("pad"):
        ap, bp = _pad3(a, bm, bk), _pad3(b, bk, bn)
    out = vp_quant_matmul_batched_pallas(
        ap, bp, a_fxp, a_vp, b_fxp, b_vp,
        a_act=a_act, b_act=b_act,
        interpret=(backend == "interpret"), blocks=blocks,
        out_dtype=out_dtype)
    with jax.named_scope("pad"):
        return out[:, :M, :N]


def vp_decode_attention(
    q, k_w, v_w, k_s, v_s, lengths,
    fmt: VPFormat,
    window: Optional[int] = None,
    rolling: bool = False,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
):
    """Single-token decode attention over a PACKED VP KV cache.

    q (B, 1, H, dh); k_w / v_w (B, Smax, KV, dh) packed VP words
    (`core.packing`); k_s / v_s (B, Smax, 1, 1) per-position pow2 cache
    scales; lengths (B,) valid cache lengths.  The cache words feed the
    kernel directly — unpack + bit-assembled scale happen in VMEM, and
    seq tiles entirely outside the valid span (past `lengths`, outside
    the sliding `window`, or past the `rolling` ring's fill level) are
    skipped, so decode work is O(cache_len), not O(Smax).  `blocks=None`
    resolves the (bq, bkv, 1) chunking through the autotuner, keyed on
    (B, Smax, KV, dh, window, rolling).
    """
    contracts.require_format_serviceable(fmt, "vp_decode_attention")
    backend = substrate.resolve_backend(interpret)
    if backend == "ref":
        return ref.vp_decode_attention_ref(
            q, k_w, v_w, k_s, v_s, lengths, fmt,
            window=window, rolling=rolling)
    B, _, H, dh = q.shape
    Smax, KV = k_w.shape[1], k_w.shape[2]
    G = H // KV
    blocks = autotune.resolve_attn_blocks(
        "vp_decode_attention",
        (B, Smax, KV, dh, window or 0, int(rolling)), (fmt,), backend,
        sq=G, sk=Smax, blocks=blocks)
    bs = blocks[1]
    ks, vs = k_s.reshape(B, Smax, 1), v_s.reshape(B, Smax, 1)
    kw, vw = k_w.reshape(B, Smax, KV * dh), v_w.reshape(B, Smax, KV * dh)
    pad = (-Smax) % bs
    if pad:
        # The kernel masks padded positions (the real `Smax` rides the
        # launch as the ring clamp), but re-padding four whole cache
        # planes EVERY decode step is the O(Smax) copy this kernel
        # exists to remove — prefer a smaller tile that divides the
        # buffer (floor: the int8-plane sublane minimum on native).
        floor = 32 if backend == "native" else 8
        bs_div = bs
        while Smax % bs_div and bs_div > floor:
            bs_div //= 2
        if Smax % bs_div == 0:
            bs, pad = bs_div, 0
    if pad:
        kw = jnp.pad(kw, ((0, 0), (0, pad), (0, 0)))
        vw = jnp.pad(vw, ((0, 0), (0, pad), (0, 0)))
        ks = jnp.pad(ks, ((0, 0), (0, pad), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, pad), (0, 0)))
    qr = q.reshape(B, KV, G, dh).astype(jnp.float32) * dh ** -0.5
    gp = max(G, 8) if backend == "native" else G
    if gp != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - G), (0, 0)))
    out = vp_decode_attention_pallas(
        qr, kw, vw, ks, vs, lengths.astype(jnp.int32), fmt,
        window=window, rolling=rolling, bs=bs, smax=Smax,
        interpret=(backend == "interpret"))
    return out[:, :, :G].reshape(B, 1, H, dh).astype(q.dtype)


# A page's scales ride one 128-lane row of the paged decode kernel, and
# a page's words are copied whole by DMA.
_SCALE_ROW = 128


def _paged_layout_ok(page_size: int, kv_heads: int, head_dim: int,
                     word_dtype, backend: str) -> bool:
    if backend == "ref":      # gathers the pages: any layout
        return True
    pack = 32 // (jnp.dtype(word_dtype).itemsize * 8)
    # The kernel reads a position's heads 32 bits at a time, and a
    # page's scales tile one 128-lane row.
    ok = _SCALE_ROW % page_size == 0 and kv_heads % pack == 0
    if backend == "native":
        # Mosaic copies a page only as whole tiles of the pool's HBM
        # layout: 128-lane heads, and KV heads a power of two or a
        # multiple of 8 (6 int16 or 12 int8 heads pad to 8 and fail).
        ok = ok and head_dim == _SCALE_ROW and (
            kv_heads & (kv_heads - 1) == 0 or kv_heads % 8 == 0)
    return ok


def paged_decode_supported(page_size: int, kv_heads: int, head_dim: int,
                           word_dtype) -> bool:
    """Whether `vp_paged_decode_attention` reads pages of this layout in
    place on the backend in force: any layout on the ref backend (which
    gathers), only the layouts the kernel tiles on the others."""
    return _paged_layout_ok(page_size, kv_heads, head_dim, word_dtype,
                            substrate.resolve_backend(None))


def vp_paged_decode_attention(
    q, k_pool, v_pool, k_s_pool, v_s_pool, k_tail, v_tail, k_tail_s,
    v_tail_s, layer, block_table, base, lengths,
    fmt: VPFormat,
    window: Optional[int] = None,
    pages_per_block: int = 16,
    interpret: Optional[bool] = None,
):
    """Single-token decode attention that reads the KV pages in place.

    q (B, 1, H, dh); k_pool / v_pool (L, n_pages, page, KV, dh) the
    whole stacked pools of packed VP words and k_s_pool / v_s_pool
    (L, n_pages, page, 1, 1) their pow2 scales (`serving.page_cache`);
    k_tail / v_tail (B, T, KV, dh) and k_tail_s / v_tail_s (B, T, 1, 1)
    the positions base .. base + T - 1 still in flight (this step's
    among them); layer () int32 indexes the pools; block_table (B, P);
    base (B,) positions already in the pools; lengths (B,) valid
    positions, pools and tail together.

    On a kernel backend the pools never leave HBM whole: the kernel
    copies only the pages holding [window bound, base) of each row,
    `pages_per_block` at a time, through the scalar-prefetched block
    table.  The word pools are passed whole — slicing one layer out of
    them in XLA would copy it.  The ref backend gathers this layer's
    pages and runs the contiguous oracle.
    """
    contracts.require_format_serviceable(fmt, "vp_decode_attention")
    backend = substrate.resolve_backend(interpret)
    if backend == "ref":
        return ref.vp_paged_decode_attention_ref(
            q, k_pool, v_pool, k_s_pool, v_s_pool, k_tail, v_tail,
            k_tail_s, v_tail_s, layer, block_table, base, lengths, fmt,
            window=window)
    B, _, H, dh = q.shape
    n_pages, page, KV = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]
    T = k_tail.shape[1]
    G = H // KV
    if not _paged_layout_ok(page, KV, dh, k_pool.dtype, backend):
        raise ValueError(
            f"paged decode attention cannot tile pages of {page} "
            f"positions x {KV} heads x {dh} {k_pool.dtype} words on the "
            f"{backend} backend")
    per = _SCALE_ROW // page
    npg = -(-pages_per_block // per) * per   # whole 128-lane scale rows

    def scale_rows(s_pool):
        # this layer's scales, a page's `page` scales tiled across one
        # 128-lane row, so one DMA brings a page's scales
        s = jax.lax.dynamic_index_in_dim(s_pool, layer, 0, keepdims=False)
        return jnp.tile(s.reshape(n_pages, 1, page), (1, 1, per))

    def tail(w):
        return w.reshape(B, T, KV * dh).astype(jnp.int32)

    qr = q.reshape(B, KV, G, dh).astype(jnp.float32) * dh ** -0.5
    gp = max(G, 8) if backend == "native" else G
    if gp != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - G), (0, 0)))
    out = vp_paged_decode_attention_pallas(
        qr, k_pool, v_pool, scale_rows(k_s_pool), scale_rows(v_s_pool),
        tail(k_tail), tail(v_tail), k_tail_s.reshape(B, T, 1),
        v_tail_s.reshape(B, T, 1), layer, block_table, base, lengths, fmt,
        window=window, pages_per_block=npg,
        interpret=(backend == "interpret"))
    return out[:, :, :G].reshape(B, 1, H, dh).astype(q.dtype)


def flash_prefill(
    q, k, v,
    pattern: str = "causal",
    window: Optional[int] = None,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
):
    """Flash-attention prefill: q (B, Sq, H, dh) x k/v (B, Sk, KV, dh).

    q-chunk x k-chunk online softmax in ONE pallas_call (scores never
    materialize); causal/local tiles above the diagonal or outside the
    window are skipped at tile granularity.  GQA rides the kernel index
    maps (kv head = head // G).  `blocks=None` resolves the (bq, bk, 1)
    chunking through the autotuner.
    """
    backend = substrate.resolve_backend(interpret)
    if backend == "ref":
        return ref.flash_prefill_ref(q, k, v, pattern=pattern,
                                     window=window)
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    if pattern in ("causal", "local") and Sq != Sk:
        # A real serving-input condition, not an internal invariant — it
        # must survive `python -O` (asserts are stripped).
        raise ValueError(
            f"causal/local prefill requires Sq == Sk, got {Sq} != {Sk}")
    blocks = autotune.resolve_attn_blocks(
        "flash_prefill",
        (B, H, KV, dh, Sq, Sk, window or 0), (), backend,
        sq=Sq, sk=Sk, blocks=blocks)
    bq, bk = blocks[0], blocks[1]
    qt = q.transpose(0, 2, 1, 3) * jnp.asarray(dh ** -0.5, q.dtype)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
    out = flash_prefill_pallas(
        qt, kt, vt, pattern=pattern, window=window, sk=Sk, g=G,
        blocks=(bq, bk), interpret=(backend == "interpret"))
    return out[:, :, :Sq].transpose(0, 2, 1, 3).astype(q.dtype)


def block_vp_matmul(
    a_m, a_i, b_m, b_i,
    a_fmt: VPFormat, b_fmt: VPFormat,
    bk: int = 256,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
):
    """Block-VP int8 matmul; index granularity = (row, k-block)."""
    contracts.check_formats(a_fmt, b_fmt, what="block_vp_matmul")
    # Each k-tile's raw-significand dot accumulates `bk` int products
    # before the f32 rescale — prove that sum cannot wrap int32.
    contracts.require_int_accum_safe(a_fmt, b_fmt, bk)
    if blocks is not None and blocks[1] != bk:
        # Validate on EVERY backend (the ref path is the parity oracle;
        # a contract violation must not pass on CPU and crash on TPU).
        raise ValueError(
            f"kernel k-tile {blocks[1]} must equal index block size {bk}")
    backend = substrate.resolve_backend(interpret)
    if backend == "ref":
        return ref.block_vp_matmul_ref(
            a_m, a_i, b_m, b_i, a_fmt, b_fmt, bk=bk, out_dtype=out_dtype)
    M, K = a_m.shape
    _, N = b_m.shape
    if blocks is None:
        # Autotune-resolve like every other matmul op (the qdot vp_block
        # path used to hardcode 256^3-class tiles here, bypassing the
        # cache entirely); the k-tile stays pinned to the index block
        # size whatever the cache says — it is part of the format, not a
        # free tiling axis, so the kernel name carries it in the key.
        r = autotune.resolve_blocks(
            f"block_vp_matmul_bk{bk}", (M, K, N), (a_fmt, b_fmt),
            backend, None)
        blocks = (r[0], bk, r[2])
    bm, _, bn = blocks
    am = _pad2(a_m, bm, bk)
    bm_ = _pad2(b_m, bk, bn)
    ai = _pad2(a_i, bm, 1)
    bi = _pad2(b_i, 1, bn)
    out = block_vp_matmul_pallas(
        am, ai, bm_, bi, a_fmt, b_fmt,
        interpret=(backend == "interpret"), blocks=blocks,
        out_dtype=out_dtype)
    return out[:M, :N]
