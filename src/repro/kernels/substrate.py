"""Shared Pallas kernel substrate: launch plumbing, in-kernel helpers, dispatch.

Every VP kernel in this package launches through this module, so three
concerns live in exactly one place instead of being cloned per kernel:

  (a) launch plumbing — grid-spec construction differs between plain and
      scalar-prefetch launches; `vp_pallas_call` absorbs both (and the
      TPU compiler params) so kernels never import `pallas.tpu` symbols
      directly.
  (b) in-kernel VP math — the quantize cascade (paper Fig. 3), the
      dequant/scale-LUT select cascade (Fig. 5 barrel-mux analogue), and the
      k-loop accumulator init/flush idiom shared by every matmul kernel.
  (c) backend dispatch — one `resolve_backend` mapping the public
      `interpret` argument to TPU-native / interpret / pure-jnp-ref
      execution, fixing the "explicit interpret=False forces TPU lowering on
      CPU" bug at a single site for every op in `ops.py`.

Paper mapping: the cascades below are the TPU analogue of the paper's
offline exponent LUTs (Sec. II-B) — all exponent work is a statically
unrolled select chain over the (static) exponent list; the MXU only ever
sees plain fixed-point significands or pre-scaled reals, which is the VP
cheap-multiplier claim restated as kernel structure.  Sharing one datapath
across the scalar-VP, block-VP, and fused kernels mirrors how run-time
reconfigurable multipliers share one array across formats rather than
cloning it per format.
"""
from __future__ import annotations

import collections
import contextlib
import functools
from typing import Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FXPFormat, VPFormat

# ---------------------------------------------------------------------------
# (a) launch plumbing
# ---------------------------------------------------------------------------

def vmem(shape: Tuple[int, ...], dtype):
    """VMEM scratch allocation (kernels never touch pltpu directly)."""
    return pltpu.VMEM(shape, dtype)


def smem(shape: Tuple[int, ...], dtype):
    """SMEM scratch allocation (scalars that persist across grid steps)."""
    return pltpu.SMEM(shape, dtype)


def dma_semaphores(n: int):
    """`n` DMA-completion semaphores as one scratch allocation."""
    return pltpu.SemaphoreType.DMA((n,))


def async_copy(src, dst, sem):
    """An HBM -> VMEM copy descriptor (`.start()` / `.wait()`)."""
    return pltpu.make_async_copy(src, dst, sem)


def vp_pallas_call(
    kernel,
    *,
    grid,
    in_specs,
    out_specs,
    out_shape,
    scratch_shapes: Sequence = (),
    num_scalar_prefetch: int = 0,
    dimension_semantics: Optional[Sequence[str]] = None,
    interpret: bool = False,
):
    """The one `pl.pallas_call` site for every kernel in this package.

    With `num_scalar_prefetch > 0` the launch goes through
    `PrefetchScalarGridSpec` (index maps then receive the scalar refs as
    trailing args); otherwise through the plain grid/in_specs path.
    `dimension_semantics` rides the TPU compiler params; both forms
    accept VMEM scratch.
    """
    kwargs = {}
    if dimension_semantics is not None:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=tuple(dimension_semantics))
    if num_scalar_prefetch:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_scalar_prefetch,
            grid=grid,
            in_specs=list(in_specs),
            out_specs=out_specs,
            scratch_shapes=list(scratch_shapes),
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
            **kwargs,
        )
    if scratch_shapes:
        kwargs["scratch_shapes"] = list(scratch_shapes)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=list(in_specs),
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        **kwargs,
    )


# ---------------------------------------------------------------------------
# (b) shared in-kernel helpers
# ---------------------------------------------------------------------------

def scale_lut_gather(i, fmt: VPFormat, dtype):
    """scale[i] = 2**-f_i via an unrolled select cascade (K <= 16).

    The TPU analogue of the paper's exponent LUT read: the list is static,
    so the gather lowers to one VPU select chain — no exponent arithmetic.
    Accepts any integer index dtype (uint8 planes or in-kernel int32).
    """
    scale = jnp.full(i.shape, jnp.asarray(2.0 ** (-fmt.f[0]), dtype))
    for k in range(1, fmt.K):
        scale = jnp.where(
            i == k, jnp.asarray(2.0 ** (-fmt.f[k]), dtype), scale)
    return scale


def dequant_cascade(m, i, fmt: VPFormat, dtype):
    """(significand, index) -> real tile: m * 2**-f_i (paper Fig. 5).

    The scale comes from `scale_of_index`: O(1) bit-assembly per element
    when the format admits it, else the unrolled select cascade — both
    produce bit-identical power-of-two scales (tests/test_packing.py).
    """
    return m.astype(dtype) * scale_of_index(i, fmt, dtype)


# -- O(1) bit-assembled scale --------------------------------------------

@functools.lru_cache(maxsize=None)
def _fpack_params(fmt: VPFormat) -> Optional[Tuple[int, int, int]]:
    """Static constants for the bit-assembled scale, or None if the format
    doesn't admit it (exponents outside the f32 normal range, or the
    biased f-list doesn't fit one 32-bit constant).

    Returns (fpack, bits, fmin): the exponent list packed little-endian
    into one uint32, `bits` bits per biased entry f_k - fmin.
    """
    fmin = min(fmt.f)
    span = max(fmt.f) - fmin
    # 2**-f must be an f32 NORMAL so its bit pattern is pure exponent:
    # biased exponent 127 - f in [1, 254].
    if not all(1 <= 127 - fv <= 254 for fv in fmt.f):
        return None
    for bits in (4, 8, 16):
        if span < (1 << bits) and fmt.K * bits <= 32:
            fpack = 0
            for k, fv in enumerate(fmt.f):
                fpack |= (fv - fmin) << (bits * k)
            return fpack, bits, fmin
    return None


def scale_bit_assemble(i, fmt: VPFormat):
    """scale[i] = 2**-f_i as f32 by integer exponent arithmetic — O(1).

    Three steps, none of which grow with K:
      1. f_i  = (FPACK >> (i * bits)) & mask  + fmin   (variable shift of
         a packed static constant — the whole exponent list rides in one
         uint32 immediate);
      2. exponent field: (127 - f_i) << 23  (2**e is an f32 normal with a
         zero mantissa, so its bit pattern IS the biased exponent field);
      3. bitcast int32 -> float32.
    Bit-identical to `scale_lut_gather` (powers of two are exact), which
    stays as the oracle; callers must check `_fpack_params(fmt)` first
    (use `scale_of_index` for the automatic fallback).
    """
    fpack, bits, fmin = _fpack_params(fmt)
    ii = i.astype(jnp.uint32)
    biased = jnp.bitwise_and(
        jnp.right_shift(jnp.uint32(fpack), ii * jnp.uint32(bits)),
        jnp.uint32((1 << bits) - 1),
    ).astype(jnp.int32)
    ebits = jnp.left_shift(jnp.int32(127 - fmin) - biased, 23)
    return jax.lax.bitcast_convert_type(ebits, jnp.float32)


def scale_of_index(i, fmt: VPFormat, dtype):
    """2**-f_i per element: the kernel-wide scale policy.

    The bit-assembly costs ~7 integer ops independent of K; the select
    chain costs K dependent selects.  So the O(1) path engages for wide
    exponent lists (K > 4, where the chain serializes), while paper-class
    K <= 4 lists keep the shorter chain; both produce bit-identical
    power-of-two scales, so this is purely a cost choice.  Falls back to
    the chain for non-f32 dtypes and exponents outside the f32 normal
    range (where no pure-exponent bit pattern exists).
    """
    if (fmt.K > 4 and dtype == jnp.float32
            and _fpack_params(fmt) is not None):
        return scale_bit_assemble(i, fmt)
    return scale_lut_gather(i, fmt, dtype)


# -- packed-word in-kernel path ------------------------------------------

def unpack_cascade(w, fmt: VPFormat):
    """Packed word tile -> (int32 significand, int32 index).

    One arithmetic shift (sign extension for free) and one mask —
    cheaper than reading a second operand plane from HBM ever was.
    Delegates to `core.packing.unpack_vp` (pure jnp, in-kernel safe):
    ONE implementation of the word layout, shared with the oracle.
    """
    from repro.core.packing import unpack_vp

    return unpack_vp(w, fmt)


def dequant_packed(w, fmt: VPFormat, dtype):
    """Packed word tile -> real tile, unpack + bit-assembled dequant."""
    m, i = unpack_cascade(w, fmt)
    return m.astype(dtype) * scale_of_index(i, fmt, dtype)


def quantize_cascade(x, fxp: FXPFormat, vp: VPFormat):
    """float tile -> (int32 significand, int32 index) (paper Fig. 3).

    The bit-window + LOD circuit as an unrolled chain of arithmetic shifts
    and in-range tests over the static exponent list — bit-identical to the
    circuit (see core.convert for the equivalence proof).  Callers cast the
    planes to their storage dtypes (int8 / uint8).
    """
    raw = jnp.clip(
        jnp.round(x * jnp.float32(2.0 ** fxp.F)),
        fxp.raw_min, fxp.raw_max,
    ).astype(jnp.int32)

    lo, hi = vp.raw_min, vp.raw_max
    m_sel = jnp.zeros_like(raw)
    i_sel = jnp.zeros_like(raw)
    valid_any = jnp.zeros(raw.shape, jnp.bool_)
    for k in range(vp.K):
        s_k = fxp.F - vp.f[k]
        m_k = (
            jnp.right_shift(raw, s_k) if s_k >= 0
            else jnp.left_shift(raw, -s_k)
        )
        valid_k = (m_k >= lo) & (m_k <= hi)
        take = valid_k & ~valid_any
        m_sel = jnp.where(take, m_k, m_sel)
        i_sel = jnp.where(take, k, i_sel)
        valid_any = valid_any | valid_k
    # Out-of-range on every option: saturate at the coarsest exponent.
    s_last = fxp.F - vp.f[-1]
    m_last = jnp.clip(
        jnp.right_shift(raw, s_last) if s_last >= 0
        else jnp.left_shift(raw, -s_last),
        lo, hi,
    )
    m = jnp.where(valid_any, m_sel, m_last)
    i = jnp.where(valid_any, i_sel, vp.K - 1)
    return m, i


def quantize_pack_cascade(x, fxp: FXPFormat, vp: VPFormat):
    """float tile -> packed VP words (int32; caller casts to storage dtype).

    The Fig. 3 cascade followed by the core.packing word assembly
    ``(m << E) | i`` — the fused producer for kernels that emit packed
    planes straight from floats, never materializing the two-plane layout.
    """
    m, i = quantize_cascade(x, fxp, vp)
    return jnp.bitwise_or(jnp.left_shift(m, vp.E), i)


def quantize_dequant_cascade(x, fxp: FXPFormat, vp: VPFormat, dtype):
    """float tile -> VP-rounded reals m * 2**-f_i in ONE cascade.

    For fused kernels: equals `dequant_cascade(*quantize_cascade(x))` bit
    for bit.  The scale is re-derived from the selected index by the O(1)
    bit-assembly (`scale_of_index`) instead of riding a third K-way select
    chain alongside (m, i) — same exact power-of-two values, fewer VPU
    selects per element.
    """
    m, i = quantize_cascade(x, fxp, vp)
    return m.astype(dtype) * scale_of_index(i, fmt=vp, dtype=dtype)


def accum_init(acc_ref, ki):
    """Zero the VMEM accumulator on the first k step."""
    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)


def accum_flush(o_ref, acc_ref, ki, nk: int):
    """Write the accumulator to the output tile on the last k step.

    The reshape lets batched kernels keep a 2-D (bm, bn) accumulator while
    writing a (1, bm, bn) output block — a no-op for the unbatched kernels
    whose output tile already matches the accumulator shape.
    """
    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype).reshape(o_ref.shape)


def cspade_launch(kernel, a_act, b_act, **statics):
    """Bind a CSPADE-capable matmul kernel for one launch.

    Masked launches scalar-prefetch the tile-activity flags flattened to
    1-D — SMEM pads every trailing axis of a multi-dimensional operand,
    so a (G, nm, nk) flag array for G = 1024 realizations would alone
    overflow it.  Unmasked launches prefetch nothing; the kernel's two
    flag refs are bound to None.  Returns (kernel, prefetch operands).
    """
    if a_act is None:
        return functools.partial(kernel, None, None, cspade=False,
                                 **statics), ()
    masks = (a_act.reshape(-1).astype(jnp.int32),
             b_act.reshape(-1).astype(jnp.int32))
    return functools.partial(kernel, cspade=True, **statics), masks


def batched_matmul_grid(
    nb: int, nm: int, nn: int, nk: int,
    bm: int, bk: int, bn: int,
    a_copies: int = 1, b_copies: int = 1,
):
    """Grid + block specs for a batch-gridded (G, M, K) x (G, K, N) matmul.

    This is the truly-batched kernel contract: the grid gains a LEADING
    batch dimension, so each batch element runs its own (M, K) x (K, N)
    tile program — no folding of the batch into the row axis and no
    masked-diagonal waste.  Grid order is (batch, m, n, k) with k innermost
    (the accumulator idiom needs the k steps of one output tile to be
    consecutive); batch/m/n are all "parallel", k is "arbitrary".

    `a_copies` / `b_copies` give the number of identically-tiled tensors
    riding each operand's index map — the plane kernels pass 2 per operand
    (significand + exponent-index), the fused float kernel passes 1.

    Index-map lambdas take `*_` trailing args so the same specs work under
    `PrefetchScalarGridSpec` (scalar refs are appended to index-map args).
    """
    grid = (nb, nm, nn, nk)
    a_spec = pl.BlockSpec(
        (1, bm, bk), lambda b, mi, ni, ki, *_: (b, mi, ki))
    b_spec = pl.BlockSpec(
        (1, bk, bn), lambda b, mi, ni, ki, *_: (b, ki, ni))
    in_specs = [a_spec] * a_copies + [b_spec] * b_copies
    out_specs = pl.BlockSpec(
        (1, bm, bn), lambda b, mi, ni, ki, *_: (b, mi, ni))
    semantics = ("parallel", "parallel", "parallel", "arbitrary")
    return grid, in_specs, out_specs, semantics


# ---------------------------------------------------------------------------
# (c) backend dispatch
# ---------------------------------------------------------------------------

def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Set only by `force_backend`; overrides the interpret/platform mapping.
_FORCED: list = []

# Backends `resolve_backend` has handed out, by name.  Ops resolve while
# they are traced, so this counts traced launches, not executions: a run
# on the chip that shows any "ref" outside a deliberate `force_backend`
# traced a jnp oracle where a kernel should have been.
resolved: collections.Counter = collections.Counter()


@contextlib.contextmanager
def force_backend(backend: str) -> Iterator[None]:
    """Pin `resolve_backend` to one backend inside the context.

    Used by `repro.analysis.jaxpr_lint` to trace model forwards through
    the "interpret" path on CPU, so the traced jaxpr contains the actual
    `pallas_call` kernel launches instead of the ref oracles (whose
    full-tensor dequants are fine for an oracle but would be findings on
    the serving path).  Re-entrant; restores the previous behavior on
    exit.  Not thread-safe — linting is a single-threaded CLI activity.
    """
    if backend not in ("native", "interpret", "ref"):
        raise ValueError(f"unknown backend {backend!r}")
    _FORCED.append(backend)
    try:
        yield
    finally:
        _FORCED.pop()


def resolve_backend(interpret: Optional[bool]) -> str:
    """Map a public op's `interpret` argument to an execution backend.

    ``True``          -> ``"interpret"``: run the Pallas kernel body through
                         the interpreter (any backend; the kernel tests use
                         this on CPU).
    ``None``/``False`` -> ``"native"`` on a TPU backend, ``"ref"`` (the
                         pure-jnp oracle in ref.py) everywhere else.

    An explicit ``False`` means "don't interpret", never "force native
    lowering": attempting TPU lowering on a CPU backend was the seed bug
    (`use_kernel = _on_tpu() if interpret is None else True`) that this
    dispatcher retires for every op at once.

    A `force_backend` context overrides the mapping entirely (analysis
    tracing only).
    """
    if _FORCED:
        backend = _FORCED[-1]
    elif interpret:
        backend = "interpret"
    else:
        backend = "native" if on_tpu() else "ref"
    resolved[backend] += 1
    return backend
