"""Pallas TPU kernels: fused VP-cache attention (decode + flash prefill).

The serving hot path PR 4 did not touch: attention.  Before this module,
every decode step dequantized the ENTIRE (B, Smax, KV, dh) VP KV cache to
floats in XLA and ran a masked softmax over all Smax positions — O(Smax)
HBM traffic and compute regardless of how many cache slots are actually
valid.  These kernels keep the cache in PACKED VP words (`core.packing`:
sign + significand + exponent index in one int8/int16 per element) all
the way into VMEM and do the unpack + bit-assembled pow2 scale in-tile,
which is the paper's claim (compact formats feed the multiplier directly)
restated for the memory-bound cache read.

Two kernels, both on the shared substrate:

  * `vp_decode_attention_pallas` — single-token decode against a packed
    KV cache.  Grid is (batch, kv_head, seq-tile) with the seq dimension
    innermost; per-batch cache lengths ride scalar prefetch, and a tile
    whose span [ki*bs, ki*bs + bs) lies entirely outside the valid range
    (past `len`, before the sliding-window lower bound, or past the
    rolling ring's fill level) is SKIPPED via `pl.when` — the same
    static-bounds trick `flash_attention`'s pair enumeration uses, so
    MXU work is O(cache_len · B · H · dh), not O(Smax).  The cache
    words arrive as (B, Smax, KV·dh) — a free reshape of the cache — so
    each (bs, dh) head tile is a lane-aligned block; per-position pow2
    cache scales arrive as (bs, 1) columns and scale the dequantized K/V
    rows by a lane broadcast.

  * `flash_prefill_pallas` — q-chunk x k-chunk online-softmax attention
    (causal / local / full masks) for the prefill pass, replacing the
    `lax.scan` pair-walk on kernel backends.  Tiles entirely above the
    causal diagonal or entirely older than the local window are skipped
    by program-id bounds; in-tile masking handles the diagonal fringe
    and the key-side padding.

Online-softmax state (running max m, denominator l, output accumulator)
lives in VMEM scratch shaped (rows, 128) / (rows, dh) and persists across
the innermost seq-tile steps; the output tile is written once, on the
last seq step, divided by the accumulated denominator.  Launch plumbing
(compat shims, scalar prefetch) is `substrate.vp_pallas_call`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import VPFormat
from . import substrate as sub

NEG_INF = -1e30
# m/l scratch rows are lane-broadcast to the TPU lane count so the
# scratch tiles are natively shaped; every lane of a row holds the same
# running statistic.
_LANES = 128


def _online_softmax_update(s, v, m_ref, l_ref, acc_ref):
    """One flash-attention accumulation step for a scores tile `s`.

    s (rows, bs) f32 scores (already masked), v (bs, dh) values.
    Updates the running (m, l, acc) scratch in place.
    """
    m_prev = m_ref[...]                      # (rows, LANES), lanes equal
    l_prev = l_ref[...]
    m_curr = jnp.max(s, axis=1)[:, None]     # (rows, 1)
    m_next = jnp.maximum(m_prev, m_curr)     # lane-broadcast
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :1])           # (rows, bs)
    l_next = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
    m_ref[...] = m_next
    l_ref[...] = l_next
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)


def _flush(o_ref, m_ref, l_ref, acc_ref, ki, nk: int):
    """Write acc / l to the output tile on the last seq step."""
    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        out = acc_ref[...] / l
        o_ref[...] = out.astype(o_ref.dtype).reshape(o_ref.shape)


# ---------------------------------------------------------------------------
# Decode: one query token vs a packed VP KV cache
# ---------------------------------------------------------------------------

def _decode_attn_kernel(
    len_ref,                     # scalar prefetch: (B,) int32 cache lengths
    q_ref, kw_ref, ks_ref, vw_ref, vs_ref,
    o_ref,
    m_ref, l_ref, acc_ref,
    *, fmt: VPFormat, bs: int, nk: int, smax: int,
    window: Optional[int], rolling: bool,
):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    start = ki * bs
    # Valid-position bounds for this batch element.  `rolling` means the
    # buffer IS the window (every slot written so far is valid); `window`
    # bounds the span from below; otherwise all positions < length count.
    if rolling:
        lo = jnp.int32(0)
        hi = jnp.minimum(length, smax)
    elif window:
        lo = jnp.maximum(length - window, 0)
        hi = length
    else:
        lo = jnp.int32(0)
        hi = length
    run = (start < hi) & (start + bs > lo)

    @pl.when(run)
    def _tile():
        q = q_ref[0, 0]                          # (Gp, dh) f32, pre-scaled
        # (bs, dh) packed words times (bs, 1) pow2 per-position scales.
        k = sub.dequant_packed(kw_ref[0], fmt, jnp.float32) * ks_ref[0]
        v = sub.dequant_packed(vw_ref[0], fmt, jnp.float32) * vs_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = (pos >= lo) & (pos < hi)
        s = jnp.where(valid, s, NEG_INF)
        _online_softmax_update(s, v, m_ref, l_ref, acc_ref)

    _flush(o_ref, m_ref, l_ref, acc_ref, ki, nk)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "window", "rolling", "bs", "smax", "interpret",
                     "out_dtype"),
)
def vp_decode_attention_pallas(
    q, k_w, v_w, k_s, v_s, lengths,
    fmt: VPFormat,
    window: Optional[int] = None,
    rolling: bool = False,
    bs: int = 256,
    smax: Optional[int] = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Decode attention over a PACKED VP KV cache.

    q (B, KV, Gp, dh) f32, already scaled by dh**-0.5; k_w / v_w
    (B, Smax_p, KV * dh) packed VP words (the cache's (B, Smax_p, KV, dh)
    buffer with its head axes merged); k_s / v_s (B, Smax_p, 1)
    per-position pow2 cache scales; lengths (B,) int32 valid lengths.
    Smax_p must be a multiple of `bs` (ops.py pads).  `smax` is the REAL
    (pre-pad) buffer length: the rolling ring clamps its valid span to
    it — clamping to the padded length would admit zero-score padding
    columns into the softmax denominator once the ring wraps
    (lengths > smax).  Returns (B, KV, Gp, dh).
    """
    B, KV, Gp, dh = q.shape
    smax_p = k_w.shape[1]
    nk = smax_p // bs
    smax = smax_p if smax is None else smax
    kernel = functools.partial(
        _decode_attn_kernel, fmt=fmt, bs=bs, nk=nk, smax=smax,
        window=window, rolling=rolling)
    # Head h's words are columns [h*dh, (h+1)*dh) of the merged axis, so
    # every block's trailing dims are (bs, dh) and (bs, 1): the shapes
    # Mosaic tiles natively, with no copy of the cache.
    cache_spec = pl.BlockSpec((1, bs, dh), lambda b, h, ki, *_: (b, ki, h))
    scale_spec = pl.BlockSpec((1, bs, 1), lambda b, h, ki, *_: (b, ki, 0))
    return sub.vp_pallas_call(
        kernel,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, Gp, dh), lambda b, h, ki, *_: (b, h, 0, 0)),
            cache_spec, scale_spec, cache_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, Gp, dh), lambda b, h, ki, *_: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, Gp, dh), out_dtype),
        scratch_shapes=[
            sub.vmem((Gp, _LANES), jnp.float32),
            sub.vmem((Gp, _LANES), jnp.float32),
            sub.vmem((Gp, dh), jnp.float32),
        ],
        num_scalar_prefetch=1,
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(lengths, q, k_w, k_s, v_w, v_s)


# ---------------------------------------------------------------------------
# Prefill: q-chunk x k-chunk flash attention (causal / local / full)
# ---------------------------------------------------------------------------

def _flash_prefill_kernel(
    q_ref, k_ref, v_ref,
    o_ref,
    m_ref, l_ref, acc_ref,
    *, bq: int, bk: int, nk: int, sk: int,
    pattern: str, window: Optional[int],
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Tile-level skip: a (qi, ki) tile can only contribute if some
    # (q_pos, k_pos) pair passes the mask — entirely-above-diagonal and
    # entirely-outside-window tiles never do (the kernel analogue of the
    # scan path's static pair enumeration).
    if pattern in ("causal", "local"):
        run = ki * bk <= qi * bq + bq - 1
        if pattern == "local" and window:
            run &= qi * bq - (ki * bk + bk - 1) < window
    else:
        run = True

    @pl.when(run)
    def _tile():
        q = q_ref[0, 0]                          # (bq, dh), pre-scaled
        k = k_ref[0, 0]                          # (bk, dh)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = k_pos < sk                       # mask the seq padding
        if pattern in ("causal", "local"):
            valid &= k_pos <= q_pos
            if pattern == "local" and window:
                valid &= q_pos - k_pos < window
        s = jnp.where(valid, s, NEG_INF)
        _online_softmax_update(s, v, m_ref, l_ref, acc_ref)

    _flush(o_ref, m_ref, l_ref, acc_ref, ki, nk)


@functools.partial(
    jax.jit,
    static_argnames=("pattern", "window", "sk", "g", "blocks", "interpret",
                     "out_dtype"),
)
def flash_prefill_pallas(
    q, k, v,
    pattern: str = "causal",
    window: Optional[int] = None,
    sk: Optional[int] = None,
    g: int = 1,
    blocks=(128, 128),
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Flash attention forward: q (B, H, Sqp, dh) x k/v (B, KV, Skp, dh).

    GQA rides the index maps (k/v head = query head // g, no materialized
    repeat).  q must already carry the dh**-0.5 scale; Sqp / Skp must be
    multiples of the (bq, bk) chunk sizes (ops.py pads — `sk` is the REAL
    key length, so padded key columns are masked; padded query rows
    compute garbage that the caller slices off).  Returns (B, H, Sqp, dh).
    """
    B, H, sqp, dh = q.shape
    KV, skp = k.shape[1], k.shape[2]
    bq, bk = blocks
    nq, nk = sqp // bq, skp // bk
    sk = skp if sk is None else sk
    kernel = functools.partial(
        _flash_prefill_kernel, bq=bq, bk=bk, nk=nk, sk=sk,
        pattern=pattern, window=window)
    kv_spec = pl.BlockSpec(
        (1, 1, bk, dh), lambda b, h, qi, ki, *_: (b, h // g, ki, 0))
    return sub.vp_pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, bq, dh), lambda b, h, qi, ki, *_: (b, h, qi, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, dh), lambda b, h, qi, ki, *_: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, sqp, dh), out_dtype),
        scratch_shapes=[
            sub.vmem((bq, _LANES), jnp.float32),
            sub.vmem((bq, _LANES), jnp.float32),
            sub.vmem((bq, dh), jnp.float32),
        ],
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        interpret=interpret,
    )(q, k, v)
