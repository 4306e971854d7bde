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

Three kernels, all on the shared substrate:

  * `vp_paged_decode_attention_pallas` — the serving decode: the same
    math read straight out of the paged pools.  The whole stacked pools
    stay in HBM; the scalar-prefetched block table, lengths and layer
    index steer double-buffered DMAs of 16 pages (256 positions, all KV
    heads) a step, and pages past a row's length are never copied.  The
    positions a decode call appends arrive as a small tail input,
    folded in after the pages.

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


def _online_softmax_update(s, v, m_ref, l_ref, acc_ref, v_scale=None):
    """One flash-attention accumulation step for a scores tile `s`.

    s (rows, bs) f32 scores (already masked), v (bs, dh) values.
    `v_scale` (1, bs), when given, is a per-position pow2 scale of v
    applied to the probabilities instead (exact: a power of two commutes
    with every rounding of the product).  Updates the running
    (m, l, acc) scratch in place.
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
    pv = p if v_scale is None else p * v_scale
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot(
        pv.astype(v.dtype), v, preferred_element_type=jnp.float32)


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
# Paged decode: one query token vs a request's pages, read in place
# ---------------------------------------------------------------------------

def _head_words(buf):
    """Each KV head's rows of a block of pages, as int32 packed words:
    yields (head, words (n * page, dh)).

    buf (n, page, KV, dh) is a VMEM block in the pool's own layout, so
    head h of position t is row t * KV + h.  A packed type shares each
    32-bit row between `32 // bits` neighbouring heads: the block is
    read as uint32 rows with a stride of KV // packing, once per group
    of heads, and each head's bits are shifted out, sign extended.  KV
    must be a multiple of the packing (`ops.paged_decode_supported`).
    """
    n, ps, kv, dh = buf.shape
    bits = jnp.dtype(buf.dtype).itemsize * 8
    pack = 32 // bits
    rows = buf.reshape(n * ps * kv, dh)
    if pack == 1:
        for h in range(kv):
            yield h, rows[pl.ds(h, n * ps, stride=kv), :].astype(jnp.int32)
        return
    packed = rows.bitcast(jnp.uint32)
    for g in range(kv // pack):
        w = jax.lax.bitcast_convert_type(
            packed[pl.ds(g, n * ps, stride=kv // pack), :], jnp.int32)
        for sub_row in range(pack):
            up = 32 - bits * (sub_row + 1)
            yield g * pack + sub_row, jnp.right_shift(
                jnp.left_shift(w, up) if up else w, 32 - bits)


def _scale_row(buf, ps: int):
    """Per-position scales of a block of pages as one (1, n * ps) row.

    buf (n, 1, 128): page j's row holds its `ps` scales tiled across the
    128 lanes, so lane l of a 128-lane chunk takes the row of the page
    that owns it, (chunk * 128 + l) // ps: static selects, no shuffle.
    """
    n = buf.shape[0]
    per = _LANES // ps
    owner = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // ps
    chunks = []
    for c in range(-(-n // per)):
        row = buf[c * per]
        for j in range(1, min(per, n - c * per)):
            row = jnp.where(owner == j, buf[c * per + j], row)
        chunks.append(row)
    row = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, 1)
    return row[:, :n * ps]


def _paged_block(q_ref, kbuf, vbuf, ksbuf, vsbuf, m_ref, l_ref, acc_ref,
                 *, fmt: VPFormat, start, lo, hi):
    """Fold one block of pages (positions start ..) into every head's
    online softmax; positions outside [lo, hi) count as absent.

    The K scale multiplies the scores and the V scale the probabilities
    (`_online_softmax_update`): per-position powers of two, so this is
    the same arithmetic as scaling the rows, on a lane-oriented row.
    """
    n, ps, _, _ = kbuf.shape
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, n * ps), 1)
    valid = (pos >= lo) & (pos < hi)
    # Pages the block did not read hold stale scratch: zero their scales
    # before they meet a probability.
    rk = jnp.where(valid, _scale_row(ksbuf, ps), 0.0)
    rv = jnp.where(valid, _scale_row(vsbuf, ps), 0.0)
    for (h, kw), (_, vw) in zip(_head_words(kbuf), _head_words(vbuf)):
        k = sub.dequant_packed(kw, fmt, jnp.float32)
        s = jax.lax.dot_general(
            q_ref[0, h], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * rk
        s = jnp.where(valid, s, NEG_INF)
        v = sub.dequant_packed(vw, fmt, jnp.float32)
        _online_softmax_update(s, v, m_ref.at[h], l_ref.at[h],
                               acc_ref.at[h], v_scale=rv)


def _paged_tail(q_ref, kt_ref, vt_ref, kts_ref, vts_ref, m_ref, l_ref,
                acc_ref, *, fmt: VPFormat, base, lo, hi):
    """Fold the in-flight positions base, base + 1, .. (not yet in the
    pools) into the online softmax, one position at a time."""
    steps = kt_ref.shape[1]
    kv, _, dh = acc_ref.shape
    for t in range(steps):
        @pl.when((base + t >= lo) & (base + t < hi))
        def _():
            ks = kts_ref[0, t:t + 1, :]                  # (1, 1)
            vs = vts_ref[0, t:t + 1, :]
            for h in range(kv):
                cols = slice(h * dh, (h + 1) * dh)
                k = sub.dequant_packed(kt_ref[0, t:t + 1, cols], fmt,
                                       jnp.float32) * ks
                v = sub.dequant_packed(vt_ref[0, t:t + 1, cols], fmt,
                                       jnp.float32) * vs
                s = jnp.sum(q_ref[0, h] * k, axis=1, keepdims=True)
                m_prev = m_ref[h]
                m_next = jnp.maximum(m_prev, s)          # (rows, LANES)
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.exp(s - m_next[:, :1])
                m_ref[h] = m_next
                l_ref[h] = alpha * l_ref[h] + p
                acc_ref[h] = acc_ref[h] * alpha[:, :1] + p * v


def _paged_decode_kernel(
    # scalar prefetch: this layer, flat block table, committed lengths,
    # valid lengths (committed + in flight)
    layer_ref, bt_ref, base_ref, len_ref,
    q_ref, kw_hbm, vw_hbm, ks_hbm, vs_hbm, kt_ref, vt_ref, kts_ref,
    vts_ref,
    o_ref,
    kbuf, vbuf, ksbuf, vsbuf, sems, state, m_ref, l_ref, acc_ref,
    *, fmt: VPFormat, ps: int, npg: int, pages: int,
    window: Optional[int],
):
    b = pl.program_id(0)
    layer = layer_ref[0]

    def span(r):
        """Row r's valid span and the blocks of pages that hold it."""
        n = len_ref[r]
        lo = jnp.maximum(n - window, 0) if window else jnp.int32(0)
        pg_lo = lo // ps
        pg_hi = (jnp.minimum(base_ref[r], n) + ps - 1) // ps
        blk_lo = pg_lo // npg
        blk_hi = jnp.where(pg_hi > pg_lo, (pg_hi + npg - 1) // npg, blk_lo)
        return n, lo, pg_lo, pg_hi, blk_lo, blk_hi

    def copies(r, blk, slot, pg_lo, pg_hi):
        """(live, descriptors) per page of block `blk` of row r: a page
        outside [pg_lo, pg_hi) is never read, so neither the dummy page
        nor a stale block-table entry is."""
        for j in range(npg):
            p = blk * npg + j
            page = bt_ref[r * pages + jnp.minimum(p, pages - 1)]
            yield (p >= pg_lo) & (p < pg_hi), (
                sub.async_copy(kw_hbm.at[layer, page], kbuf.at[slot, j],
                               sems.at[slot]),
                sub.async_copy(vw_hbm.at[layer, page], vbuf.at[slot, j],
                               sems.at[slot]),
                sub.async_copy(ks_hbm.at[page], ksbuf.at[slot, j],
                               sems.at[slot]),
                sub.async_copy(vs_hbm.at[page], vsbuf.at[slot, j],
                               sems.at[slot]))

    def start(*args):
        for live, cps in copies(*args):
            @pl.when(live)
            def _():
                for c in cps:
                    c.start()

    def wait(*args):
        for live, cps in copies(*args):
            @pl.when(live)
            def _():
                for c in cps:
                    c.wait()

    # state[0]: the buffer slot the next block lands in; state[1]: the
    # row whose first block the previous row already started.
    @pl.when(b == 0)
    def _():
        state[0] = 0
        state[1] = -1

    n, lo, pg_lo, pg_hi, blk_lo, blk_hi = span(b)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((blk_hi > blk_lo) & (state[1] != b))
    def _():
        start(b, blk_lo, state[0], pg_lo, pg_hi)

    def block(blk, carry):
        slot = state[0]
        nxt = 1 - slot

        @pl.when(blk + 1 < blk_hi)
        def _():
            start(b, blk + 1, nxt, pg_lo, pg_hi)

        # The row's last block: start the next row's first one.
        @pl.when((blk + 1 == blk_hi) & (b + 1 < pl.num_programs(0)))
        def _():
            _, _, lo2, hi2, first2, end2 = span(b + 1)

            @pl.when(end2 > first2)
            def _():
                start(b + 1, first2, nxt, lo2, hi2)
                state[1] = b + 1

        wait(b, blk, slot, pg_lo, pg_hi)
        _paged_block(q_ref, kbuf.at[slot], vbuf.at[slot], ksbuf.at[slot],
                     vsbuf.at[slot], m_ref, l_ref, acc_ref, fmt=fmt,
                     start=blk * npg * ps, lo=lo,
                     hi=jnp.minimum(base_ref[b], n))
        state[0] = nxt
        return carry

    jax.lax.fori_loop(blk_lo, blk_hi, block, 0)
    _paged_tail(q_ref, kt_ref, vt_ref, kts_ref, vts_ref, m_ref, l_ref,
                acc_ref, fmt=fmt, base=base_ref[b], lo=lo, hi=n)
    l = jnp.maximum(l_ref[...][:, :, :1], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "window", "pages_per_block", "interpret",
                     "out_dtype"),
)
def vp_paged_decode_attention_pallas(
    q, k_pool, v_pool, k_rows, v_rows, k_tail, v_tail, k_tail_s, v_tail_s,
    layer, block_table, base, lengths,
    fmt: VPFormat,
    window: Optional[int] = None,
    pages_per_block: int = 16,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Decode attention that reads a request's KV pages in place.

    q (B, KV, Gp, dh) f32, already scaled by dh**-0.5; k_pool / v_pool
    (L, n_pages, page, KV, dh) the WHOLE stacked pools of packed VP
    words, left in HBM and read page by page, `pages_per_block` pages a
    step, double-buffered; k_rows / v_rows (n_pages, 1, 128) this
    layer's per-position pow2 scales, a page's `page` scales tiled
    across one 128-lane row; k_tail / v_tail (B, T, KV * dh) int32 and
    k_tail_s / v_tail_s (B, T, 1) the in-flight positions base ..
    base + T - 1, not yet in the pools; layer () int32; block_table
    (B, P) int32; base (B,) positions in the pools; lengths (B,) valid
    positions, pools and tail.  Pages at or past ceil(base / page) are
    never copied.  Returns (B, KV, Gp, dh).
    """
    B, KV, Gp, dh = q.shape
    page = k_pool.shape[2]
    P = block_table.shape[1]
    T = k_tail.shape[1]
    npg = pages_per_block
    kernel = functools.partial(
        _paged_decode_kernel, fmt=fmt, ps=page, npg=npg, pages=P,
        window=window)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    head_spec = pl.BlockSpec((1, KV, Gp, dh), lambda b, *_: (b, 0, 0, 0))
    tail_spec = pl.BlockSpec((1, T, KV * dh), lambda b, *_: (b, 0, 0))
    tail_s_spec = pl.BlockSpec((1, T, 1), lambda b, *_: (b, 0, 0))
    return sub.vp_pallas_call(
        kernel,
        grid=(B,),
        in_specs=[head_spec, hbm, hbm, hbm, hbm, tail_spec, tail_spec,
                  tail_s_spec, tail_s_spec],
        out_specs=head_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Gp, dh), out_dtype),
        scratch_shapes=[
            sub.vmem((2, npg, page, KV, dh), k_pool.dtype),
            sub.vmem((2, npg, page, KV, dh), v_pool.dtype),
            sub.vmem((2, npg, 1, _LANES), jnp.float32),
            sub.vmem((2, npg, 1, _LANES), jnp.float32),
            sub.dma_semaphores(2),
            sub.smem((2,), jnp.int32),
            sub.vmem((KV, Gp, _LANES), jnp.float32),
            sub.vmem((KV, Gp, _LANES), jnp.float32),
            sub.vmem((KV, Gp, dh), jnp.float32),
        ],
        num_scalar_prefetch=4,
        # Sequential: a row starts the next row's first copies.
        dimension_semantics=("arbitrary",),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_table.reshape(-1).astype(jnp.int32), base.astype(jnp.int32),
      lengths.astype(jnp.int32), q, k_pool, v_pool, k_rows, v_rows,
      k_tail, v_tail, k_tail_s, v_tail_s)


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
