"""Attention: GQA with RoPE/qk-norm, flash-style chunked softmax, KV cache.

Training/prefill use a "pair-scan flash" implementation: the (q-chunk,
k-chunk) pairs that can contribute under the mask (causal triangle, local
band, or full rectangle) are enumerated STATICALLY, and a single lax.scan
walks the pair list carrying running (max, denom, acc).  This gives
  * bounded peak memory (one q-chunk x k-chunk score block at a time),
  * exact mask-aware FLOPs (no wasted upper-triangle compute),
  * one compiled body regardless of sequence length.

Decode attends a single query against the (optionally VP-quantized) cache.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, QuantConfig
from repro.core import FXPFormat, default_vp_format
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels import substrate as ksub
from repro.kernels.autotune import _pow2_at_least
from .layers import qdot, rms_norm, rope

NEG_INF = -1e30


def _chunk_and_pad(s: int, target: int = 512):
    """Chunk size and padded length for a sequence of length s.

    The chunk is the largest power of two <= target that is needed to
    cover s; s pads up to the next chunk multiple (pad < chunk, masked
    in the kernel).  The old policy demanded an exact DIVISOR of s, so a
    prime length (e.g. 509) degraded to chunk=1 and a scan over s^2
    singleton pairs.
    """
    c = min(target, _pow2_at_least(max(s, 1)))
    return c, s + (-s) % c


def _chunk_pairs(n_q: int, n_k: int, pattern: str, window_chunks: int):
    """Static list of contributing (qi, ki) chunk pairs."""
    pairs = []
    for qi in range(n_q):
        for ki in range(n_k):
            if pattern == "causal" and ki > qi:
                continue
            if pattern == "local" and (ki > qi or qi - ki > window_chunks):
                continue
            pairs.append((qi, ki))
    return pairs


def flash_attention(
    q, k, v,
    pattern: str = "causal",
    window: Optional[int] = None,
    chunk: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """q (B, Sq, H, dh), k/v (B, Sk, KV, dh) -> (B, Sq, H, dh).

    GQA: H must be a multiple of KV; k/v heads are repeated logically via
    reshape (no materialized repeat).
    pattern: causal | local (banded causal) | full (encoder/cross).
    Causal/local require Sq == Sk.
    """
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    if scale is None and ksub.resolve_backend(None) == "native":
        # Kernel backend: one fused flash pallas_call (q-chunk x k-chunk
        # online softmax, diagonal/window tiles skipped) replaces the
        # lax.scan pair-walk.
        return kops.flash_prefill(q, k, v, pattern=pattern, window=window)
    scale = scale if scale is not None else dh ** -0.5
    c, sqp = _chunk_and_pad(Sq, chunk)
    ck, skp = _chunk_and_pad(Sk, chunk)
    if pattern in ("causal", "local"):
        assert Sq == Sk
        ck, skp = c, sqp
    nq, nk = sqp // c, skp // ck
    wc = max(1, (window or sqp) // c) if pattern == "local" else nk
    pairs = _chunk_pairs(nq, nk, pattern, wc)
    pair_arr = jnp.asarray(pairs, jnp.int32)  # (P, 2)

    if sqp != Sq:
        q = jnp.pad(q, ((0, 0), (0, sqp - Sq), (0, 0), (0, 0)))
    if skp != Sk:
        k = jnp.pad(k, ((0, 0), (0, skp - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skp - Sk), (0, 0), (0, 0)))

    # Layout: (B, KV, G, nq, c, dh) for q; (B, KV, nk, ck, dh) for k/v.
    qr = q.reshape(B, sqp, KV, G, dh).transpose(0, 2, 3, 1, 4)
    qr = qr.reshape(B, KV, G, nq, c, dh) * scale
    kr = k.transpose(0, 2, 1, 3).reshape(B, KV, nk, ck, dh)
    vr = v.transpose(0, 2, 1, 3).reshape(B, KV, nk, ck, dh)

    q_off = jnp.arange(c, dtype=jnp.int32)
    k_off = jnp.arange(ck, dtype=jnp.int32)

    def step(carry, pair):
        m, l, acc = carry                        # running stats per q pos
        qi, ki = pair[0], pair[1]
        qb = jax.lax.dynamic_index_in_dim(qr, qi, axis=3, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(kr, ki, axis=2, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vr, ki, axis=2, keepdims=False)
        # scores (B, KV, G, c, ck) — operands stay bf16 (halves the
        # SP-gather bytes), accumulation in f32 (MXU-native)
        s = jnp.einsum(
            "bkgqd,bkcd->bkgqc", qb, kb,
            preferred_element_type=jnp.float32)
        q_pos = qi * c + q_off[:, None]
        k_pos = ki * ck + k_off[None, :]
        if pattern in ("causal", "local"):
            mask = k_pos <= q_pos
            if pattern == "local" and window:
                mask &= q_pos - k_pos < window
            if skp != Sk:
                mask &= k_pos < Sk
            s = jnp.where(mask, s, NEG_INF)
        elif skp != Sk:
            s = jnp.where(k_pos < Sk, s, NEG_INF)
        # online softmax update for q chunk qi
        m_old = jax.lax.dynamic_index_in_dim(m, qi, 3, keepdims=False)
        l_old = jax.lax.dynamic_index_in_dim(l, qi, 3, keepdims=False)
        a_old = jax.lax.dynamic_index_in_dim(acc, qi, 3, keepdims=False)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_old, m_blk)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_old - m_new)
        l_new = l_old * corr + jnp.sum(p, axis=-1)
        a_new = a_old * corr[..., None] + jnp.einsum(
            "bkgqc,bkcd->bkgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, 3)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, 3)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qi, 3)
        return (m, l, acc), None

    init = (
        jnp.full((B, KV, G, nq, c), NEG_INF, jnp.float32),
        jnp.zeros((B, KV, G, nq, c), jnp.float32),
        jnp.zeros((B, KV, G, nq, c, dh), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(step, init, pair_arr)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.reshape(B, KV, G, sqp, dh).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, sqp, H, dh)[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache (optionally VP-quantized) + decode attention
# ---------------------------------------------------------------------------

def kv_cache_formats(q: QuantConfig):
    fxp = FXPFormat(q.W, q.W - 1)
    vp = default_vp_format(fxp, q.M, q.E)
    return fxp, vp


def _kv_scale(x):
    """Per-position pow2 scale: smallest 2^n >= max|x| over (KV, dh)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-2, -1),
                   keepdims=True)
    return jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30))))


def quantize_kv(x, q: QuantConfig, layout: str = "packed"):
    """bf16 KV block (B, S, KV, dh) -> VP storage + per-position pow2
    scale.

    layout "packed" (default): ONE packed VP word per element
    (`core.packing`: sign + significand + exponent index,
    `vp.storage_bits` bits) -> (w, s).  This is the layout the
    decode-attention kernel consumes directly — no per-step index
    unpacking, no two-plane HBM reads.
    layout "planes": the legacy (int8 significand, bit-packed uint8
    index) planes -> (m, i, s), kept as the golden jnp oracle the
    packed path is pinned against.
    """
    fxp, vp = kv_cache_formats(q)
    s = _kv_scale(x)
    xn = x.astype(jnp.float32) / s
    if layout == "packed":
        return kref.vp_quant_packed_ref(xn, fxp, vp), s.astype(jnp.float32)
    from repro.core.vp_tensor import pack_indices

    m, i = kref.vp_quant_ref(xn, fxp, vp)
    if vp.E and x.shape[-1] % (8 // vp.E) == 0:
        i = pack_indices(i, vp.E)
    return m, i, s.astype(jnp.float32)


def dequantize_kv(m, i, s, q: QuantConfig, dtype):
    """Planes cache -> reals (the legacy whole-cache jnp dequant)."""
    from repro.core.vp_tensor import unpack_indices

    _, vp = kv_cache_formats(q)
    if i.shape[-1] != m.shape[-1]:
        i = unpack_indices(i, vp.E, m.shape[-1])
    return (kref.vp_dequant_ref(m, i, vp, jnp.float32) * s).astype(dtype)


def dequantize_kv_packed(w, s, q: QuantConfig, dtype):
    """Packed-word cache -> reals (offline whole-word LUT, bit-identical
    to `dequantize_kv` on the planes it packs)."""
    from repro.core.packing import dequant_words

    _, vp = kv_cache_formats(q)
    return (dequant_words(w, vp, jnp.float32) * s).astype(dtype)


def decode_attention(
    q, k_cache, v_cache, cache_len,
    window: Optional[int] = None,
    rolling: bool = False,
) -> jax.Array:
    """Single-token decode: q (B, 1, H, dh), caches (B, Smax, KV, dh).

    Masks positions >= cache_len (and outside the sliding window if given).
    `rolling`: the buffer IS the window (SWA ring buffer) — every slot
    written so far is valid, no window masking by absolute position.
    When a non-rolling `window` bounds the valid span and Smax is
    statically larger, the cache is sliced to the window before the
    einsum (O(window) scores instead of O(Smax) — see
    `kernels.ref._decode_attention_core`, the shared implementation).
    """
    return kref.decode_attention_ref(q, k_cache, v_cache, cache_len,
                                     window=window, rolling=rolling)


# ---------------------------------------------------------------------------
# Full attention block (projections + norms + rope + flash/decode)
# ---------------------------------------------------------------------------

def _cache_buf(cache: dict):
    """The key buffer of any cache layout (float / planes / packed)."""
    for key in ("k", "k_m", "k_w"):
        if key in cache:
            return cache[key]
    raise KeyError(f"unrecognized KV cache layout: {sorted(cache)}")


def _chunked_prefill_attention(qp, k_all, v_all, offset, hist_len: int):
    """Causal attention of a prompt chunk against cache history + itself.

    qp (B, S, H, dh) is the chunk's queries; k_all/v_all (B, hist_len+S,
    KV, dh) are the dequantized cache history concatenated with the
    chunk's own K/V.  offset (B,) is the valid history span: history
    position t contributes iff t < offset, chunk position c iff c <= s
    (intra-chunk causality).  Everything past offset is masked to
    NEG_INF, so garbage in unwritten cache slots cannot leak.
    """
    B, S, H, dh = qp.shape
    KV = k_all.shape[2]
    G = H // KV
    qr = qp.reshape(B, S, KV, G, dh).astype(jnp.float32) * dh ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", qr,
                        k_all.astype(jnp.float32))
    t = jnp.arange(k_all.shape[1], dtype=jnp.int32)[None, None, :]
    s_idx = jnp.arange(S, dtype=jnp.int32)[None, :, None]
    mask = (t < offset[:, None, None]) | \
        ((t >= hist_len) & (t - hist_len <= s_idx))      # (B, S, T)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgst,btkd->bskgd", p / jnp.maximum(l, 1e-30),
                     v_all.astype(jnp.float32))
    return out.reshape(B, S, H, dh).astype(qp.dtype)


def attn_block(
    x, params, cfg: ModelConfig,
    positions,
    pattern: str,
    window: Optional[int],
    cache: Optional[dict] = None,
    train: bool = False,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
    chunked: bool = False,
):
    """Self/cross attention block.

    cache: {"k": (B, Smax, KV, dh) floats, "v": ..., "len": (B,)} — or
    the VP-quantized layouts: packed words {"k_w", "k_s", "v_w", "v_s"}
    (kernel-consumed, default) / legacy planes {"k_m", "k_i", "k_s", ...}
    -> returns (out, new_cache).  kv_override supplies precomputed
    encoder K/V for cross-attention.

    chunked: the multi-token input is a prompt CHUNK appended at offset
    `cache["len"]` (continuous-batching prefill) rather than the start
    of an empty cache — the chunk attends to the already-written history
    plus itself, and its K/V are written at the offset.  Full-causal
    caches only (a rolling ring's chunk writes would need wraparound
    bookkeeping no caller exercises).
    """
    q_cfg = cfg.quant
    B = x.shape[0]
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    qp = qdot(x, params["wq"], q_cfg, train)
    if params.get("bq") is not None:
        qp = qp + params["bq"].astype(qp.dtype)
    qp = qp.reshape(*x.shape[:-1], H, dh)

    if kv_override is None:
        kp = qdot(x, params["wk"], q_cfg, train)
        vp_ = qdot(x, params["wv"], q_cfg, train)
        if params.get("bk") is not None:
            kp = kp + params["bk"].astype(kp.dtype)
            vp_ = vp_ + params["bv"].astype(vp_.dtype)
        kp = kp.reshape(*x.shape[:-1], KV, dh)
        vp_ = vp_.reshape(*x.shape[:-1], KV, dh)
    else:
        kp, vp_ = kv_override

    if cfg.qk_norm:
        qp = rms_norm(qp, params["q_norm"])
        kp = rms_norm(kp, params["k_norm"]) if kv_override is None else kp

    if positions is not None and kv_override is None:
        qp = rope(qp, positions, cfg.rope_theta)
        kp = rope(kp, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and kv_override is None and x.shape[1] > 1 \
            and chunked:
        # CHUNKED PREFILL: this chunk's queries attend to the valid
        # cache history (dequantized once per chunk — O(chunk * hist)
        # like any prefill, unlike the per-token decode path which never
        # dequantizes the whole cache) plus the chunk itself; the
        # chunk's K/V append at offset `len` via the same per-position
        # quantization the one-shot path uses.
        if window is not None:
            raise NotImplementedError(
                "chunked prefill over rolling/windowed caches is not "
                "implemented; use whole-prompt prefill")
        S = x.shape[1]
        smax = _cache_buf(cache).shape[1]
        idx = cache["len"]  # (B,)
        if "k_w" in cache:
            k_hist = dequantize_kv_packed(cache["k_w"], cache["k_s"],
                                          q_cfg, kp.dtype)
            v_hist = dequantize_kv_packed(cache["v_w"], cache["v_s"],
                                          q_cfg, vp_.dtype)
        elif "k_m" in cache:
            k_hist = dequantize_kv(cache["k_m"], cache["k_i"],
                                   cache["k_s"], q_cfg, kp.dtype)
            v_hist = dequantize_kv(cache["v_m"], cache["v_i"],
                                   cache["v_s"], q_cfg, vp_.dtype)
        else:
            k_hist, v_hist = cache["k"].astype(kp.dtype), \
                cache["v"].astype(vp_.dtype)
        k_all = jnp.concatenate([k_hist, kp.astype(k_hist.dtype)], axis=1)
        v_all = jnp.concatenate([v_hist, vp_.astype(v_hist.dtype)], axis=1)
        out = _chunked_prefill_attention(qp, k_all, v_all, idx, smax)
        upd = lambda buf, val: jax.vmap(
            lambda b, v, j: jax.lax.dynamic_update_slice_in_dim(
                b, v, j, axis=0))(buf, val, idx)
        if "k_w" in cache:
            w_k, s_k = quantize_kv(kp, q_cfg)
            w_v, s_v = quantize_kv(vp_, q_cfg)
            new_cache = dict(
                k_w=upd(cache["k_w"], w_k), k_s=upd(cache["k_s"], s_k),
                v_w=upd(cache["v_w"], w_v), v_s=upd(cache["v_s"], s_v),
                len=idx + S)
        elif "k_m" in cache:
            m_k, i_k, s_k = quantize_kv(kp, q_cfg, layout="planes")
            m_v, i_v, s_v = quantize_kv(vp_, q_cfg, layout="planes")
            new_cache = dict(
                k_m=upd(cache["k_m"], m_k), k_i=upd(cache["k_i"], i_k),
                k_s=upd(cache["k_s"], s_k),
                v_m=upd(cache["v_m"], m_v), v_i=upd(cache["v_i"], i_v),
                v_s=upd(cache["v_s"], s_v), len=idx + S)
        else:
            new_cache = dict(k=upd(cache["k"], kp.astype(cache["k"].dtype)),
                             v=upd(cache["v"], vp_.astype(cache["v"].dtype)),
                             len=idx + S)
        out = out.reshape(*x.shape[:-1], H * dh)
        return qdot(out, params["wo"], q_cfg, train), new_cache
    if cache is not None and kv_override is None and x.shape[1] > 1:
        # PREFILL: full causal pass over the prompt, then write all S
        # positions into the cache in one shot.
        S = x.shape[1]
        smax = _cache_buf(cache).shape[1]
        out = flash_attention(qp, kp, vp_, pattern=pattern, window=window)
        kw, vw = kp, vp_
        if S > smax:  # ring buffer shorter than prompt: keep the tail,
            # arranged so slot j holds position p with p % smax == j (the
            # decode writer uses len % smax).
            kw = jnp.roll(kp[:, -smax:], S % smax, axis=1)
            vw = jnp.roll(vp_[:, -smax:], S % smax, axis=1)
        pad = smax - kw.shape[1]
        if pad:
            kw = jnp.pad(kw, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vw = jnp.pad(vw, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if "k_w" in cache:  # packed-word VP cache (kernel layout)
            w_k, s_k = quantize_kv(kw, q_cfg)
            w_v, s_v = quantize_kv(vw, q_cfg)
            new_cache = dict(k_w=w_k, k_s=s_k, v_w=w_v, v_s=s_v,
                             len=cache["len"] + S)
        elif "k_m" in cache:
            m_k, i_k, s_k = quantize_kv(kw, q_cfg, layout="planes")
            m_v, i_v, s_v = quantize_kv(vw, q_cfg, layout="planes")
            new_cache = dict(
                k_m=m_k, k_i=i_k, k_s=s_k, v_m=m_v, v_i=i_v, v_s=s_v,
                len=cache["len"] + S)
        else:
            new_cache = dict(k=kw.astype(cache["k"].dtype),
                             v=vw.astype(cache["v"].dtype),
                             len=cache["len"] + S)
        out = out.reshape(*x.shape[:-1], H * dh)
        return qdot(out, params["wo"], q_cfg, train), new_cache
    if cache is not None and kv_override is None and "pages" in cache:
        # Paged decode (`serving.runner`): the cache is the WHOLE stacked
        # page pools, the block table and this layer's index, plus a
        # small tail of the positions not yet committed to the pools.
        # This step's K/V join the tail; the op reads the committed
        # positions page by page, so no contiguous view is built.
        pages = cache["pages"]
        idx = cache["len"]  # (B,)
        # Tail slot of this step per row, written by a select: a batched
        # dynamic_update_slice becomes a loop over rows on TPU.
        at = idx - pages["base"]
        hit = jnp.arange(cache["k_w"].shape[1])[None] == at[:, None]
        upd = lambda buf, val: jnp.where(
            hit.reshape(hit.shape + (1,) * (buf.ndim - 2)),
            val.astype(buf.dtype), buf)
        with jax.named_scope("kv_append"):
            w_k, s_k = quantize_kv(kp, q_cfg)
            w_v, s_v = quantize_kv(vp_, q_cfg)
            new_cache = dict(
                k_w=upd(cache["k_w"], w_k), k_s=upd(cache["k_s"], s_k),
                v_w=upd(cache["v_w"], w_v), v_s=upd(cache["v_s"], s_v),
                len=idx + kp.shape[1])
        _, vp_fmt = kv_cache_formats(q_cfg)
        out = kops.vp_paged_decode_attention(
            qp, pages["k_w"], pages["v_w"], pages["k_s"], pages["v_s"],
            new_cache["k_w"], new_cache["v_w"], new_cache["k_s"],
            new_cache["v_s"], cache["layer"], pages["block_table"],
            pages["base"], new_cache["len"], vp_fmt, window=window)
    elif cache is not None and kv_override is None:
        # Decode: append this step's K/V.  A buffer no longer than the
        # sliding window acts as a ring buffer (long-context SWA decode).
        smax = _cache_buf(cache).shape[1]
        rolling = window is not None and smax <= window
        idx = cache["len"]  # (B,)
        widx = idx % smax if rolling else idx
        upd = lambda buf, val: jax.vmap(
            lambda b, v, j: jax.lax.dynamic_update_slice_in_dim(
                b, v, j, axis=0))(buf, val, widx)
        if "k_w" in cache:
            # Packed-word VP cache: the words go straight to the
            # decode-attention kernel op — unpack + bit-assembled pow2
            # scale happen in-tile, and seq tiles outside the valid span
            # are skipped.  The whole cache is never dequantized in XLA.
            with jax.named_scope("kv_append"):
                w_k, s_k = quantize_kv(kp, q_cfg)
                w_v, s_v = quantize_kv(vp_, q_cfg)
                new_cache = dict(
                    k_w=upd(cache["k_w"], w_k), k_s=upd(cache["k_s"], s_k),
                    v_w=upd(cache["v_w"], w_v), v_s=upd(cache["v_s"], s_v),
                    len=idx + kp.shape[1],
                )
            _, vp_fmt = kv_cache_formats(q_cfg)
            out = kops.vp_decode_attention(
                qp, new_cache["k_w"], new_cache["v_w"],
                new_cache["k_s"], new_cache["v_s"], new_cache["len"],
                vp_fmt, window=window, rolling=rolling)
        else:
            if "k_m" in cache:  # legacy planes VP cache (golden baseline)
                with jax.named_scope("kv_append"):
                    m_k, i_k, s_k = quantize_kv(kp, q_cfg, layout="planes")
                    m_v, i_v, s_v = quantize_kv(vp_, q_cfg, layout="planes")
                    new_cache = dict(
                        k_m=upd(cache["k_m"], m_k),
                        k_i=upd(cache["k_i"], i_k),
                        k_s=upd(cache["k_s"], s_k),
                        v_m=upd(cache["v_m"], m_v),
                        v_i=upd(cache["v_i"], i_v),
                        v_s=upd(cache["v_s"], s_v),
                        len=idx + kp.shape[1],
                    )
                k_full = dequantize_kv(
                    new_cache["k_m"], new_cache["k_i"], new_cache["k_s"],
                    q_cfg, kp.dtype)
                v_full = dequantize_kv(
                    new_cache["v_m"], new_cache["v_i"], new_cache["v_s"],
                    q_cfg, vp_.dtype)
            else:
                with jax.named_scope("kv_append"):
                    new_cache = dict(
                        k=upd(cache["k"], kp), v=upd(cache["v"], vp_),
                        len=idx + kp.shape[1],
                    )
                k_full, v_full = new_cache["k"], new_cache["v"]
            out = decode_attention(
                qp, k_full, v_full, new_cache["len"], window,
                rolling=rolling)
    elif kv_override is not None:
        if qp.shape[1] == 1:
            # Cross-attention during decode: full-length source.
            src_len = jnp.full((B,), kp.shape[1], jnp.int32)
            out = decode_attention(qp, kp, vp_, src_len)
        else:
            out = flash_attention(qp, kp, vp_, pattern="full")
    else:
        out = flash_attention(qp, kp, vp_, pattern=pattern, window=window)

    out = out.reshape(*x.shape[:-1], H * dh)
    out = qdot(out, params["wo"], q_cfg, train)
    return out, new_cache
