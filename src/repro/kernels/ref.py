"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are tested against
(`tests/test_kernels.py` sweeps shapes/dtypes and asserts allclose), and the
CPU execution path used by models / the dry-run (same math, no Pallas).

The oracle entry points are `jax.jit`-compiled (formats/tiles static):
eagerly, each quantize cascade dispatches ~10 elementwise XLA ops PER
exponent option and materializes every intermediate — at serving batch
sizes that is pure HBM/cache traffic, and it made the CPU engine path's
per-element cost grow with the working set (the BENCH_pr2 OFDM S=64
regression).  Under jit the cascades fuse into one loop; numerics are
unchanged (same ops, no reassociation), which the parity suites pin.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.formats import FXPFormat, VPFormat
from repro.core.fxp import fxp_quantize
from repro.core.convert import fxp2vp, vp_to_float
from repro.core.packing import pack_vp, unpack_vp, dequant_words


@functools.partial(jax.jit, static_argnames=("fxp", "vp"))
def vp_quant_ref(x, fxp: FXPFormat, vp: VPFormat):
    """float -> (int8 significand, uint8 index) through the FXP grid."""
    raw = fxp_quantize(x, fxp)
    m, i = fxp2vp(raw, fxp, vp)
    from repro.core.vp_tensor import significand_dtype

    return m.astype(significand_dtype(vp.M)), i.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("fxp", "vp"))
def vp_quant_packed_ref(x, fxp: FXPFormat, vp: VPFormat):
    """float -> packed VP words (`core.packing` layout, one plane)."""
    raw = fxp_quantize(x, fxp)
    m, i = fxp2vp(raw, fxp, vp)
    return pack_vp(m, i, vp)


@functools.partial(jax.jit, static_argnames=("vp", "dtype"))
def vp_dequant_ref(m, i, vp: VPFormat, dtype=jnp.float32):
    """(significand, index) -> real values m * 2^-f_i."""
    return vp_to_float(m, i, vp, dtype)


@functools.partial(jax.jit, static_argnames=("vp", "dtype"))
def vp_dequant_packed_ref(w, vp: VPFormat, dtype=jnp.float32):
    """packed VP words -> real values (word-LUT / unpack oracle)."""
    return dequant_words(w, vp, dtype)


def tile_activity(x_abs_max, threshold: float):
    """CSPADE tile-activity flag: a tile is 'loud' if its max magnitude
    reaches the threshold (paper Sec. IV-A, tile-granular adaptation)."""
    return x_abs_max >= threshold


def cspade_tile_masks(
    a_deq, b_deq, bm: int, bk: int, bn: int,
    thresh_a: float, thresh_b: float,
) -> Tuple[jax.Array, jax.Array]:
    """Per-tile activity of A (M,K) and B (K,N) on the kernel tiling grid.

    A partial-product TILE is skipped when BOTH operand tiles are quiet —
    the tile-granular analogue of CSPADE's per-scalar muting.
    Returns (a_act [M/bm, K/bk], b_act [K/bk, N/bn]) int32 flags.
    """
    M, K = a_deq.shape
    _, N = b_deq.shape
    a_tiles = jnp.abs(a_deq).reshape(M // bm, bm, K // bk, bk).max((1, 3))
    b_tiles = jnp.abs(b_deq).reshape(K // bk, bk, N // bn, bn).max((1, 3))
    return (
        tile_activity(a_tiles, thresh_a).astype(jnp.int32),
        tile_activity(b_tiles, thresh_b).astype(jnp.int32),
    )


@functools.partial(
    jax.jit, static_argnames=("a_fmt", "b_fmt", "tiles", "out_dtype"))
def vp_matmul_ref(
    a_m, a_i, b_m, b_i,
    a_fmt: VPFormat, b_fmt: VPFormat,
    a_act: Optional[jax.Array] = None,
    b_act: Optional[jax.Array] = None,
    tiles: Tuple[int, int, int] = (128, 128, 128),
    out_dtype=jnp.float32,
):
    """VP x VP matmul oracle: dequantize then f32 matmul.

    With activity masks, contributions from tile-pairs where BOTH operands
    are quiet are zeroed (exactly what the kernel's `pl.when` skip does).
    """
    a = vp_to_float(a_m, a_i, a_fmt, out_dtype)
    b = vp_to_float(b_m, b_i, b_fmt, out_dtype)
    if a_act is None:
        return a @ b
    bm, bk, bn = tiles
    M, K = a.shape
    _, N = b.shape
    nm, nk, nn = M // bm, K // bk, N // bn
    # mute[mi, ki, ni]: both quiet -> kill that tile-pair's contribution.
    keep = (a_act[:, :, None] | b_act[None, :, :]).astype(out_dtype)
    a_t = a.reshape(nm, bm, nk, bk).transpose(0, 2, 1, 3)
    b_t = b.reshape(nk, bk, nn, bn).transpose(0, 2, 1, 3)
    # per-(mi,ki,ni) tile product
    prod = jnp.einsum("xyab,yzbc->xyzac", a_t, b_t)
    prod = prod * keep[:, :, :, None, None]
    out = prod.sum(1)  # sum over k tiles
    return out.transpose(0, 2, 1, 3).reshape(M, N)


@functools.partial(
    jax.jit, static_argnames=("a_fmt", "b_fmt", "tiles", "out_dtype"))
def vp_matmul_packed_ref(
    a_w, b_w,
    a_fmt: VPFormat, b_fmt: VPFormat,
    a_act: Optional[jax.Array] = None,
    b_act: Optional[jax.Array] = None,
    tiles: Tuple[int, int, int] = (128, 128, 128),
    out_dtype=jnp.float32,
):
    """Packed-word matmul oracle: unpack INSIDE the jit (no eager unpack
    round-trip), then the plane oracle — bit-identical to
    `vp_matmul_ref(*unpack_vp(a_w), *unpack_vp(b_w))`."""
    a_m, a_i = unpack_vp(a_w, a_fmt)
    b_m, b_i = unpack_vp(b_w, b_fmt)
    return vp_matmul_ref(
        a_m, a_i, b_m, b_i, a_fmt, b_fmt,
        a_act=a_act, b_act=b_act, tiles=tiles, out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("w_fmt", "out_dtype"))
def vp_dequant_matmul_ref(
    x, w,
    w_fmt: VPFormat,
    out_dtype=jnp.float32,
):
    """Serving-matmul oracle: real x (M, K) @ dequant(packed w (K, N)).

    Unpack + dequant happen INSIDE the jit in `out_dtype` (the model's
    compute dtype), then one plain dot — exactly the computation the
    models' legacy jnp-dequant path ran on two-plane weights, so the
    cross-arch golden-parity suite can pin the kernel path against it
    bit for bit (power-of-two scales are exact in any float dtype).
    Unlike the masked-matmul oracles this one takes NO `tiles`: the math
    is tile-independent, and a static tiling arg would force a fresh XLA
    compile per resolved block triple (pure churn on the ref backend).
    Dequant goes through the offline whole-word LUT
    (`core.packing.dequant_words`) when the format admits it — one gather
    per element instead of shift+mask+scale, bit-identical either way.
    """
    deq = dequant_words(w, w_fmt, out_dtype)
    return jnp.dot(x.astype(out_dtype), deq)


@functools.partial(
    jax.jit,
    static_argnames=("a_fxp", "a_vp", "b_fxp", "b_vp", "tiles", "out_dtype"))
def vp_quant_matmul_ref(
    a, b,
    a_fxp: FXPFormat, a_vp: VPFormat,
    b_fxp: FXPFormat, b_vp: VPFormat,
    a_act: Optional[jax.Array] = None,
    b_act: Optional[jax.Array] = None,
    tiles: Tuple[int, int, int] = (128, 128, 128),
    out_dtype=jnp.float32,
):
    """Fused quantize+matmul oracle: quantize both floats, then VP matmul.

    Exactly `vp_quant_ref` on each operand followed by `vp_matmul_ref` —
    the fused kernel must reproduce this composition bit-for-bit (it runs
    the same cascades, just without the HBM round-trip).
    """
    a_m, a_i = vp_quant_ref(a, a_fxp, a_vp)
    b_m, b_i = vp_quant_ref(b, b_fxp, b_vp)
    return vp_matmul_ref(
        a_m, a_i, b_m, b_i, a_vp, b_vp,
        a_act=a_act, b_act=b_act, tiles=tiles, out_dtype=out_dtype)


def cspade_tile_masks_batched(
    a_deq, b_deq, bm: int, bk: int, bn: int,
    thresh_a: float, thresh_b: float,
) -> Tuple[jax.Array, jax.Array]:
    """Per-(batch, tile) activity of A (G,M,K) and B (G,K,N) on the batched
    kernel grid: `cspade_tile_masks` with a leading batch axis.

    Returns (a_act [G, M/bm, K/bk], b_act [G, K/bk, N/bn]) int32 flags.
    On the MVM shapes (one tile per axis) this degenerates to one flag per
    realization — the batched analogue of muting a whole quiet request.
    """
    G, M, K = a_deq.shape
    _, _, N = b_deq.shape
    a_tiles = jnp.abs(a_deq).reshape(
        G, M // bm, bm, K // bk, bk).max((2, 4))
    b_tiles = jnp.abs(b_deq).reshape(
        G, K // bk, bk, N // bn, bn).max((2, 4))
    return (
        tile_activity(a_tiles, thresh_a).astype(jnp.int32),
        tile_activity(b_tiles, thresh_b).astype(jnp.int32),
    )


@functools.partial(
    jax.jit, static_argnames=("a_fmt", "b_fmt", "tiles", "out_dtype"))
def vp_matmul_batched_ref(
    a_m, a_i, b_m, b_i,
    a_fmt: VPFormat, b_fmt: VPFormat,
    a_act: Optional[jax.Array] = None,
    b_act: Optional[jax.Array] = None,
    tiles: Tuple[int, int, int] = (128, 128, 128),
    out_dtype=jnp.float32,
):
    """Batched VP x VP matmul oracle: (G, M, K) x (G, K, N) -> (G, M, N).

    Per batch element this is exactly `vp_matmul_ref`; with activity masks
    the muting is per (batch, tile-pair) like the batched kernel's skip.
    """
    a = vp_to_float(a_m, a_i, a_fmt, out_dtype)
    b = vp_to_float(b_m, b_i, b_fmt, out_dtype)
    if a_act is None:
        return jax.lax.dot_general(
            a, b, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=out_dtype)
    bm, bk, bn = tiles
    G, M, K = a.shape
    _, _, N = b.shape
    nm, nk, nn = M // bm, K // bk, N // bn
    keep = (a_act[:, :, :, None] | b_act[:, None, :, :]).astype(out_dtype)
    a_t = a.reshape(G, nm, bm, nk, bk).transpose(0, 1, 3, 2, 4)
    b_t = b.reshape(G, nk, bk, nn, bn).transpose(0, 1, 3, 2, 4)
    prod = jnp.einsum("gxyab,gyzbc->gxyzac", a_t, b_t)
    prod = prod * keep[:, :, :, :, None, None]
    out = prod.sum(2)
    return out.transpose(0, 1, 3, 2, 4).reshape(G, M, N)


@functools.partial(
    jax.jit, static_argnames=("a_fmt", "b_fmt", "tiles", "out_dtype"))
def vp_matmul_batched_packed_ref(
    a_w, b_w,
    a_fmt: VPFormat, b_fmt: VPFormat,
    a_act: Optional[jax.Array] = None,
    b_act: Optional[jax.Array] = None,
    tiles: Tuple[int, int, int] = (128, 128, 128),
    out_dtype=jnp.float32,
):
    """Batched packed-word matmul oracle (unpack fused into the jit)."""
    a_m, a_i = unpack_vp(a_w, a_fmt)
    b_m, b_i = unpack_vp(b_w, b_fmt)
    return vp_matmul_batched_ref(
        a_m, a_i, b_m, b_i, a_fmt, b_fmt,
        a_act=a_act, b_act=b_act, tiles=tiles, out_dtype=out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("a_fxp", "a_vp", "b_fxp", "b_vp", "tiles", "out_dtype"))
def vp_quant_matmul_batched_ref(
    a, b,
    a_fxp: FXPFormat, a_vp: VPFormat,
    b_fxp: FXPFormat, b_vp: VPFormat,
    a_act: Optional[jax.Array] = None,
    b_act: Optional[jax.Array] = None,
    tiles: Tuple[int, int, int] = (128, 128, 128),
    out_dtype=jnp.float32,
):
    """Batched fused quantize+matmul oracle: quantize, then batched matmul."""
    a_m, a_i = vp_quant_ref(a, a_fxp, a_vp)
    b_m, b_i = vp_quant_ref(b, b_fxp, b_vp)
    return vp_matmul_batched_ref(
        a_m, a_i, b_m, b_i, a_vp, b_vp,
        a_act=a_act, b_act=b_act, tiles=tiles, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# Attention oracles (decode over a VP cache + flash prefill)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _decode_attention_core(q, k_cache, v_cache, cache_len,
                           window: Optional[int], rolling: bool):
    """Masked single-token decode attention over a FLOAT cache (traced).

    q (B, 1, H, dh), caches (B, Smax, KV, dh) -> (B, 1, H, dh).  This is
    THE decode-attention math: `models.attention.decode_attention` and
    the packed-cache oracle below both call it, so the packed-vs-planes
    parity is bit-identical by construction (they differ only in the
    dequant, which `core.packing` pins bit-for-bit).

    When a non-rolling `window` bounds the valid span and the buffer is
    statically larger, the cache is SLICED to the window before the
    einsum — scores for positions the mask would zero anyway are never
    computed, so decode work is O(window), not O(Smax).  Masked-out
    entries contribute exactly 0 after the softmax's exp, so slicing
    only drops exact zeros from the contractions.
    """
    B, _, H, dh = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qr = q.reshape(B, KV, G, dh).astype(jnp.float32) * dh ** -0.5
    if not rolling and window and window < Smax:
        start = jnp.clip(cache_len - window, 0, Smax - window)
        slc = jax.vmap(functools.partial(
            jax.lax.dynamic_slice_in_dim, slice_size=window, axis=0))
        kc, vc = slc(k_cache, start), slc(v_cache, start)
        pos = start[:, None] + jnp.arange(window)[None, :]
    else:
        kc, vc = k_cache, v_cache
        pos = jnp.broadcast_to(jnp.arange(Smax)[None, :], (B, Smax))
    kr = kc.transpose(0, 2, 1, 3).astype(jnp.float32)
    vr = vc.transpose(0, 2, 1, 3).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qr, kr)
    if rolling:
        valid = pos < jnp.minimum(cache_len, Smax)[:, None]
    else:
        valid = pos < cache_len[:, None]
        if window:
            valid &= pos >= (cache_len[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, vr)
    return out.reshape(B, 1, H, dh).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("window", "rolling"))
def decode_attention_ref(q, k_cache, v_cache, cache_len,
                         window: Optional[int] = None,
                         rolling: bool = False):
    """Jitted float decode-attention oracle (see `_decode_attention_core`)."""
    return _decode_attention_core(q, k_cache, v_cache, cache_len,
                                  window, rolling)


@functools.partial(
    jax.jit, static_argnames=("fmt", "window", "rolling"))
def vp_decode_attention_ref(
    q, k_w, v_w, k_s, v_s, lengths,
    fmt: VPFormat,
    window: Optional[int] = None,
    rolling: bool = False,
):
    """Packed-KV decode oracle: dequant INSIDE the jit, then the shared
    decode core.

    k_w / v_w (B, Smax, KV, dh) packed VP words, k_s / v_s per-position
    pow2 cache scales ((B, Smax) or (B, Smax, 1, 1)).  The dequant goes
    through the offline whole-word LUT (`core.packing.dequant_words`) —
    one gather per element instead of the planes path's index-unpack +
    select cascade, which is where the ref-backend decode speedup comes
    from — and mirrors the planes path's dtype hop (f32 dequant, scale,
    cast to the model dtype) so parity is bit-identical on this backend.
    """
    if k_s.ndim == 2:
        k_s = k_s[:, :, None, None]
    if v_s.ndim == 2:
        v_s = v_s[:, :, None, None]
    kr = (dequant_words(k_w, fmt, jnp.float32) * k_s).astype(q.dtype)
    vr = (dequant_words(v_w, fmt, jnp.float32) * v_s).astype(q.dtype)
    return _decode_attention_core(q, kr, vr, lengths, window, rolling)


@functools.partial(jax.jit, static_argnames=("fmt", "window"))
def vp_paged_decode_attention_ref(
    q, k_pool, v_pool, k_s_pool, v_s_pool, k_tail, v_tail, k_tail_s,
    v_tail_s, layer, block_table, base, lengths,
    fmt: VPFormat,
    window: Optional[int] = None,
):
    """Paged packed-KV decode oracle: layer `layer`'s pages gathered
    through the block table into a contiguous view, the in-flight tail
    written at `base`, then `vp_decode_attention_ref`.

    This is the computation a gathered-view decode step performs, so the
    paged path is bit-identical to it on this backend: positions past
    `lengths` differ (stale pages, an unfilled tail) but contribute
    exact zeros.
    """
    from .paged import gather_pages

    def view(pool, tail):
        cache = gather_pages(pool[layer][None], block_table)[0]
        return jax.vmap(lambda c, t, j: jax.lax.dynamic_update_slice_in_dim(
            c, t, j, axis=0))(cache, tail, base)

    return vp_decode_attention_ref(
        q, view(k_pool, k_tail), view(v_pool, v_tail),
        view(k_s_pool, k_tail_s), view(v_s_pool, v_tail_s), lengths, fmt,
        window=window)


@functools.partial(jax.jit, static_argnames=("pattern", "window"))
def flash_prefill_ref(q, k, v, pattern: str = "causal",
                      window: Optional[int] = None):
    """Unfused prefill-attention oracle: full (Sq, Sk) scores + mask.

    q (B, Sq, H, dh), k/v (B, Sk, KV, dh) -> (B, Sq, H, dh).  O(S^2)
    memory — the oracle the flash kernel (which never materializes the
    scores) is tested against; `models.attention.flash_attention`'s
    pair-scan is the bounded-memory production path off-TPU.
    """
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qr = q.reshape(B, Sq, KV, G, dh).astype(jnp.float32) * dh ** -0.5
    kr = k.astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qr, kr)
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    if pattern in ("causal", "local"):
        mask = k_pos <= q_pos
        if pattern == "local" and window:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, dh).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("a_fmt", "b_fmt", "bk", "out_dtype"))
def block_vp_matmul_ref(
    a_m, a_i, b_m, b_i,
    a_fmt: VPFormat, b_fmt: VPFormat,
    bk: int,
    out_dtype=jnp.float32,
):
    """Block-VP matmul oracle.

    a_m (M, K) int8 significands with a_i (M, K//bk) per-(row, k-block)
    exponent indices; b_m (K, N) with b_i (K//bk, N).  Within k-block `t`:
      out += (lutA[a_i[:, t]] outer lutB[b_i[t, :]]) * (A_t @ B_t in int32)
    """
    M, K = a_m.shape
    _, N = b_m.shape
    nk = K // bk
    lut_a = jnp.asarray([2.0 ** (-fv) for fv in a_fmt.f], out_dtype)
    lut_b = jnp.asarray([2.0 ** (-fv) for fv in b_fmt.f], out_dtype)
    out = jnp.zeros((M, N), out_dtype)
    for t in range(nk):
        at = a_m[:, t * bk:(t + 1) * bk]
        bt = b_m[t * bk:(t + 1) * bk, :]
        acc = jax.lax.dot_general(
            at, bt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        sa = lut_a[a_i[:, t].astype(jnp.int32)]
        sb = lut_b[b_i[t, :].astype(jnp.int32)]
        out = out + acc.astype(out_dtype) * sa[:, None] * sb[None, :]
    return out


# ---------------------------------------------------------------------------
# Backward-pass oracles (custom-VJP grad matmuls over packed words)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("w_fmt", "out_dtype"))
def vp_matmul_dx_ref(
    g, w,
    w_fmt: VPFormat,
    out_dtype=jnp.float32,
):
    """Transposed serving-matmul oracle: g (M, N) @ dequant(w (K, N))^T.

    This is EXACTLY what `jax.grad` of `vp_dequant_matmul_ref` computes
    for the activation cotangent — XLA transposes `dot_general(x, deq,
    contract (1, 0))` into `dot_general(g, deq, contract (1, 1))` — so
    the custom-VJP grad check can pin the rule bit-for-bit against the
    autodiff-through-dequant oracle on the ref backend."""
    deq = dequant_words(w, w_fmt, out_dtype)
    return jax.lax.dot_general(
        g.astype(out_dtype), deq, (((1,), (1,)), ((), ())),
        preferred_element_type=out_dtype)


@functools.partial(jax.jit, static_argnames=("a_fmt", "out_dtype"))
def vp_matmul_dw_ref(
    a_w, g,
    a_fmt: VPFormat,
    out_dtype=jnp.float32,
):
    """Second-operand grad oracle: dequant(a_w (M, K))^T @ g (M, N).

    The STE backward of the fused quantize-matmul w.r.t. its second
    operand, consuming the PACKED quantized first operand saved as the
    VJP residual — mirrors XLA's transpose of `dot_general(deq_a, b,
    contract (1, 0))` w.r.t. b: `dot_general(deq_a, g, contract (0, 0))`."""
    deq = dequant_words(a_w, a_fmt, out_dtype)
    return jax.lax.dot_general(
        deq, g.astype(out_dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=out_dtype)
