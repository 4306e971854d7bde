"""Fused VP-cache attention: cross-layout KV parity + kernel conformance.

PR 5 moves the serving attention hot path onto the packed-word VP cache:
`quantize_kv` emits ONE packed word per element, `attn_block` hands the
cache words to the `vp_decode_attention` kernel op, and prefill gains a
fused flash kernel on TPU backends.  This suite pins:

  * packed-vs-planes cache parity, BIT-IDENTICAL on the jnp ref backend
    (the CI environment): per element and end-to-end through every
    decode grid — full/windowed/rolling-ring, GQA, decode vs
    prefill-tail cache writes;
  * property tests: the packed KV round-trip under RANDOM (M, E)
    formats recovers the planes layout exactly;
  * kernel conformance: the Pallas decode and flash-prefill kernel
    bodies (interpreter) match their jnp oracles, including ragged
    (padded) cache lengths and chunk-unaligned sequence lengths;
  * the `_pick_chunk` prime-length regression: a prime Sq now pads to
    one power-of-two chunk instead of degrading to chunk=1 and an S^2
    singleton-pair scan;
  * the decode window-slice fast path == the legacy whole-cache mask.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.configs.base import QuantConfig
from repro.core import FXPFormat, default_vp_format
from repro.core.packing import pack_vp, storage_dtype, unpack_vp
from repro.kernels import autotune, ops, ref as kref, substrate
from repro.models import init_params, init_cache, prefill, decode_step
from repro.models.attention import (
    _chunk_and_pad,
    decode_attention,
    dequantize_kv,
    dequantize_kv_packed,
    flash_attention,
    kv_cache_formats,
    quantize_kv,
)

REF_BACKEND = substrate.resolve_backend(None) == "ref"
KVQ = QuantConfig(mode="none", quantize_kv_cache=True)


def assert_parity(got, want, err_msg=""):
    """Bit-identical on the shared jnp ref path; tight otherwise."""
    if REF_BACKEND:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=err_msg)
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6, err_msg=err_msg)


def _random_kv(key, B, S, KV, dh):
    kk, kv_, kq = jax.random.split(key, 3)
    k = jax.random.normal(kk, (B, S, KV, dh), jnp.float32) * 2.0
    v = jax.random.normal(kv_, (B, S, KV, dh), jnp.float32)
    return k, v, kq


# ---------------------------------------------------------------------------
# Satellite: _pick_chunk prime-length regression
# ---------------------------------------------------------------------------

def test_chunk_and_pad_never_degenerates():
    # the old largest-divisor policy gave chunk=1 for any prime
    assert _chunk_and_pad(509) == (512, 512)
    assert _chunk_and_pad(512) == (512, 512)
    assert _chunk_and_pad(700) == (512, 1024)
    assert _chunk_and_pad(16) == (16, 16)
    for s in (127, 509, 1021):
        c, sp = _chunk_and_pad(s)
        assert c >= min(s, 128) and sp % c == 0 and sp >= s


@pytest.mark.parametrize("pattern,window,sq,sk", [
    ("causal", None, 509, 509),     # prime: the regression shape
    ("local", 37, 127, 127),
    ("full", None, 37, 53),         # ragged cross-attention
])
def test_flash_attention_unaligned_lengths(pattern, window, sq, sk):
    """Chunk-unaligned (incl. prime) lengths pad+mask instead of
    degrading to singleton chunks; output matches the O(S^2) oracle."""
    B, KV, G, dh = 2, 2, 2, 16
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, sq, KV * G, dh), jnp.float32)
    k, v, _ = _random_kv(jax.random.PRNGKey(1), B, sk, KV, dh)
    out = flash_attention(q, k, v, pattern=pattern, window=window)
    want = kref.flash_prefill_ref(q, k, v, pattern=pattern, window=window)
    assert out.shape == (B, sq, KV * G, dh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Satellite: decode window slicing == legacy whole-cache mask
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,smax,lens", [
    (16, 100, (3, 40, 100)),
    (64, 256, (1, 200, 256)),
])
def test_decode_window_slice_matches_whole_cache_mask(window, smax, lens):
    B, KV, G, dh = len(lens), 2, 3, 16
    H = KV * G
    key = jax.random.PRNGKey(2)
    k, v, kq = _random_kv(key, B, smax, KV, dh)
    q = jax.random.normal(kq, (B, 1, H, dh), jnp.float32)
    cache_len = jnp.asarray(lens, jnp.int32)
    got = decode_attention(q, k, v, cache_len, window=window)

    # legacy path: scores for ALL smax positions, mask, softmax
    qr = q.reshape(B, KV, G, dh) * dh ** -0.5
    s = jnp.einsum("bkgd,bksd->bkgs", qr, k.transpose(0, 2, 1, 3))
    pos = jnp.arange(smax)[None, :]
    valid = (pos < cache_len[:, None]) & (pos >= cache_len[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, -1)
    want = jnp.einsum("bkgs,bksd->bkgd", p, v.transpose(0, 2, 1, 3))
    want = want.reshape(B, 1, H, dh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Property: packed KV round-trip under random (M, E) formats
# ---------------------------------------------------------------------------

@given(M=st.integers(3, 8), E=st.integers(1, 2), seed=st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_packed_kv_roundtrip_random_formats(M, E, seed):
    """quantize_kv packed words == pack(planes) and dequantize exactly,
    for random KV formats on the canonical FXP grid."""
    q = QuantConfig(mode="none", M=M, E=E, quantize_kv_cache=True)
    fxp, vp = kv_cache_formats(q)
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (2, 5, 2, 8), jnp.float32) * 3.0
    w, s = quantize_kv(x, q)
    assert w.dtype == storage_dtype(vp) and w.shape == x.shape
    m, i_packed, s2 = quantize_kv(x, q, layout="planes")
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))
    mw, iw = unpack_vp(w, vp)
    np.testing.assert_array_equal(np.asarray(mw), np.asarray(m))
    np.testing.assert_array_equal(
        np.asarray(pack_vp(mw, iw, vp)), np.asarray(w))
    deq_w = dequantize_kv_packed(w, s, q, jnp.float32)
    deq_p = dequantize_kv(m, i_packed, s2, q, jnp.float32)
    np.testing.assert_array_equal(np.asarray(deq_w), np.asarray(deq_p))


# ---------------------------------------------------------------------------
# Cross-layout cache parity: packed vs planes, every decode grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,rolling,G", [
    (None, False, 1),        # full span, MHA
    (None, False, 4),        # full span, GQA
    (24, False, 2),          # bounded window, buffer larger than window
    (24, True, 2),           # rolling ring (buffer IS the window)
])
def test_packed_vs_planes_decode_parity(window, rolling, G):
    """The tentpole contract: packed-word decode attention is
    bit-identical to the legacy dequant-whole-cache planes path on the
    ref backend (power-of-two scales are exact; both run the shared
    decode core)."""
    B, smax, KV, dh = 3, 64, 2, 16
    H = KV * G
    key = jax.random.PRNGKey(5)
    k, v, kq = _random_kv(key, B, smax, KV, dh)
    q = jax.random.normal(kq, (B, 1, H, dh), jnp.float32)
    lens = jnp.asarray([7, 40, 64], jnp.int32)
    _, vp = kv_cache_formats(KVQ)

    w_k, s_k = quantize_kv(k, KVQ)
    w_v, s_v = quantize_kv(v, KVQ)
    got = ops.vp_decode_attention(q, w_k, w_v, s_k, s_v, lens, vp,
                                  window=window, rolling=rolling)

    m_k, i_k, ps_k = quantize_kv(k, KVQ, layout="planes")
    m_v, i_v, ps_v = quantize_kv(v, KVQ, layout="planes")
    k_full = dequantize_kv(m_k, i_k, ps_k, KVQ, q.dtype)
    v_full = dequantize_kv(m_v, i_v, ps_v, KVQ, q.dtype)
    want = decode_attention(q, k_full, v_full, lens, window=window,
                            rolling=rolling)
    assert got.shape == want.shape == (B, 1, H, dh)
    assert_parity(got, want, err_msg=f"w={window} roll={rolling} G={G}")


def test_prefill_tail_vs_decode_write_parity():
    """Writing position S via a one-shot prefill quantize vs a decode
    append produces bit-identical packed words and scales (per-position
    pow2 scales make the quantization independent of the write route)."""
    B, S, KV, dh = 2, 9, 2, 16
    k, _, _ = _random_kv(jax.random.PRNGKey(7), B, S, KV, dh)
    w_all, s_all = quantize_kv(k, KVQ)                     # prefill route
    w_head, s_head = quantize_kv(k[:, :S - 1], KVQ)        # decode route
    w_tail, s_tail = quantize_kv(k[:, S - 1:], KVQ)
    np.testing.assert_array_equal(
        np.asarray(w_all),
        np.asarray(jnp.concatenate([w_head, w_tail], axis=1)))
    np.testing.assert_array_equal(
        np.asarray(s_all),
        np.asarray(jnp.concatenate([s_head, s_tail], axis=1)))


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "mixtral-8x22b",
                                  "gemma3-27b"])
def test_model_kv_cache_layout_parity(arch):
    """Full-model golden parity across cache layouts: packed-kernel
    serving vs the planes jnp baseline, prefill + two decode steps, over
    causal / SWA-rolling-ring / local-global architectures."""
    outs = {}
    for layout in ("packed", "planes"):
        q = dataclasses.replace(KVQ, kv_layout=layout)
        cfg = registry.get_smoke_config(arch, quant=q)
        key = jax.random.PRNGKey(11)
        params = init_params(key, cfg)
        toks = jax.random.randint(key, (2, 8), 0, cfg.vocab)
        caches = init_cache(cfg, 2, 16)
        lo, caches = prefill(params, toks, caches, cfg)
        nxt = jnp.argmax(lo, -1)[:, None]
        lo2, caches = decode_step(params, nxt, caches, cfg)
        lo3, _ = decode_step(params, jnp.argmax(lo2, -1)[:, None],
                             caches, cfg)
        assert bool(jnp.isfinite(lo3).all()), (arch, layout)
        outs[layout] = tuple(np.asarray(x) for x in (lo, lo2, lo3))
    for stage in range(3):
        assert_parity(outs["packed"][stage], outs["planes"][stage],
                      err_msg=f"{arch} stage {stage}")


def test_init_cache_layouts():
    q = dataclasses.replace(KVQ, kv_layout="packed")
    cfg = registry.get_smoke_config("qwen3-0.6b", quant=q)
    _, vp = kv_cache_formats(cfg.quant)
    c = init_cache(cfg, 2, 16)[0]["sub0"]
    assert set(c) == {"k_w", "k_s", "v_w", "v_s", "len"}
    assert c["k_w"].dtype == storage_dtype(vp)
    cfg_p = registry.get_smoke_config(
        "qwen3-0.6b", quant=dataclasses.replace(KVQ, kv_layout="planes"))
    cp = init_cache(cfg_p, 2, 16)[0]["sub0"]
    assert {"k_m", "k_i", "k_s"} <= set(cp)


# ---------------------------------------------------------------------------
# Kernel conformance (interpret mode vs the jnp oracles)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,rolling,smax,G", [
    (None, False, 128, 2),
    (None, False, 100, 2),   # ragged: op pads the seq axis
    (16, False, 100, 1),
    (16, True, 100, 3),
])
def test_decode_attention_kernel_interpret_parity(window, rolling, smax, G):
    """The Pallas decode kernel body (interpreter) == the packed oracle,
    including the cache_len-aware tile skip and seq padding."""
    B, KV, dh = 2, 2, 32
    H = KV * G
    key = jax.random.PRNGKey(13)
    k, v, kq = _random_kv(key, B, smax, KV, dh)
    q = jax.random.normal(kq, (B, 1, H, dh), jnp.float32)
    lens = jnp.asarray([smax // 3, smax], jnp.int32)
    _, vp = kv_cache_formats(KVQ)
    w_k, s_k = quantize_kv(k, KVQ)
    w_v, s_v = quantize_kv(v, KVQ)
    args = (q, w_k, w_v, s_k, s_v, lens, vp)
    want = kref.vp_decode_attention_ref(*args, window=window,
                                        rolling=rolling)
    got = ops.vp_decode_attention(*args, window=window, rolling=rolling,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("pattern,window,sq,sk,G", [
    ("causal", None, 64, 64, 2),
    ("causal", None, 509, 509, 1),   # prime -> padded grid + fringe mask
    ("local", 24, 64, 64, 2),
    ("full", None, 37, 53, 4),       # ragged cross-attention shapes
])
def test_flash_prefill_kernel_interpret_parity(pattern, window, sq, sk, G):
    B, KV, dh = 2, 2, 16
    key = jax.random.PRNGKey(17)
    q = jax.random.normal(key, (B, sq, KV * G, dh), jnp.float32)
    k, v, _ = _random_kv(jax.random.PRNGKey(19), B, sk, KV, dh)
    want = kref.flash_prefill_ref(q, k, v, pattern=pattern, window=window)
    got = ops.flash_prefill(q, k, v, pattern=pattern, window=window,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    scan = flash_attention(q, k, v, pattern=pattern, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(scan),
                               rtol=1e-5, atol=1e-6)


def test_decode_kernel_rolling_ring_wrap_with_padding():
    """Regression: a rolling ring whose buffer is NOT a tile multiple,
    decoded past the wrap (lengths > buffer).  The kernel's ring clamp
    must use the REAL buffer length — clamping to the padded length let
    zero-score padding columns into the softmax denominator."""
    B, smax, KV, dh, G = 2, 24, 2, 32, 2
    H = KV * G
    key = jax.random.PRNGKey(31)
    k, v, kq = _random_kv(key, B, smax, KV, dh)
    q = jax.random.normal(kq, (B, 1, H, dh), jnp.float32)
    lens = jnp.asarray([30, 100], jnp.int32)   # both past the wrap
    _, vp = kv_cache_formats(KVQ)
    w_k, s_k = quantize_kv(k, KVQ)
    w_v, s_v = quantize_kv(v, KVQ)
    args = (q, w_k, w_v, s_k, s_v, lens, vp)
    want = kref.vp_decode_attention_ref(*args, window=smax, rolling=True)
    # blocks=(1, 32, 1): the 24-slot ring pads to 32 inside the op
    got = ops.vp_decode_attention(*args, window=smax, rolling=True,
                                  blocks=(1, 32, 1), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Autotune plumbing for the attention kernels
# ---------------------------------------------------------------------------

def test_attn_candidates_shapes():
    for sq, sk in ((1, 64), (4, 1024), (509, 509)):
        cands = autotune.attn_candidates(sq, sk)
        assert cands, (sq, sk)
        for bq, bk, one in cands:
            assert one == 1
            assert bq <= max(128, autotune._pow2_at_least(sq))
            assert bk <= max(512, autotune._pow2_at_least(sk))
            assert bq & (bq - 1) == 0 and bk & (bk - 1) == 0


def test_resolve_attn_blocks_cache_roundtrip(tmp_path, monkeypatch):
    """A tuned entry keyed on the FULL decode geometry (incl. window and
    rolling) is what `ops.vp_decode_attention` launches next time."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    autotune._caches.clear()
    _, vp = kv_cache_formats(KVQ)
    shape = (2, 256, 2, 32, 16, 0)
    key = autotune.make_key("vp_decode_attention", shape, (vp,),
                            "interpret")
    autotune.record(key, (1, 64, 1))
    got = autotune.resolve_attn_blocks(
        "vp_decode_attention", shape, (vp,), "interpret", sq=2, sk=256)
    assert got == (1, 64, 1)
    # a DIFFERENT window must not hit the same entry
    other = autotune.resolve_attn_blocks(
        "vp_decode_attention", (2, 256, 2, 32, 32, 0), (vp,), "interpret",
        sq=2, sk=256)
    assert other == (2, 256, 1)
    # and the tuned tile actually drives the kernel launch, numerics
    # unchanged vs the heuristic tile
    B, smax, KV, dh, G = 2, 256, 2, 32, 1
    k, v, kq = _random_kv(jax.random.PRNGKey(23), B, smax, KV, dh)
    q = jax.random.normal(kq, (B, 1, KV * G, dh), jnp.float32)
    lens = jnp.asarray([100, 256], jnp.int32)
    w_k, s_k = quantize_kv(k, KVQ)
    w_v, s_v = quantize_kv(v, KVQ)
    out_tuned = ops.vp_decode_attention(
        q, w_k, w_v, s_k, s_v, lens, vp, window=16, interpret=True)
    out_explicit = ops.vp_decode_attention(
        q, w_k, w_v, s_k, s_v, lens, vp, window=16, blocks=(1, 128, 1),
        interpret=True)
    np.testing.assert_allclose(np.asarray(out_tuned),
                               np.asarray(out_explicit),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Paged decode: pages read in place through the block table
# ---------------------------------------------------------------------------

# Pools of L layers x N pages of PS positions; rows of P pages (capacity
# P * PS).  Pages of 32 give 4 pages a 128-lane scale row, so a block is
# 4 pages and a row of 8 pages takes two blocks.
L_, N_, PS_, P_ = 2, 40, 32, 8
CAP_ = P_ * PS_


# 8-bit words: VP(7,[1,-1])'s M + E = 8 (KVQ's words are 16 bits).
KVQ8 = dataclasses.replace(KVQ, E=1)


def _paged_inputs(steps, G, seed=0, KV=2, kvq=KVQ):
    """Pools, a shuffled block table and ragged rows: committed lengths
    on and off page boundaries, one at capacity - steps, and a parked
    padding row (all-dummy block table, nothing committed)."""
    B, dh = 6, 32
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4, kq = jax.random.split(key, 5)
    k_w, k_s = quantize_kv(
        jax.random.normal(k1, (L_ * N_, PS_, KV, dh)) * 2.0, kvq)
    v_w, v_s = quantize_kv(jax.random.normal(k2, (L_ * N_, PS_, KV, dh)),
                           kvq)
    pools = [a.reshape((L_, N_) + a.shape[1:]) for a in (k_w, v_w, k_s, v_s)]
    t_kw, t_ks = quantize_kv(jax.random.normal(k3, (B, steps, KV, dh)), kvq)
    t_vw, t_vs = quantize_kv(jax.random.normal(k4, (B, steps, KV, dh)), kvq)
    base = np.array([1, PS_, PS_ + 1, 5 * PS_ - 1, CAP_ - steps, 0],
                    np.int32)
    fill = rng.integers(1, steps + 1, size=B).astype(np.int32)
    fill[4] = steps
    # live pages come shuffled from 1 .. N - 9; the last 8 ids are the
    # stale ones the block tables' unused tails point at
    ids = rng.permutation(np.arange(1, N_ - 8))
    bt = np.zeros((B, P_), np.int32)
    used = 0
    for r in range(B - 1):
        n = -(-int(base[r]) // PS_)
        bt[r, :n] = ids[used:used + n]
        bt[r, n:] = rng.integers(N_ - 8, N_, size=P_ - n)
        used += n
    q = jax.random.normal(kq, (B, 1, KV * G, dh), jnp.float32)
    return (q, *pools, t_kw, t_vw, t_ks, t_vs, jnp.int32(1),
            jnp.asarray(bt), jnp.asarray(base), jnp.asarray(base + fill))


def _poisoned(args):
    """The dummy page and every page only a stale block-table entry
    names, in every layer: extreme words, NaN scales."""
    bad = jnp.asarray([0] + list(range(N_ - 8, N_)))
    q, k_w, v_w, k_s, v_s, *rest = args
    top = jnp.iinfo(k_w.dtype).max
    return (q, k_w.at[:, bad].set(top), v_w.at[:, bad].set(-top - 1),
            k_s.at[:, bad].set(jnp.nan), v_s.at[:, bad].set(jnp.nan), *rest)


@pytest.mark.parametrize("steps,G,window,KV,kvq", [
    (1, 1, None, 2, KVQ),
    (4, 2, None, 2, KVQ),
    (1, 2, 40, 2, KVQ),
    (4, 1, 70, 2, KVQ),
    (1, 1, None, 4, KVQ8),      # 8-bit words, four heads a 32-bit row
    (4, 2, 70, 8, KVQ8),
])
def test_paged_decode_kernel_interpret_parity(steps, G, window, KV, kvq):
    """The paged kernel body (interpreter) == the gather-plus-contiguous
    oracle, and reads no page outside a row's live span: poisoning the
    dummy page and the stale block-table entries changes no bit."""
    _, vp = kv_cache_formats(kvq)
    args = _paged_inputs(steps, G, KV=KV, kvq=kvq)
    want = kref.vp_paged_decode_attention_ref(*args, vp, window=window)
    got = ops.vp_paged_decode_attention(*args, vp, window=window,
                                        pages_per_block=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    poisoned = ops.vp_paged_decode_attention(
        *_poisoned(args), vp, window=window, pages_per_block=4,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(got))


@pytest.mark.parametrize("backend,page,KV,dh,dtype,ok", [
    ("native", 16, 8, 128, jnp.int16, True),    # qwen3-0.6b's cell
    ("native", 16, 4, 128, jnp.int8, True),
    ("native", 16, 2, 128, jnp.int8, False),    # heads share 32-bit rows
    ("native", 16, 1, 128, jnp.int16, False),
    ("native", 16, 2, 64, jnp.int16, False),    # half-lane heads
    ("native", 16, 6, 128, jnp.int16, False),   # pads to 8 heads in HBM
    ("native", 48, 8, 128, jnp.int16, False),   # scales miss 128 lanes
    ("interpret", 16, 2, 64, jnp.int16, True),
    ("interpret", 16, 2, 128, jnp.int8, False),
    ("ref", 16, 2, 128, jnp.int8, True),        # gathers: any layout
])
def test_paged_decode_supported_layouts(backend, page, KV, dh, dtype, ok):
    """The runner reads pages in place only in layouts the backend's
    kernel tiles; the op refuses the others rather than drop heads."""
    with substrate.force_backend(backend):
        assert ops.paged_decode_supported(page, KV, dh, dtype) is ok
    if backend == "interpret" and not ok:
        pools = jnp.zeros((1, 3, page, KV, dh), dtype)
        scales = jnp.ones((1, 3, page, 1, 1), jnp.float32)
        tail = jnp.zeros((1, 1, KV, dh), dtype)
        tail_s = jnp.ones((1, 1, 1, 1), jnp.float32)
        with pytest.raises(ValueError, match="cannot tile"):
            ops.vp_paged_decode_attention(
                jnp.zeros((1, 1, KV, dh)), pools, pools, scales, scales,
                tail, tail, tail_s, tail_s, jnp.int32(0),
                jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32),
                jnp.ones((1,), jnp.int32), kv_cache_formats(KVQ8)[1],
                interpret=True)


@pytest.mark.parametrize("steps", [1, 4])
def test_paged_decode_ref_is_the_gathered_view(steps):
    """On the ref backend the paged op IS the contiguous op on the view
    the runner used to gather: bit for bit, so engine logits stay
    identical to the static driver's."""
    _, vp = kv_cache_formats(KVQ)
    (q, k_w, v_w, k_s, v_s, t_kw, t_vw, t_ks, t_vs, layer, bt, base,
     lens) = _paged_inputs(steps, 2, seed=3)

    def view(pool, tail):
        cache = pool[1][bt].reshape((bt.shape[0], CAP_) + pool.shape[3:])
        for r in range(bt.shape[0]):
            cache = cache.at[r, int(base[r]):int(base[r]) + steps].set(
                tail[r])
        return cache

    want = kref.vp_decode_attention_ref(
        q, view(k_w, t_kw), view(v_w, t_vw), view(k_s, t_ks),
        view(v_s, t_vs), lens, vp)
    got = kref.vp_paged_decode_attention_ref(
        q, k_w, v_w, k_s, v_s, t_kw, t_vw, t_ks, t_vs, layer, bt, base,
        lens, vp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
