"""Cross-arch golden-parity suite for kernel-backed VP serving (PR 4).

The model zoo's serving matmuls route packed VP weight words through the
Pallas `vp_dequant_matmul` substrate (`models.layers.qdot`).  This suite
pins that path against the legacy jnp-dequant two-plane path — the
"golden" baseline that shipped in PRs 1–3 — for EVERY architecture's
smoke config and EVERY quant mode, at both serving shapes:

  decode   M = B        (skinny single-token step)
  prefill  M = S * B    (full-prompt batch)

For mode "vp" the parity is BIT-IDENTICAL on the jnp ref backend (the CI
environment): power-of-two scales are exact in any float dtype and both
layouts run the same contraction.  On a kernel backend (TPU) the Pallas
kernel accumulates f32 per k-tile — a different summation order than one
flat dot — so the suite scopes the exact asserts to the ref backend and
pins a 1e-6-class tolerance otherwise.  Also here: the all-zero-weight
`_pow2_scale` regression, the packed-checkpoint round-trip, and the
skinny-decode autotune profile.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import QuantConfig
from repro.core.packing import dequant_words, pack_vp, unpack_vp
from repro.kernels import autotune, ops, substrate
from repro.models import (
    init_params, init_cache, prefill, decode_step, quantize_params,
)
from repro.models.layers import (
    canonical_formats, quantize_weight, qdot, _pow2_scale,
)

B, S = 2, 16
MODES = ("none", "fxp", "vp", "vp_block")

# Exact bit-parity is the contract of the shared jnp ref path; kernel
# backends reassociate the k-reduction (per-tile f32 accumulators).
REF_BACKEND = substrate.resolve_backend(None) == "ref"


def assert_parity(got, want, err_msg=""):
    if REF_BACKEND:
        np.testing.assert_array_equal(got, want, err_msg=err_msg)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                   err_msg=err_msg)


def _quant_config(mode: str, d_in: int) -> QuantConfig:
    if mode != "vp_block":
        return QuantConfig(mode=mode)
    # Pick the largest block dividing the contraction dim so the i_blk
    # (int8-MXU) path is exercised where the arch's width allows it; the
    # per-element fallback covers the rest.
    for blk in (256, 128, 64, 32, 16):
        if d_in % blk == 0:
            return QuantConfig(mode="vp_block", block=blk)
    return QuantConfig(mode="vp_block")


def _weight_panel(cfg):
    """A representative (d_model, d_ff) MLP weight panel for the arch."""
    return cfg.d_model, cfg.d_ff


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("arch", registry.ARCH_NAMES)
def test_qdot_golden_parity(arch, mode):
    """Kernel-backed qdot == legacy jnp-dequant qdot, per arch x mode,
    at decode (M=B, rank 2) and prefill (M=S*B, rank 3) shapes."""
    cfg = registry.get_smoke_config(arch)
    d_in, d_out = _weight_panel(cfg)
    q = _quant_config(mode, d_in)
    key = jax.random.PRNGKey(17)
    kw, kx = jax.random.split(key)
    w = jax.random.normal(kw, (d_in, d_out), jnp.float32) * 0.05
    x_prefill = jax.random.normal(kx, (B, S, d_in), jnp.float32)
    x_decode = x_prefill[:, 0]

    wq_serve = quantize_weight(w, q)                      # packed default
    wq_gold = quantize_weight(w, q, layout="planes")      # jnp baseline
    for x in (x_decode, x_prefill):
        got = qdot(x, wq_serve, q)
        want = qdot(x, wq_gold, q)
        assert got.shape == want.shape and got.dtype == want.dtype
        assert bool(jnp.isfinite(got).all()), (arch, mode)
        if mode == "vp":
            # packed words feed the kernel op; planes feed jnp dequant —
            # bit-for-bit on the ref backend, 1e-6 under k-tiled kernels.
            assert_parity(np.asarray(got), np.asarray(want),
                          err_msg=f"{arch} {mode}")
        else:
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
        # pinned tolerance against the float matmul (quantization error
        # only — a wrong kernel path shows up as a gross violation)
        ref = jnp.dot(x, w)
        rel = float(jnp.linalg.norm(got - ref)
                    / (jnp.linalg.norm(ref) + 1e-9))
        assert rel < (1e-6 if mode == "none" else 0.2), (arch, mode, rel)


@pytest.mark.parametrize("mode", ("vp", "vp_block"))
def test_qdot_packed_words_reach_the_kernel_op(monkeypatch, mode):
    """The serving layout actually calls the kernel op (not jnp dequant)."""
    calls = []
    orig = ops.vp_dequant_matmul

    def spy(*a, **k):
        calls.append(a[1].dtype)
        return orig(*a, **k)

    from repro.models import layers as L
    monkeypatch.setattr(L.kops, "vp_dequant_matmul", spy)
    q = QuantConfig(mode=mode)           # d_in below any block: vp_block
    w = jax.random.normal(jax.random.PRNGKey(0), (24, 8), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 24), jnp.float32)
    wq = quantize_weight(w, q)
    assert "w_packed" in wq
    qdot(x, wq, q)
    assert len(calls) == 1 and calls[0] == wq["w_packed"].dtype


@pytest.mark.parametrize("arch", registry.ARCH_NAMES)
def test_model_logits_parity_vp(arch):
    """Full-model golden parity: packed-kernel serving vs planes baseline,
    prefill AND one decode step, for every arch (bit-identical on the
    ref backend; 1e-6 under k-tiled kernel accumulation)."""
    cfg = registry.get_smoke_config(arch, quant=QuantConfig(mode="vp"))
    key = jax.random.PRNGKey(3)
    params = init_params(key, cfg)
    qp_k = quantize_params(params, cfg)                   # packed kernel
    qp_g = quantize_params(params, cfg, layout="planes")  # jnp golden
    toks = jax.random.randint(key, (B, 8), 0, cfg.vocab)

    extra = None
    cross_kv = None
    if cfg.family == "vlm":
        extra = jax.random.normal(
            key, (B, cfg.n_patches, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        from repro.models.model import _encoder_forward, _cross_kv
        frames = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
        cross_kv_k = _cross_kv(qp_k, _encoder_forward(qp_k, frames, cfg),
                               cfg)
        cross_kv_g = _cross_kv(qp_g, _encoder_forward(qp_g, frames, cfg),
                               cfg)
        assert_parity(np.asarray(cross_kv_k[0]), np.asarray(cross_kv_g[0]))
    outs = {}
    for name, qp in (("kernel", qp_k), ("golden", qp_g)):
        if cfg.family == "encdec":
            extra = cross_kv_k if name == "kernel" else cross_kv_g
            cross_kv = extra
        lo, caches = prefill(qp, toks, init_cache(cfg, B, 16), cfg,
                             patches=extra)
        nxt = jnp.argmax(lo, -1)[:, None]
        if cfg.family == "encdec":
            lo2, _ = decode_step(qp, nxt, caches, cfg, cross_kv=cross_kv)
        else:
            lo2, _ = decode_step(qp, nxt, caches, cfg)
        outs[name] = (np.asarray(lo), np.asarray(lo2))
    assert np.isfinite(outs["kernel"][0]).all(), arch
    assert_parity(outs["kernel"][0], outs["golden"][0],
                  err_msg=f"{arch} prefill")
    assert_parity(outs["kernel"][1], outs["golden"][1],
                  err_msg=f"{arch} decode")


@pytest.mark.parametrize("mkn", [(4, 64, 64), (1, 13, 50), (33, 96, 24)])
def test_vp_dequant_matmul_kernel_interpret_parity(mkn):
    """The Pallas kernel body (interpreter) == the ref oracle == plain
    dequant-then-dot, including ragged shapes through the op's padding
    (packed-word 0 decodes to real 0, so padding is exact).

    The kernel accumulates k-tiles into an f32 scratch while the oracle
    runs one dot, so the same K products are summed in another order:
    each output may move by a few ulps of the magnitude its partial sums
    reach, sum_k |x_k w_k| (3 at most on JAX 0.9.0).  Held to 4."""
    M, K, N = mkn
    q = QuantConfig(mode="vp")
    _, vp = canonical_formats(q)
    key = jax.random.PRNGKey(11)
    w = jax.random.normal(key, (K, N), jnp.float32) * 0.05
    wq = quantize_weight(w, q)
    x = jax.random.normal(key, (M, K), jnp.float32)
    ref_out = ops.vp_dequant_matmul(x, wq["w_packed"], vp)
    kern_out = ops.vp_dequant_matmul(x, wq["w_packed"], vp, interpret=True)
    assert kern_out.shape == (M, N)
    w_real = dequant_words(wq["w_packed"], vp, jnp.float32)
    magnitude = np.abs(np.asarray(x)) @ np.abs(np.asarray(w_real))
    ulp = np.spacing(magnitude.astype(np.float32))
    diff = np.abs(np.asarray(kern_out) - np.asarray(ref_out))
    assert (diff <= 4 * ulp).all(), float((diff / ulp).max())


# ---------------------------------------------------------------------------
# Satellite regressions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ("fxp", "vp", "vp_block"))
def test_quantize_weight_all_zero(mode):
    """All-zero weights: the pow2 clamp floor must not leak a spurious
    ~2^-100 scale; the round trip is exactly zero."""
    q = QuantConfig(mode=mode)
    z = jnp.zeros((32, 16), jnp.float32)
    assert float(_pow2_scale(z)) == 1.0
    wq = quantize_weight(z, q)
    scale = float(np.asarray(wq["scale"]))
    # fxp folds 1/127 into the stored scale; vp keeps the raw pow2.
    assert scale == pytest.approx(1.0 / 127.0 if mode == "fxp" else 1.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)
    out = qdot(x, wq, q)
    assert (np.asarray(out) == 0.0).all()
    # and the scale survives a nonzero neighbour unchanged (no regression
    # of the normal path)
    w = jnp.ones((32, 16), jnp.float32) * 0.3
    assert float(_pow2_scale(w)) == 0.5


def test_pow2_scale_all_zero_activations():
    """vp_block quantizes ACTIVATIONS dynamically with the same helper:
    an all-zero activation block must not be divided by a denormal."""
    q = QuantConfig(mode="vp_block", block=16)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)
    wq = quantize_weight(w, q)
    out = qdot(jnp.zeros((4, 32), jnp.float32), wq, q)
    assert (np.asarray(out) == 0.0).all()


def test_ckpt_roundtrip_packed_serving(tmp_path):
    """quantize_params -> CheckpointManager save/restore -> bit-identical
    packed words, scales, and logits."""
    from repro.train.ckpt import CheckpointManager

    cfg = registry.get_smoke_config(
        "qwen3-0.6b", quant=QuantConfig(mode="vp"))
    key = jax.random.PRNGKey(5)
    qparams = quantize_params(init_params(key, cfg), cfg)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    mgr.save(7, qparams, extra={"layout": "packed"})
    assert mgr.latest_step() == 7
    restored, manifest = mgr.restore(7, qparams)
    assert manifest["extra"]["layout"] == "packed"
    for a, b in zip(jax.tree_util.tree_leaves(qparams),
                    jax.tree_util.tree_leaves(restored)):
        assert a.dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    toks = jax.random.randint(key, (B, 8), 0, cfg.vocab)
    lo_a, _ = prefill(qparams, toks, init_cache(cfg, B, 16), cfg)
    lo_b, _ = prefill(restored, toks, init_cache(cfg, B, 16), cfg)
    np.testing.assert_array_equal(np.asarray(lo_a), np.asarray(lo_b))


def test_packed_weight_words_roundtrip_format():
    """The serving dict's packed words ARE `core.packing` words: unpack
    recovers the planes layout exactly (storage contract, not just value
    parity)."""
    q = QuantConfig(mode="vp")
    _, vp = canonical_formats(q)
    w = jax.random.normal(jax.random.PRNGKey(9), (40, 24), jnp.float32)
    wq = quantize_weight(w, q)
    wl = quantize_weight(w, q, layout="planes")
    m, i = unpack_vp(wq["w_packed"], vp)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(wl["m"]))
    np.testing.assert_array_equal(
        np.asarray(pack_vp(m, i, vp)), np.asarray(wq["w_packed"]))
    np.testing.assert_array_equal(
        np.asarray(wq["scale"]), np.asarray(wl["scale"]))


def test_decode_autotune_profile(tmp_path, monkeypatch):
    """The M=1..B skinny-decode profile persists one tuned entry per
    batch size, and `resolve_blocks` then launches the measured tiling."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    autotune._caches.clear()
    q = QuantConfig(mode="vp")
    _, vp = canonical_formats(q)
    K, N = 48, 24
    w = quantize_weight(
        jax.random.normal(jax.random.PRNGKey(2), (K, N), jnp.float32),
        q)["w_packed"]
    x = jax.random.normal(jax.random.PRNGKey(3), (8, K), jnp.float32)

    def bench(M, blocks):
        jax.block_until_ready(
            ops.vp_dequant_matmul(x[:M], w, vp, blocks=blocks))

    profile = autotune.tune_serving_decode(
        "vp_dequant_matmul", K, N, (vp,), "ref", bench,
        batch_sizes=(1, 4, 8), repeats=1)
    assert set(profile) == {1, 4, 8}
    for M, blocks in profile.items():
        key = autotune.make_key(
            "vp_dequant_matmul", (M, K, N), (vp,), "ref")
        assert autotune.get_cached(key) == blocks
        assert autotune.resolve_blocks(
            "vp_dequant_matmul", (M, K, N), (vp,), "ref") == blocks
        # skinny profile never tiles beyond the padded operand
        assert blocks[0] <= autotune._pow2_at_least(M)


def test_block_vp_matmul_consults_autotune_cache(tmp_path, monkeypatch):
    """`block_vp_matmul(blocks=None)` resolves through the autotune cache
    with the k-tile pinned to the index block size (regression: the qdot
    vp_block path used to hardcode (256, block, 256), bypassing it)."""
    from repro.core import block_vp_quantize

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    autotune._caches.clear()
    q = QuantConfig(mode="vp_block", block=16)
    fxp, vp = canonical_formats(q)
    w = jax.random.normal(jax.random.PRNGKey(4), (32, 16), jnp.float32)
    wq = quantize_weight(w, q)
    assert "i_blk" in wq
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 32), jnp.float32)
    sa = _pow2_scale(x)
    a_m, a_i = block_vp_quantize(x / sa, fxp, vp, block=16, axis=-1)
    args = (a_m, a_i, wq["m"], wq["i_blk"], vp, vp)
    base = np.asarray(ops.block_vp_matmul(*args, bk=16))          # ref
    # Plant a tuned entry under the bk-pinned kernel key; the interpret
    # launch must resolve it — and even a cached entry with a WRONG
    # k-tile must come back pinned to bk, numerics unchanged.
    key = autotune.make_key(
        "block_vp_matmul_bk16", (4, 32, 16), (vp, vp), "interpret")
    autotune.record(key, (2, 999, 8))
    got = np.asarray(ops.block_vp_matmul(*args, bk=16, interpret=True))
    np.testing.assert_allclose(got, base, rtol=1e-6, atol=1e-6)
    # and qdot's blocks=None vp_block path == the op composition it
    # wraps (dynamic activation pow2 scale, block matmul, rescale)
    want = base * np.asarray(sa * wq["scale"])
    np.testing.assert_allclose(
        np.asarray(qdot(x, wq, q)), want, rtol=1e-6, atol=1e-6)
