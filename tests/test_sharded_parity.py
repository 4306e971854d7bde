"""Sharded-execution parity suite (PR 8).

The contract under test: every collective on the sharded packed-VP
datapath is a pure CONCATENATION (all-gather of output column blocks /
head shards / expert outputs; the ppermute ring writes disjoint column
blocks), so on the jnp ref backend the shard_map'd ops, the full-model
forwards, and the mesh-constructed serving engine match their
single-device oracles — integer outputs bit for bit, float outputs to
`F32_ULPS` ulps (see `assert_parity`) — across the quant x KV-layout
matrix, for all three weight-sharding modes, and for the
expert-parallel MoE branch.  Runs on the 8-host-device platform
`tests/conftest.py` pins.

Also here: the `shard_param_specs` placement rules (which leaves shard,
which error when they cannot), the autotune mesh-key migration shim,
and the JX-SHGATH lint rule (the `gather` mode's full-weight
re-materialization is flagged; `ring`/`column` stay clean).
"""
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, QuantConfig
from repro.kernels import autotune, substrate
from repro.kernels import ops as kops
from repro.launch import mesh as mesh_mod
from repro.models import (
    decode_step, init_cache, init_params, prefill, quantize_params,
)
from repro.models.layers import canonical_formats
from repro.parallel import shard_ops

REF_BACKEND = substrate.resolve_backend(None) == "ref"
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 host devices (conftest flag)")


# A sharded matmul contracts each (M, K) x (K, N/tp) column block on its
# own, while the oracle runs one (M, K) x (K, N) dot; XLA's CPU backend
# may order the K-reduction differently for the two shapes, which moves
# f32 results by a few ulps (at most 3 on JAX 0.9.0).  Float outputs are
# held to F32_ULPS ulps of the array's largest magnitude; integer outputs
# (packed words, lengths) are concatenations and must match exactly.
F32_ULPS = 4


def assert_parity(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    if not np.issubdtype(want.dtype, np.floating):
        assert np.array_equal(got, want)
        return
    scale = np.float32(np.abs(want).max()) if want.size else np.float32(0)
    np.testing.assert_allclose(
        got, want, rtol=0, atol=F32_ULPS * float(np.spacing(scale)))


def _mesh(data=1, model=8):
    return mesh_mod.elastic_mesh(1, data, model)


def _tiny_cfg(quant, family="dense", **kw):
    base = dict(name="tiny", family=family, n_layers=2, d_model=64,
                n_heads=8, n_kv_heads=4, d_ff=128, vocab=128,
                dtype="float32", quant=quant)
    if family == "moe":
        base.update(n_experts=8, experts_per_token=2)
    base.update(kw)
    return ModelConfig(**base)


def _quant(mode="vp", kv="packed", **kw):
    if kv != "float":
        kw.update(quantize_kv_cache=True, kv_layout=kv)
    if mode == "vp_block":
        kw.setdefault("block", 16)
    return QuantConfig(mode=mode, **kw)


# ---------------------------------------------------------------------------
# Op-level parity
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not REF_BACKEND, reason="bit parity is a ref contract")
@pytest.mark.parametrize("mode", shard_ops.MODES)
@pytest.mark.parametrize("tp", [2, 8])
def test_dequant_matmul_parity(mode, tp):
    fxp, vp = canonical_formats(QuantConfig(mode="vp"))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 128), jnp.float32)
    w_pk = kops.vp_quant(w, fxp, vp, packed=True)
    y_ref = np.asarray(kops.vp_dequant_matmul(x, w_pk, vp))
    fn = jax.jit(shard_map(
        partial(shard_ops.sharded_dequant_matmul, fmt=vp, mode=mode),
        mesh=_mesh(model=tp) if tp == 8 else _mesh(4, 2),
        in_specs=(P(), P(None, "model")), out_specs=P(), check_vma=False))
    assert_parity(fn(x, w_pk), y_ref)


def test_dequant_matmul_bad_mode():
    _, vp = canonical_formats(QuantConfig(mode="vp"))
    with pytest.raises(ValueError, match="mode"):
        shard_ops.sharded_dequant_matmul(
            jnp.zeros((2, 4)), jnp.zeros((4, 8), jnp.int16), vp,
            mode="scatter")


@pytest.mark.skipif(not REF_BACKEND, reason="bit parity is a ref contract")
@pytest.mark.parametrize("mode", ["seq", "heads"])
def test_decode_attention_parity(mode):
    fxp, vp = canonical_formats(QuantConfig(mode="vp"))
    B, S, H, KV, dh = 2, 32, 8, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, H, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, dh), jnp.float32)
    k_w = kops.vp_quant(k, fxp, vp, packed=True)
    v_w = kops.vp_quant(v, fxp, vp, packed=True)
    ones = jnp.ones((B, S, 1, 1), jnp.float32)
    lens = jnp.asarray([S, S // 2], jnp.int32)
    o_ref = np.asarray(
        kops.vp_decode_attention(q, k_w, v_w, ones, ones, lens, vp))
    if mode == "seq":
        in_specs = (P(), P(None, "model"), P(None, "model"),
                    P(None, "model"), P(None, "model"), P())
    else:
        in_specs = (P(None, None, "model"), P(None, None, "model"),
                    P(None, None, "model"), P(), P(), P())
    fn = jax.jit(shard_map(
        partial(shard_ops.sharded_decode_attention, fmt=vp, mode=mode),
        mesh=_mesh(model=8 if mode == "seq" else 4) if mode == "seq"
        else _mesh(2, 4),
        in_specs=in_specs, out_specs=P(), check_vma=False))
    assert np.array_equal(np.asarray(fn(q, k_w, v_w, ones, ones, lens)),
                          o_ref)


@pytest.mark.skipif(not REF_BACKEND, reason="bit parity is a ref contract")
def test_flash_prefill_parity():
    from repro.models.attention import flash_attention

    B, S, H, KV, dh = 2, 16, 8, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, dh), jnp.float32)
    o_ref = np.asarray(flash_attention(q, k, v))
    fn = jax.jit(shard_map(
        partial(shard_ops.sharded_flash_prefill),
        mesh=_mesh(2, 4),
        in_specs=(P(None, None, "model"), P(None, None, "model"),
                  P(None, None, "model")),
        out_specs=P(), check_vma=False))
    assert np.array_equal(np.asarray(fn(q, k, v)), o_ref)


# ---------------------------------------------------------------------------
# Backward collectives (PR 9): dx psum/ring, local dw, DP grad codec
# ---------------------------------------------------------------------------

def _bwd_operands():
    fxp, vp = canonical_formats(QuantConfig(mode="vp"))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 128), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(2), (16, 128), jnp.float32)
    return (vp, fxp, kops.vp_quant(x, fxp, vp, packed=True),
            kops.vp_quant(w, fxp, vp, packed=True), g)


@pytest.mark.skipif(not REF_BACKEND, reason="oracle parity is a ref check")
@pytest.mark.parametrize("mode", ["psum", "ring"])
@pytest.mark.parametrize("tp", [2, 8])
def test_sharded_matmul_dx_parity(mode, tp):
    """dx across psum/ring modes vs the single-device backward kernel.

    Unlike the forward modes (concatenation-exact), dx REDUCES partial
    products across shards, so the contract is allclose, not bit-equal:
    psum/ring add the same tp partials in different orders."""
    vp, _, _, w_pk, g = _bwd_operands()
    dx_ref = np.asarray(kops.vp_matmul_dx(g, w_pk, vp))
    fn = jax.jit(shard_map(
        partial(shard_ops.sharded_matmul_dx, fmt=vp, mode=mode),
        mesh=_mesh(model=tp) if tp == 8 else _mesh(4, 2),
        in_specs=(P(), P(None, "model")), out_specs=P(),
        check_vma=False))
    np.testing.assert_allclose(np.asarray(fn(g, w_pk)), dx_ref,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.skipif(not REF_BACKEND, reason="oracle parity is a ref check")
def test_sharded_matmul_dx_ring_scatter_output():
    """gather=False leaves dx row-sharded; the reassembled shards must
    equal the gathered result."""
    vp, _, _, w_pk, g = _bwd_operands()
    dx_ref = np.asarray(kops.vp_matmul_dx(g, w_pk, vp))
    fn = jax.jit(shard_map(
        partial(shard_ops.sharded_matmul_dx, fmt=vp, mode="ring",
                gather=False),
        mesh=_mesh(model=8), in_specs=(P(), P(None, "model")),
        out_specs=P("model"), check_vma=False))
    np.testing.assert_allclose(np.asarray(fn(g, w_pk)), dx_ref,
                               rtol=1e-5, atol=1e-5)


def test_sharded_matmul_dx_bad_mode():
    _, vp = canonical_formats(QuantConfig(mode="vp"))
    with pytest.raises(ValueError, match="mode"):
        shard_ops.sharded_matmul_dx(
            jnp.zeros((2, 8)), jnp.zeros((4, 8), jnp.int16), vp,
            mode="gather")


@pytest.mark.skipif(not REF_BACKEND, reason="bit parity is a ref contract")
def test_sharded_matmul_dw_local_bit_exact():
    """The weight-grad shard is computed purely locally (no collective),
    so it is BIT-identical to the matching slice of the full dw."""
    vp, _, x_pk, _, g = _bwd_operands()
    dw_ref = np.asarray(kops.vp_matmul_dw(x_pk, g, vp))
    fn = jax.jit(shard_map(
        partial(shard_ops.sharded_matmul_dw, fmt=vp),
        mesh=_mesh(model=8), in_specs=(P(), P()),
        out_specs=P(None, "model"), check_vma=False))
    assert np.array_equal(np.asarray(fn(x_pk, g)), dw_ref)


@pytest.mark.skipif(not REF_BACKEND, reason="oracle parity is a ref check")
@pytest.mark.parametrize("codec", ["int8", "vp"])
def test_dp_compress_reduce_oracle(codec):
    """Compressed DP reduction == per-rank local compress, then mean —
    with per-rank residuals carried in the returned state."""
    from repro.train.compression import (
        CompressionConfig, compress_decompress, init_compressor_state,
    )

    dp = 8
    cfg = CompressionConfig(codec=codec)
    grads = {"w": jax.random.normal(jax.random.PRNGKey(3),
                                    (dp, 16, 16), jnp.float32)}
    state = init_compressor_state(grads)
    fn = jax.jit(shard_map(
        partial(shard_ops.dp_compress_reduce, axis="data", config=cfg),
        mesh=_mesh(8, 1),
        in_specs=({"w": P("data")}, {"w": P("data")}),
        out_specs=({"w": P()}, {"w": P("data")}), check_vma=False))
    red, new_state = fn(grads, state)
    deqs, errs = [], []
    for i in range(dp):
        d, e = compress_decompress({"w": grads["w"][i:i + 1]},
                                   {"w": state["w"][i:i + 1]}, cfg)
        deqs.append(np.asarray(d["w"]))
        errs.append(np.asarray(e["w"]))
    oracle = np.mean(np.concatenate(deqs, 0), axis=0)
    np.testing.assert_allclose(np.asarray(red["w"][0]), oracle,
                               rtol=1e-6, atol=1e-7)
    # jit-vs-eager f32 rounding (~1e-7) on the residual subtraction
    np.testing.assert_allclose(np.asarray(new_state["w"]),
                               np.concatenate(errs, 0),
                               rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# Full-model parity: quant x KV-layout matrix, dense + MoE (EP)
# ---------------------------------------------------------------------------

MATRIX = [("vp", "packed"), ("vp", "planes"), ("fxp", "packed"),
          ("vp_block", "packed"), ("vp", "float")]


def _model_oracle_and_sharded(cfg, mesh, B=2, S=16, cap=32):
    params = init_params(jax.random.PRNGKey(0), cfg)
    if cfg.quant.mode != "none":
        params = quantize_params(params, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    caches = init_cache(cfg, B, cap)
    logits1, caches1 = jax.jit(
        lambda p, t, c: prefill(p, t, c, cfg))(params, tokens, caches)
    tok = jnp.argmax(logits1, -1).astype(jnp.int32)[:, None]
    dlogits1, caches1 = jax.jit(
        lambda p, t, c: decode_step(p, t, c, cfg))(params, tok, caches1)

    placed = shard_ops.place_params(params, cfg, mesh)
    prefill_fn, decode_fn = shard_ops.sharded_forward_fns(
        params, cfg, mesh)
    logits2, caches2 = jax.jit(prefill_fn)(placed, tokens, caches)
    dlogits2, caches2 = jax.jit(decode_fn)(placed, tok, caches2)
    return (logits1, dlogits1, caches1), (logits2, dlogits2, caches2)


@pytest.mark.skipif(not REF_BACKEND, reason="bit parity is a ref contract")
@pytest.mark.parametrize("mode,kv", MATRIX)
def test_model_parity_dense(mode, kv):
    cfg = _tiny_cfg(_quant(mode, kv))
    (l1, d1, c1), (l2, d2, c2) = _model_oracle_and_sharded(cfg, _mesh())
    assert_parity(l2, l1)
    assert_parity(d2, d1)
    for a, b in zip(jax.tree_util.tree_leaves(c1),
                    jax.tree_util.tree_leaves(c2)):
        assert_parity(b, a)


@pytest.mark.skipif(not REF_BACKEND, reason="bit parity is a ref contract")
@pytest.mark.parametrize("mode", ["vp", "none"])
def test_model_parity_moe_expert_parallel(mode):
    cfg = _tiny_cfg(_quant(mode, "packed" if mode == "vp" else "float"),
                    family="moe")
    (l1, d1, _), (l2, d2, _) = _model_oracle_and_sharded(cfg, _mesh())
    assert_parity(l2, l1)
    assert_parity(d2, d1)


# ---------------------------------------------------------------------------
# Serving engine under a mesh (TP and DP x TP)
# ---------------------------------------------------------------------------

REQS = [([1, 2, 3, 4, 5], 4, 0.0), (list(range(7)), 5, 0.0),
        ([9, 8, 7], 3, 0.05)]


def _engine_tokens(cfg, params, mesh):
    from repro.serving import ServingEngine, VirtualClock

    eng = ServingEngine(params, cfg, max_slots=2, capacity=24, page_size=8,
                        clock=VirtualClock(), mesh=mesh)
    for prompt, gen, t in REQS:
        eng.submit(prompt, gen, t)
    return {r["rid"]: r["tokens"] for r in eng.run()}


@pytest.mark.skipif(not REF_BACKEND, reason="bit parity is a ref contract")
@pytest.mark.parametrize("data,model", [(1, 8), (2, 4)])
def test_engine_mesh_parity(data, model):
    cfg = _tiny_cfg(_quant("vp", "packed"), n_heads=4, n_kv_heads=2)
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg), cfg)
    want = _engine_tokens(cfg, params, None)
    got = _engine_tokens(cfg, params, _mesh(data, model))
    assert got == want


# ---------------------------------------------------------------------------
# Placement rules + mesh factory
# ---------------------------------------------------------------------------

def test_shard_specs_divisibility_error():
    cfg = _tiny_cfg(_quant("vp", "packed"), d_ff=100)  # 100 % 8 != 0
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg), cfg)
    with pytest.raises(shard_ops.ShardSpecError, match="divisible"):
        shard_ops.shard_param_specs(params, cfg, tp=8)


def test_shard_specs_replicate_floats():
    cfg = _tiny_cfg(QuantConfig(mode="none"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    specs = shard_ops.shard_param_specs(params, cfg, tp=8)
    assert all(s == P() for s in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))


def test_mesh_validation_errors():
    with pytest.raises(ValueError, match="devices"):
        mesh_mod.elastic_mesh(1, 3, 5)
    with pytest.raises(ValueError, match=">= 1"):
        mesh_mod.elastic_mesh(0, 1, 8)
    with pytest.raises(ValueError, match="exposes"):
        mesh_mod.best_effort_mesh(1024)
    m = mesh_mod.best_effort_mesh(8)
    assert dict(m.shape) == {"data": 1, "model": 8}
    assert dict(mesh_mod.best_effort_mesh(4, prefer="data").shape) == \
        {"data": 4, "model": 1}


# ---------------------------------------------------------------------------
# Autotune mesh keys + migration shim
# ---------------------------------------------------------------------------

def test_autotune_mesh_key_scoped():
    key0 = autotune.make_key("vp_dequant_matmul", (8, 64, 128), (), "ref")
    assert key0.endswith("|mesh=1")
    with autotune.mesh_scope("model8.N"):
        key8 = autotune.make_key("vp_dequant_matmul", (8, 64, 128), (),
                                 "ref")
    assert key8.endswith("|mesh=model8.N") and key8 != key0


def test_autotune_cache_migration(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    legacy = {"vp_matmul|64x64x64|VP(4,[0,2])|ref": [64, 64, 64]}
    path.write_text(json.dumps(legacy))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    # the shim rewrites the legacy 4-part key to the canonical |mesh=1 form
    entry = autotune.get_cached(
        "vp_matmul|64x64x64|VP(4,[0,2])|ref|mesh=1")
    assert entry == (64, 64, 64)


# ---------------------------------------------------------------------------
# JX-SHGATH lint
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not REF_BACKEND,
                    reason="lint traces the ref dequant graph")
def test_lint_flags_gather_not_ring():
    from repro.analysis import jaxpr_lint

    fxp, vp = canonical_formats(QuantConfig(mode="vp"))
    x = jnp.zeros((8, 256), jnp.float32)
    w_pk = kops.vp_quant(jnp.zeros((256, 512), jnp.float32), fxp, vp,
                         packed=True)

    def traced(mode):
        fn = shard_map(
            partial(shard_ops.sharded_dequant_matmul, fmt=vp, mode=mode),
            mesh=_mesh(), in_specs=(P(), P(None, "model")),
            out_specs=P(), check_vma=False)
        return jax.make_jaxpr(fn)(x, w_pk)

    flagged = jaxpr_lint.lint_sharded_traced(traced("gather"), where="t")
    assert len(flagged) == 1 and flagged[0]["rule"] == "JX-SHGATH"
    assert jaxpr_lint.lint_sharded_traced(traced("ring"), where="t") == []
    assert jaxpr_lint.lint_sharded_traced(traced("column"), where="t") == []


def test_check_sharded_serving_clean():
    from repro.analysis import rules

    assert [f for f in rules.check_sharded()
            if f.rule == "JX-SHGATH"] == []
