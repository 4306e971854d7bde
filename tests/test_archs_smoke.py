"""Per-architecture smoke tests: REDUCED same-family configs, one forward/
train step on CPU, asserting output shapes and no NaNs (the FULL configs
are exercised only via the dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import QuantConfig
from repro.models import (
    init_params, loss_fn, init_cache, prefill, decode_step,
)

B, S = 2, 16


def _batch(cfg, key):
    ks = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab),
    }
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            ks[2], (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            ks[2], (B, cfg.n_patches, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", registry.ARCH_NAMES)
def test_smoke_train_step(arch):
    cfg = registry.get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    batch = _batch(cfg, key)
    (loss, metrics), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, batch, cfg, True)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), (arch, float(loss))
    gn = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree_util.tree_leaves(grads))
    assert bool(jnp.isfinite(gn)), arch


@pytest.mark.parametrize("arch", registry.ARCH_NAMES)
def test_smoke_prefill_decode(arch):
    cfg = registry.get_smoke_config(arch)
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    toks = jax.random.randint(key, (B, 8), 0, cfg.vocab)
    caches = init_cache(cfg, B, 24)
    extra = None
    cross_kv = None
    if cfg.family == "vlm":
        extra = jax.random.normal(key, (B, cfg.n_patches, cfg.d_model),
                                  jnp.float32)
    if cfg.family == "encdec":
        from repro.models.model import _encoder_forward, _cross_kv
        frames = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
        enc = _encoder_forward(params, frames, cfg)
        cross_kv = _cross_kv(params, enc, cfg)
        extra = cross_kv
    logits, caches = prefill(params, toks, caches, cfg, patches=extra)
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), arch
    nxt = jnp.argmax(logits, -1)[:, None]
    if cfg.family == "encdec":
        logits2, _ = decode_step(params, nxt, caches, cfg, cross_kv=cross_kv)
    else:
        logits2, _ = decode_step(params, nxt, caches, cfg)
    assert logits2.shape == (B, cfg.vocab)
    assert bool(jnp.isfinite(logits2).all()), arch


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "rwkv6-3b", "zamba2-7b"])
def test_smoke_vp_quantized_serving(arch):
    """VP-quantized weights (paper technique) through each family's decode."""
    from repro.models import quantize_params

    cfg = registry.get_smoke_config(arch, quant=QuantConfig(mode="vp"))
    key = jax.random.PRNGKey(2)
    params = init_params(key, cfg)
    qparams = quantize_params(params, cfg)
    toks = jax.random.randint(key, (B, 8), 0, cfg.vocab)
    caches = init_cache(cfg, B, 16)
    logits, _ = prefill(qparams, toks, caches, cfg)
    assert bool(jnp.isfinite(logits).all())
    # quantized path stays close to the float path
    caches2 = init_cache(cfg, B, 16)
    cfg_f = registry.get_smoke_config(arch)
    logits_f, _ = prefill(params, toks, caches2, cfg_f)
    rel = float(jnp.linalg.norm(logits - logits_f)
                / (jnp.linalg.norm(logits_f) + 1e-9))
    assert rel < 0.25, (arch, rel)


def test_full_configs_match_assignment():
    """The FULL configs carry the exact assigned hyperparameters."""
    t = {
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936),
        "stablelm-12b": (40, 5120, 32, 8, 13824, 100352),
        "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
    }
    for arch, (L, d, H, KV, ff, V) in t.items():
        cfg = registry.get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.d_ff, cfg.vocab) == (L, d, H, KV, ff, V), arch
    # family-specific extras
    assert registry.get_config("zamba2-7b").ssm_state == 64
    assert registry.get_config("qwen3-moe-30b-a3b").n_experts == 128
    assert registry.get_config("qwen3-moe-30b-a3b").experts_per_token == 8
    assert registry.get_config("mixtral-8x22b").n_experts == 8
    assert registry.get_config("mixtral-8x22b").experts_per_token == 2
    assert registry.get_config("mixtral-8x22b").sliding_window == 4096
    assert registry.get_config("gemma3-27b").local_global_period == 6
    assert registry.get_config("qwen3-0.6b").qk_norm
    assert registry.get_config("qwen3-0.6b").head_dim == 128
    assert registry.get_config("qwen2-0.5b").qkv_bias


def test_cell_enumeration():
    cells = registry.cells()
    assert len(cells) == 33  # 10*4 - 7 documented long_500k skips
    skips = [c for c in registry.cells(include_skipped=True)
             if c[2].startswith("SKIP")]
    assert len(skips) == 7
