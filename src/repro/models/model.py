"""Model assembly for every assigned architecture.

A model is (init_params, loss_fn / prefill / decode_step) driven purely by
ModelConfig.  Layers are SCANNED in homogeneous groups so the HLO contains
one body per distinct layer pattern regardless of depth:

  dense GQA        : one group of n_layers x [causal]
  gemma3 5:1       : groups of [5 x local, 1 x global] + tail locals
  mixtral SWA+MoE  : n_layers x [swa + moe]
  qwen3-moe        : n_layers x [causal + moe]
  rwkv6            : n_layers x [time_mix + channel_mix]
  zamba2 hybrid    : groups of [6 x mamba2] with ONE SHARED attention block
                     applied between groups (weight sharing — the shared
                     params live outside the scan)
  whisper enc-dec  : encoder stack (full attn) + decoder stack (causal
                     self-attn + cross-attn)
  internvl2 (vlm)  : patch-embedding stub prepended to token embeddings,
                     then the dense LM stack

Caches for decode are stacked per group; `quantize_params` converts float
weights to the configured serving representation (VP planes etc.).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import qdot, rms_norm, layer_norm, embed_lookup, quantize_weight
from .attention import attn_block
from .mlp import swiglu, gelu_mlp
from .moe import moe_block
from .mamba2 import mamba2_block, mamba2_dims, D_CONV
from .rwkv6 import rwkv6_time_mix, rwkv6_channel_mix, HEAD_DIM as RWKV_HEAD


def model_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Layer grouping (static plan of scanned groups)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerGroup:
    repeats: int
    patterns: Tuple[str, ...]   # per sub-layer: causal|local|global|swa|
                                # moe|moe_swa|mamba|rwkv|shared_attn


def layer_groups(cfg: ModelConfig) -> List[LayerGroup]:
    if cfg.family == "hybrid":
        per = cfg.shared_attn_period
        n_full, tail = divmod(cfg.n_layers, per)
        groups = []
        if n_full:
            groups.append(LayerGroup(n_full, ("mamba",) * per + ("shared_attn",)))
        if tail:
            groups.append(LayerGroup(1, ("mamba",) * tail))
        return groups
    if cfg.family == "ssm" and cfg.rwkv:
        return [LayerGroup(cfg.n_layers, ("rwkv",))]
    if cfg.local_global_period:
        per = cfg.local_global_period
        n_full, tail = divmod(cfg.n_layers, per)
        groups = []
        if n_full:
            groups.append(
                LayerGroup(n_full, ("local",) * (per - 1) + ("global",)))
        if tail:
            groups.append(LayerGroup(1, ("local",) * tail))
        return groups
    if cfg.family == "moe":
        pat = "moe_swa" if cfg.sliding_window else "moe"
        return [LayerGroup(cfg.n_layers, (pat,))]
    pat = "swa" if cfg.sliding_window else "causal"
    return [LayerGroup(cfg.n_layers, (pat,))]


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------

def _dense_init(key, shape, dtype, scale=0.02):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _attn_params(key, cfg: ModelConfig, dtype, cross: bool = False):
    H, KV, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, H * dh), dtype),
        "wk": _dense_init(ks[1], (d, KV * dh), dtype),
        "wv": _dense_init(ks[2], (d, KV * dh), dtype),
        "wo": _dense_init(ks[3], (H * dh, d), dtype,
                          scale=0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * dh,), dtype)
        p["bk"] = jnp.zeros((KV * dh,), dtype)
        p["bv"] = jnp.zeros((KV * dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), jnp.float32)
        p["k_norm"] = jnp.zeros((dh,), jnp.float32)
    return p


def _mlp_params(key, cfg: ModelConfig, dtype, gelu: bool = False):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if gelu:
        return {
            "w_in": _dense_init(ks[0], (d, ff), dtype),
            "b_in": jnp.zeros((ff,), dtype),
            "w_out": _dense_init(ks[1], (ff, d), dtype),
            "b_out": jnp.zeros((d,), dtype),
        }
    return {
        "w_gate": _dense_init(ks[0], (d, ff), dtype),
        "w_up": _dense_init(ks[1], (d, ff), dtype),
        "w_down": _dense_init(ks[2], (ff, d), dtype,
                              scale=0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
    }


def _moe_params(key, cfg: ModelConfig, dtype):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "w_router": _dense_init(ks[0], (d, E), jnp.float32),
        "w_gate": _dense_init(ks[1], (E, d, ff), dtype),
        "w_up": _dense_init(ks[2], (E, d, ff), dtype),
        "w_down": _dense_init(ks[3], (E, ff, d), dtype,
                              scale=0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
    }


def _mamba_params(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    di, n, h, p_, conv_dim, proj_dim = mamba2_dims(cfg)
    ks = jax.random.split(key, 6)
    return {
        "w_z": _dense_init(ks[0], (d, di), dtype),
        "w_x": _dense_init(ks[1], (d, di), dtype),
        "w_bc": _dense_init(ks[2], (d, 2 * n), dtype),
        "w_dt": _dense_init(ks[3], (d, h), dtype),
        "conv_w": _dense_init(ks[4], (D_CONV, conv_dim), jnp.float32, 0.2),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "a_log": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "out_norm": jnp.zeros((di,), jnp.float32),
        "w_out": _dense_init(ks[5], (di, d), dtype,
                             scale=0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
    }


def _rwkv_params(key, cfg: ModelConfig, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    lora = max(32, d // 16)
    ks = jax.random.split(key, 10)
    p = {
        "w_r": _dense_init(ks[0], (d, d), dtype),
        "w_k": _dense_init(ks[1], (d, d), dtype),
        "w_v": _dense_init(ks[2], (d, d), dtype),
        "w_g": _dense_init(ks[3], (d, d), dtype),
        "w_o": _dense_init(ks[4], (d, d), dtype,
                           scale=0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
        "w_dec_a": _dense_init(ks[5], (d, lora), jnp.float32),
        "w_dec_b": _dense_init(ks[6], (lora, d), jnp.float32),
        "w_dec0": jnp.full((d,), 0.0, jnp.float32),
        "u_bonus": jnp.zeros((d // RWKV_HEAD, RWKV_HEAD), jnp.float32),
        "ln_x": jnp.zeros((d,), jnp.float32),
        "w_ck": _dense_init(ks[7], (d, ff), dtype),
        "w_cv": _dense_init(ks[8], (ff, d), dtype,
                            scale=0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
        "w_cr": _dense_init(ks[9], (d, d), dtype),
    }
    for name in ("r", "k", "v", "g", "w"):
        p[f"mu_{name}"] = jnp.full((d,), 0.5, jnp.float32)
    p["mu_ck"] = jnp.full((d,), 0.5, jnp.float32)
    p["mu_cr"] = jnp.full((d,), 0.5, jnp.float32)
    p["ln1"] = jnp.zeros((d,), jnp.float32)
    p["ln2"] = jnp.zeros((d,), jnp.float32)
    return p


def _sublayer_params(key, cfg: ModelConfig, pattern: str, dtype):
    if pattern == "rwkv":
        return _rwkv_params(key, cfg, dtype)
    if pattern == "mamba":
        p = _mamba_params(key, cfg, dtype)
        p["ln"] = jnp.zeros((cfg.d_model,), jnp.float32)
        return p
    if pattern in ("moe", "moe_swa"):
        k1, k2 = jax.random.split(key)
        return {
            "attn": _attn_params(k1, cfg, dtype),
            "moe": _moe_params(k2, cfg, dtype),
            "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
            "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
        }
    # plain attention + dense mlp
    k1, k2 = jax.random.split(key)
    return {
        "attn": _attn_params(k1, cfg, dtype),
        "mlp": _mlp_params(k2, cfg, dtype),
        "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
        "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
    }


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = model_dtype(cfg)
    keys = jax.random.split(key, 16)
    d = cfg.d_model
    params: Dict[str, Any] = {
        "embed": _dense_init(keys[0], (cfg.vocab, d), dtype),
        "final_norm": jnp.zeros((d,), jnp.float32),
        "lm_head": _dense_init(keys[1], (d, cfg.vocab), dtype),
    }
    groups = []
    for gi, group in enumerate(layer_groups(cfg)):
        gkey = jax.random.fold_in(keys[2], gi)
        sub = {}
        for j, pattern in enumerate(group.patterns):
            if pattern == "shared_attn":
                continue  # lives outside the scan (weight sharing)
            jkeys = jax.random.split(jax.random.fold_in(gkey, j),
                                     group.repeats)
            stacked = jax.vmap(
                lambda k: _sublayer_params(k, cfg, pattern, dtype))(jkeys)
            sub[f"sub{j}"] = stacked
        groups.append(sub)
    params["groups"] = groups
    if cfg.family == "hybrid":
        k1, k2 = jax.random.split(keys[3])
        params["shared_attn"] = {
            "attn": _attn_params(k1, cfg, dtype),
            "mlp": _mlp_params(k2, cfg, dtype),
            "ln1": jnp.zeros((d,), jnp.float32),
            "ln2": jnp.zeros((d,), jnp.float32),
        }
    if cfg.family == "encdec":
        enc_keys = jax.random.split(keys[4], cfg.encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: {
                "attn": _attn_params(k, cfg, dtype),
                "mlp": _mlp_params(jax.random.fold_in(k, 1), cfg, dtype,
                                   gelu=True),
                "ln1_g": jnp.ones((d,), jnp.float32),
                "ln1_b": jnp.zeros((d,), jnp.float32),
                "ln2_g": jnp.ones((d,), jnp.float32),
                "ln2_b": jnp.zeros((d,), jnp.float32),
            })(enc_keys)
        dec_keys = jax.random.split(keys[5], cfg.n_layers)
        params["cross"] = jax.vmap(
            lambda k: {
                "attn": _attn_params(k, cfg, dtype, cross=True),
                "ln_g": jnp.ones((d,), jnp.float32),
                "ln_b": jnp.zeros((d,), jnp.float32),
            })(dec_keys)
        params["enc_ln_g"] = jnp.ones((d,), jnp.float32)
        params["enc_ln_b"] = jnp.zeros((d,), jnp.float32)
    if cfg.family == "vlm":
        # modality-frontend STUB projection (patch embeds arrive precomputed)
        params["patch_proj"] = _dense_init(keys[6], (d, d), dtype)
    return params


# ---------------------------------------------------------------------------
# Sub-layer application
# ---------------------------------------------------------------------------

def _sp_gather(x, cfg: ModelConfig):
    """Megatron-SP gather point: attention/MLP consume the FULL sequence.

    The residual stream is pinned seq-sharded between layers
    (`_maybe_shard_seq`); right before each block we pin the post-norm
    tensor seq-UNsharded, so GSPMD emits one all-gather(seq) here and one
    reduce-scatter at the next residual pin — instead of resolving the
    (seq x weight) double-"model"-sharding by all-gathering full weight
    matrices inside the layer scan (462 MB f32 per matmul observed).
    """
    if not cfg.seq_shard or x.ndim != 3 or not cfg.mesh_axis_sizes:
        return x
    from jax.sharding import PartitionSpec as P
    sizes = dict(cfg.mesh_axis_sizes)
    nb = 1
    for a in cfg.mesh_batch_axes:
        nb *= sizes.get(a, 1)
    bax = cfg.mesh_batch_axes if nb > 1 and x.shape[0] % nb == 0 else None
    return jax.lax.with_sharding_constraint(x, P(bax, None, None))


def _apply_sublayer(x, p, cfg: ModelConfig, pattern: str, positions,
                    cache=None, train: bool = False, chunked: bool = False):
    """Returns (x, new_cache, aux) — aux is (2,) f32 [load_balance, z]."""
    zero_aux = jnp.zeros((2,), jnp.float32)
    if pattern == "rwkv":
        h, st1 = rwkv6_time_mix(
            rms_norm(x, p["ln1"]), p, cfg, state=cache, train=train)
        x = x + h
        h, st2 = rwkv6_channel_mix(
            rms_norm(x, p["ln2"]), p, cfg, state=cache, train=train)
        x = x + h
        new_cache = None if cache is None else {**st1, **st2}
        return x, new_cache, zero_aux
    if pattern == "mamba":
        h, st = mamba2_block(rms_norm(x, p["ln"]), p, cfg,
                             state=cache, train=train)
        return x + h, st, zero_aux
    # attention-based sub-layers
    pat, window = {
        "causal": ("causal", None),
        "global": ("causal", None),
        "local": ("local", cfg.local_window),
        "swa": ("local", cfg.sliding_window),
        "moe": ("causal", None),
        "moe_swa": ("local", cfg.sliding_window),
        "shared_attn": ("causal", None),
    }[pattern]
    h, new_cache = attn_block(
        _sp_gather(rms_norm(x, p["ln1"]), cfg), p["attn"], cfg, positions,
        pattern=pat, window=window, cache=cache, train=train,
        chunked=chunked)
    x = x + h
    aux = zero_aux
    if pattern in ("moe", "moe_swa"):
        h, aux_d = moe_block(_sp_gather(rms_norm(x, p["ln2"]), cfg),
                             p["moe"], cfg, train=train)
        aux = jnp.stack([aux_d["load_balance"], aux_d["router_z"]])
    else:
        h = swiglu(_sp_gather(rms_norm(x, p["ln2"]), cfg), p["mlp"],
                   cfg.quant, train)
    return x + h, new_cache, aux


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _maybe_shard_seq(x, cfg: ModelConfig):
    """Pin the residual stream's layout between layers.

    (1) batch stays sharded over the data axes — CRITICAL under FSDP
    weight sharding: without this, GSPMD may choose partial-sum matmuls
    that ALL-REDUCE full activations (10 GB/layer observed) instead of
    all-gathering weights (19 MB/layer);
    (2) with cfg.seq_shard, the sequence dim additionally shards over
    "model" (Megatron-SP) — GSPMD inserts the all-gather/reduce-scatter
    pairs around attention/MLP.
    No-op outside a mesh context (CPU unit tests).
    """
    if x.ndim != 3 or not cfg.mesh_axis_sizes:
        return x
    from jax.sharding import PartitionSpec as P
    sizes = dict(cfg.mesh_axis_sizes)
    nb = 1
    for a in cfg.mesh_batch_axes:
        nb *= sizes.get(a, 1)
    if nb <= 1:
        return x
    bax = cfg.mesh_batch_axes if x.shape[0] % nb == 0 else None
    sax = None
    if cfg.seq_shard and x.shape[1] >= 64 \
            and x.shape[1] % sizes.get("model", 1) == 0:
        sax = "model"
    if bax is None and sax is None:
        return x
    return jax.lax.with_sharding_constraint(x, P(bax, sax, None))


def _scan_group(x, group_params, cfg, patterns, positions, shared=None,
                caches=None, train=False, chunked=False, pages=None):
    """Scan a homogeneous group of layers.

    group_params: {"sub{j}": stacked-params} (leading axis = repeats).
    caches: matching stacked cache pytree or None.
    pages: {"sub{j}": whole stacked page pools with the block table}
    for paged decode (`serving.runner.paged_decode_caches`) or None.
    They ride outside the scan, which would otherwise slice (copy) one
    layer's pool per step; each layer finds them in its sub-cache as
    "pages", beside its layer index "layer".
    Returns (x, new_caches, aux_sum).
    """
    pages = pages or {}

    def body(carry, per_layer):
        h = carry
        h = _maybe_shard_seq(h, cfg)
        p_layer, cache_layer, layer = per_layer
        new_caches = {}
        aux_acc = jnp.zeros((2,), jnp.float32)
        for j, pattern in enumerate(patterns):
            p_sub = shared if pattern == "shared_attn" else p_layer[f"sub{j}"]
            c_in = None if cache_layer is None else cache_layer.get(f"sub{j}")
            if f"sub{j}" in pages:
                c_in = {**c_in, "pages": pages[f"sub{j}"], "layer": layer}
            h, c_out, aux = _apply_sublayer(
                h, p_sub, cfg, pattern, positions, cache=c_in, train=train,
                chunked=chunked)
            aux_acc = aux_acc + aux
            if c_in is not None:
                new_caches[f"sub{j}"] = c_out
        return h, (new_caches if new_caches else None, aux_acc)

    n_rep = jax.tree_util.tree_leaves(group_params)[0].shape[0]
    if cfg.remat == "full":
        body = jax.checkpoint(body)
    layers = jnp.arange(n_rep, dtype=jnp.int32) if pages else None
    x, (new_caches, aux) = jax.lax.scan(body, x,
                                        (group_params, caches, layers))
    return x, new_caches, aux.sum(0)


def sinusoid_pos(s: int, d: int, dtype):
    """Whisper-style sinusoidal positions (S, d)."""
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (dim / max(d // 2 - 1, 1)))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


def _encoder_forward(params, frames, cfg: ModelConfig, train=False):
    """Whisper encoder over stub frame embeddings (B, S_enc, d)."""
    x = frames + sinusoid_pos(frames.shape[1], cfg.d_model, frames.dtype)

    def body(h, p):
        a, _ = attn_block(
            layer_norm(h, p["ln1_g"], p["ln1_b"]), p["attn"], cfg,
            positions=None, pattern="full", window=None, train=train)
        h = h + a
        m = gelu_mlp(layer_norm(h, p["ln2_g"], p["ln2_b"]), p["mlp"],
                     cfg.quant, train)
        return h + m, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return layer_norm(x, params["enc_ln_g"], params["enc_ln_b"])


def _cross_kv(params, enc_out, cfg: ModelConfig):
    """Precompute per-decoder-layer cross K/V from encoder output."""
    KV, dh = cfg.n_kv_heads, cfg.head_dim
    B, S, _ = enc_out.shape

    def per_layer(p):
        k = qdot(enc_out, p["attn"]["wk"], cfg.quant).reshape(B, S, KV, dh)
        v = qdot(enc_out, p["attn"]["wv"], cfg.quant).reshape(B, S, KV, dh)
        return k, v

    return jax.vmap(per_layer)(params["cross"])  # (L, B, S, KV, dh) x2


def _decoder_backbone(params, x, cfg: ModelConfig, positions, cross_kv,
                      caches=None, train=False):
    """Whisper decoder: scanned [self-attn, cross-attn, mlp] layers."""
    group = params["groups"][0]

    def body(h, inp):
        p_layer, (ck, cv), cache_layer = inp
        p = p_layer["sub0"]
        c_in = None if cache_layer is None else cache_layer["self"]
        a, c_out = attn_block(
            rms_norm(h, p["ln1"]), p["attn"], cfg, positions,
            pattern="causal", window=None, cache=c_in, train=train)
        h = h + a
        pc = p_layer["cross"]
        a, _ = attn_block(
            layer_norm(h, pc["ln_g"], pc["ln_b"]), pc["attn"], cfg,
            positions=None, pattern="full", window=None,
            kv_override=(ck, cv), train=train)
        h = h + a
        m = swiglu(rms_norm(h, p["ln2"]), p["mlp"], cfg.quant, train)
        new_cache = None if c_in is None else {"self": c_out}
        return h + m, new_cache

    layer_params = {"sub0": group["sub0"], "cross": params["cross"]}
    x, new_caches = jax.lax.scan(body, x, (layer_params, cross_kv, caches))
    return x, new_caches


def _lm_backbone(params, x, cfg: ModelConfig, positions, caches=None,
                 train=False, chunked=False, pages=None):
    """Run all scanned groups.  caches, pages: lists aligned with groups
    or None (`_scan_group`)."""
    shared = params.get("shared_attn")
    new_caches = []
    aux = jnp.zeros((2,), jnp.float32)
    for gi, group in enumerate(layer_groups(cfg)):
        c_in = None if caches is None else caches[gi]
        x, c_out, a = _scan_group(
            x, params["groups"][gi], cfg, group.patterns, positions,
            shared=shared, caches=c_in, train=train, chunked=chunked,
            pages=None if pages is None else pages[gi])
        new_caches.append(c_out)
        aux = aux + a
    return x, new_caches, aux


def chunked_cross_entropy(hidden, lm_head, labels, cfg: ModelConfig,
                          chunk: int = 1024):
    """Mean CE over valid labels (-1 = ignore), logits in f32, computed in
    sequence chunks so the (B, S, V) logits tensor never materializes."""
    q = cfg.quant
    B, S, d = hidden.shape
    c = min(chunk, S)
    while S % c:
        c -= 1
    n = S // c

    def body(carry, inp):
        tot, cnt = carry
        h_c, y_c = inp                      # (B, c, d), (B, c)
        h_c = _maybe_shard_seq(h_c, dataclasses.replace(
            cfg, seq_shard=False))
        logits = qdot(h_c, lm_head, q).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(y_c, 0)[..., None], -1)[..., 0]
        valid = (y_c >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((logz - gold) * valid)
        cnt = cnt + jnp.sum(valid)
        return (tot, cnt), None

    hs = hidden.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    ys = labels.reshape(B, n, c).transpose(1, 0, 2)
    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ys))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, batch, cfg: ModelConfig, train: bool = True):
    """batch: {"tokens" (B,S), "labels" (B,S)} + family-specific stubs:
    encdec: "frames" (B, S_enc, d); vlm: "patches" (B, P, d)."""
    dtype = model_dtype(cfg)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_lookup(tokens, params["embed"], cfg.quant, train).astype(dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    labels = batch["labels"]

    if cfg.family == "encdec":
        enc = _encoder_forward(params, batch["frames"].astype(dtype), cfg,
                               train)
        x = x + sinusoid_pos(S, cfg.d_model, dtype)
        ck, cv = _cross_kv(params, enc, cfg)
        x, _ = _decoder_backbone(params, x, cfg, None, (ck, cv), train=train)
        aux = jnp.zeros((2,), jnp.float32)
    else:
        if cfg.family == "vlm":
            patches = qdot(batch["patches"].astype(dtype),
                           params["patch_proj"], cfg.quant, train)
            x = jnp.concatenate([patches, x], axis=1)
            P = patches.shape[1]
            S = S + P
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None], (B, S))
            labels = jnp.concatenate(
                [jnp.full((B, P), -1, labels.dtype), labels], axis=1)
        x, _, aux = _lm_backbone(params, x, cfg, positions, train=train)

    x = rms_norm(x, params["final_norm"])
    ce = chunked_cross_entropy(x, params["lm_head"], labels, cfg,
                               cfg.loss_chunk)
    loss = ce + 0.01 * aux[0] + 1e-3 * aux[1]
    return loss, {"ce": ce, "load_balance": aux[0], "router_z": aux[1]}


# ---------------------------------------------------------------------------
# Serving: cache init, prefill, decode
# ---------------------------------------------------------------------------

def _attn_cache(cfg: ModelConfig, reps, B, max_len, dtype, window=None):
    KV, dh = cfg.n_kv_heads, cfg.head_dim
    buf_len = min(max_len, window) if window else max_len
    if cfg.quant.quantize_kv_cache:
        if cfg.quant.kv_layout == "packed":
            # ONE packed VP word per element (`core.packing`), consumed
            # directly by the decode-attention kernel — no per-step
            # index unpacking, no two-plane reads.
            from repro.core.packing import storage_dtype
            from .attention import kv_cache_formats

            _, vp = kv_cache_formats(cfg.quant)
            wdt = storage_dtype(vp)
            return dict(
                k_w=jnp.zeros((reps, B, buf_len, KV, dh), wdt),
                k_s=jnp.zeros((reps, B, buf_len, 1, 1), jnp.float32),
                v_w=jnp.zeros((reps, B, buf_len, KV, dh), wdt),
                v_s=jnp.zeros((reps, B, buf_len, 1, 1), jnp.float32),
                len=jnp.zeros((reps, B), jnp.int32),
            )
        E = cfg.quant.E
        per = 8 // E if E else 1
        dh_i = dh // per if (E and dh % per == 0) else dh
        return dict(
            k_m=jnp.zeros((reps, B, buf_len, KV, dh), jnp.int8),
            k_i=jnp.zeros((reps, B, buf_len, KV, dh_i), jnp.uint8),
            k_s=jnp.zeros((reps, B, buf_len, 1, 1), jnp.float32),
            v_m=jnp.zeros((reps, B, buf_len, KV, dh), jnp.int8),
            v_i=jnp.zeros((reps, B, buf_len, KV, dh_i), jnp.uint8),
            v_s=jnp.zeros((reps, B, buf_len, 1, 1), jnp.float32),
            len=jnp.zeros((reps, B), jnp.int32),
        )
    return dict(
        k=jnp.zeros((reps, B, buf_len, KV, dh), dtype),
        v=jnp.zeros((reps, B, buf_len, KV, dh), dtype),
        len=jnp.zeros((reps, B), jnp.int32),
    )


def init_cache(cfg: ModelConfig, B: int, max_len: int):
    """Decode caches, stacked per group (leading axis = group repeats)."""
    dtype = model_dtype(cfg)
    d = cfg.d_model
    caches = []
    for group in layer_groups(cfg):
        g = {}
        for j, pattern in enumerate(group.patterns):
            reps = group.repeats
            if pattern == "rwkv":
                H, N = d // RWKV_HEAD, RWKV_HEAD
                g[f"sub{j}"] = dict(
                    s=jnp.zeros((reps, B, H, N, N), jnp.float32),
                    last_tm=jnp.zeros((reps, B, d), dtype),
                    last_cm=jnp.zeros((reps, B, d), dtype),
                )
            elif pattern == "mamba":
                di, n, h, p_, conv_dim, _ = mamba2_dims(cfg)
                g[f"sub{j}"] = dict(
                    h=jnp.zeros((reps, B, h, p_, n), jnp.float32),
                    conv=jnp.zeros((reps, B, D_CONV - 1, conv_dim), dtype),
                )
            else:
                window = (cfg.sliding_window
                          if pattern in ("swa", "moe_swa")
                          else (cfg.local_window
                                if pattern == "local" else None))
                g[f"sub{j}"] = _attn_cache(cfg, reps, B, max_len, dtype,
                                           window)
        caches.append(g)
    if cfg.family == "encdec":
        caches = [{"self": _attn_cache(cfg, cfg.n_layers, B, max_len, dtype)}]
    return caches


def decode_step(params, token, caches, cfg: ModelConfig,
                cross_kv=None, pages=None):
    """One decode step: token (B, 1) -> (logits (B, V), new caches).

    pages: per group, the page pools that paged sub-caches read in place
    (`_scan_group`), or None."""
    dtype = model_dtype(cfg)
    B = token.shape[0]
    x = embed_lookup(token, params["embed"], cfg.quant).astype(dtype)
    if cfg.family == "encdec":
        from .attention import _cache_buf
        self_c = caches[0]["self"]
        pos_len = self_c["len"][0]                       # (B,)
        max_pos = _cache_buf(self_c).shape[2]
        sin = sinusoid_pos(max_pos, cfg.d_model, dtype)  # (Smax, d)
        x = x + jnp.take(sin, jnp.clip(pos_len, 0, max_pos - 1),
                         axis=0)[:, None]
        x, new_caches = _decoder_backbone(
            params, x, cfg, None, cross_kv, caches=caches[0], train=False)
        new_caches = [new_caches]
    else:
        pos = _decode_positions(caches, cfg)
        x, new_caches, _ = _lm_backbone(params, x, cfg, pos, caches=caches,
                                        pages=pages)
    x = rms_norm(x, params["final_norm"])
    logits = qdot(x[:, 0], params["lm_head"], cfg.quant)
    return logits.astype(jnp.float32), new_caches


def _decode_positions(caches, cfg):
    """Current absolute position per batch element from any attn cache."""
    for g in caches:
        if g is None:
            continue
        for sub in g.values():
            if isinstance(sub, dict) and "len" in sub:
                return sub["len"][0][:, None]
    # SSM-only model: positions unused (no rope) — return zeros
    first = jax.tree_util.tree_leaves(caches)[0]
    B = first.shape[1]
    return jnp.zeros((B, 1), jnp.int32)


def prefill(params, tokens, caches, cfg: ModelConfig, patches=None,
            chunked=False):
    """Prefill the caches with a full prompt — ONE batched causal pass.

    Attention layers write all S key/values into their caches; SSM layers
    run the chunked scan and keep the final state.  Returns
    (last-position logits (B, V), filled caches).

    chunked: `tokens` is a prompt CHUNK continuing already-prefilled
    caches (continuous batching) — positions offset by the cache length,
    attention layers append at that offset, SSM states carry forward.
    """
    dtype = model_dtype(cfg)
    B, S = tokens.shape
    if chunked and cfg.family == "encdec":
        raise ValueError("chunked prefill is not supported for encdec")
    x = embed_lookup(tokens, params["embed"], cfg.quant).astype(dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if chunked:
        positions = _decode_positions(caches, cfg) + positions
    if cfg.family == "vlm" and patches is not None:
        pp = qdot(patches.astype(dtype), params["patch_proj"], cfg.quant)
        x = jnp.concatenate([pp, x], axis=1)
        S = x.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.family == "encdec":
        # decoder-prompt prefill: frames must already be encoded; the
        # caller passes cross_kv via `patches` (reused slot).
        cross_kv = patches
        x = x + sinusoid_pos(S, cfg.d_model, dtype)
        x, dec_caches = _decoder_backbone(
            params, x, cfg, None, cross_kv, caches=caches[0], train=False)
        x = rms_norm(x, params["final_norm"])
        logits = qdot(x[:, -1], params["lm_head"], cfg.quant)
        return logits.astype(jnp.float32), [dec_caches]
    x, new_caches, _ = _lm_backbone(params, x, cfg, positions, caches=caches,
                                    chunked=chunked)
    x = rms_norm(x, params["final_norm"])
    logits = qdot(x[:, -1], params["lm_head"], cfg.quant)
    return logits.astype(jnp.float32), new_caches


def quantize_params(params, cfg: ModelConfig, layout: str = "packed"):
    """Export-time transform: float weights -> serving representation.

    `layout` (VP modes only) picks the storage the serving path consumes:
    "packed" (default) emits ONE packed VP word per element — the layout
    the Pallas `vp_dequant_matmul` kernel reads directly in `qdot`;
    "planes" emits the legacy two-plane layout dequantized in jnp (the
    golden baseline the cross-arch parity suite pins the kernel against).
    """
    if cfg.quant.mode == "none":
        return params
    QUANT_KEYS = {
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_in", "w_out",
        "w_r", "w_k", "w_v", "w_g", "w_o", "w_ck", "w_cv", "w_cr",
        "w_z", "w_x", "w_bc", "w_dt",
        "embed", "lm_head", "patch_proj",
    }

    def qw(w):
        return quantize_weight(w, cfg.quant, layout=layout)

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k in QUANT_KEYS and isinstance(v, jax.Array):
                    if v.ndim == 2:
                        out[k] = qw(v)
                    elif v.ndim == 3:  # stacked (L or E, d_in, d_out)
                        out[k] = jax.vmap(qw)(v)
                    elif v.ndim == 4:  # layer- AND expert-stacked MoE
                        out[k] = jax.vmap(jax.vmap(qw))(v)
                    else:
                        out[k] = v
                elif isinstance(v, (dict, list)):
                    out[k] = walk(v)
                else:
                    out[k] = v
            return out
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node

    return walk(params)
