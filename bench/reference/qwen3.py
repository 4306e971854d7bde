"""Qwen3 dense decoder served from VP words, forward pass in plain
jax.numpy and float32.

Follows the published architecture (Hugging Face `Qwen3ForCausalLM`):
RMSNorm before attention and MLP, per-head RMSNorm of q and k, rotary
embedding by halves (`rotate_half`) at `rope_theta`, grouped-query
attention, SwiGLU MLP, a final RMSNorm, and the LM head tied to the
embedding.  Every matmul runs at `Precision.HIGHEST`.

The benchmark's weights are made here from the seed (`make_weights`):
normal with the config's `initializer_range`, norms at one, in bfloat16,
the dtype the config publishes.  The program under test is handed the
same arrays and quantizes them itself.

What the configuration serves is rounded here too, with the benchmark's
own VP arithmetic (`bench/reference/vp.py`), so that the reference holds
the values a faithful VP datapath holds and the comparison sees the
precision of the arithmetic alone:

- every weight matrix (the embedding, and with it the tied LM head) is
  divided by the power of two at or above its largest magnitude (one per
  layer), put on the VP grid of `serving.quant` and scaled back;
- the keys (after norm and rotation) and the values enter the cache on
  the same grid, each position scaled by the power of two at or above
  its largest magnitude over heads and head dimensions.  A prompt
  attends to its own keys and values before they are rounded, as a
  prefill does; every later position reads them from the cache.

Everything else is float32.  `act="fp8"` is the control: the served
configuration computes in bfloat16, every activation it holds between
two operations included, and the next precision down is fp8.  So the
control rounds each of those to float8 e4m3 at a scale of its own row's
largest magnitude: the embedding's output, the residual stream after
each addition, each norm's output, q, k and v, the attention output,
the MLP's gate, up and product, and the final hidden state (which
enters the LM head).  Inside an operation (a matmul's sums, softmax) it
stays float32, as the program's kernels accumulate in float32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import vp

HIGHEST = jax.lax.Precision.HIGHEST
MATRICES = ("embed", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
E4M3_MAX = 448.0


def make_weights(cfg: dict, key) -> dict:
    """The benchmark's weights for `cfg`, made on the device (call under
    `jax.jit`); matrices are (d_in, d_out), stacked over layers."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KV, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, V = cfg["intermediate_size"], cfg["vocab_size"]
    std = cfg["initializer_range"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    shapes = {
        "embed": (V, d),
        "wq": (L, d, H * dh), "wk": (L, d, KV * dh), "wv": (L, d, KV * dh),
        "wo": (L, H * dh, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }
    keys = jax.random.split(key, len(shapes))
    w = {name: (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
         for k, (name, shape) in zip(keys, sorted(shapes.items()))}
    ones = functools.partial(jnp.ones, dtype=dtype)
    w.update(ln1=ones((L, d)), ln2=ones((L, d)), q_norm=ones((L, dh)),
             k_norm=ones((L, dh)), final_norm=ones((d,)))
    return w


def vp_format(cfg: dict) -> tuple:
    """(W, F, M, f) of the served VP words: values normalized to (-1, 1]
    on FXP(W, W - 1), M-bit significands, the paper's default fractions."""
    q = cfg["serving"]["quant"]
    W, M, E = q["W"], q["M"], q["E"]
    return W, W - 1, M, vp.default_fractions(W, W - 1, M, E)


def _on_grid(x, fmt, axes):
    """x scaled by the power of two at or above its largest magnitude over
    `axes`, rounded to the VP grid, scaled back."""
    s = vp.pow2_ceil(jnp.max(jnp.abs(x), axis=axes, keepdims=True))
    return vp.grid(x / s, *fmt) * s


def served_weights(cfg: dict, w: dict) -> dict:
    """The weights as the served VP words hold them, float32."""
    fmt = vp_format(cfg)
    out = {k: v.astype(jnp.float32) for k, v in w.items()}
    for k in MATRICES:
        x = out[k]
        out[k] = _on_grid(x, fmt, (-2, -1))      # per layer when stacked
    return out


def e4m3(x):
    """x rounded to float8 e4m3 (3 fraction bits, subnormals below 2^-6,
    largest 448), at the scale that maps each row's largest magnitude to
    448."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, E4M3_MAX / amax, 1.0)
    y = x * s
    _, e = jnp.frexp(jnp.maximum(jnp.abs(y), 2.0 ** -6))   # 2^(e-1) <= |y|
    ulp = jnp.ldexp(jnp.ones_like(y), e - 4)
    y = jnp.clip(jnp.round(y / ulp) * ulp, -E4M3_MAX, E4M3_MAX)
    return y / s


def _act(x, act: Optional[str]):
    if act is None:
        return x
    if act == "fp8":
        return e4m3(x)
    raise ValueError(f"unknown activation precision {act!r}")


def _mm(x, w):
    """x (..., K) @ w (K, N)."""
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, theta):
    """x (S, heads, dh) rotated by halves at positions 0 .. S-1."""
    S, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, causal):
    """Causal GQA attention: q (S, H, dh), k and v (S, KV, dh)."""
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / np.sqrt(q.shape[-1])
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)


def hidden_states(cfg: dict, w: dict, tokens, prompt_len,
                  act: Optional[str] = None):
    """Final-norm hidden states (S, d) of one sequence, float32.  `w` is
    `served_weights`; positions before `prompt_len` are the prompt."""
    H, KV, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    fmt = vp_format(cfg)
    S = tokens.shape[0]

    def hold(t):
        return _act(t, act)

    x = hold(w["embed"][tokens])
    causal = jnp.tril(jnp.ones((S, S), bool))
    prompt = (jnp.arange(S) < prompt_len)[:, None, None]

    def layer(x, p):
        h = hold(_rms(x, p["ln1"], eps))
        q = hold(_mm(h, p["wq"]).reshape(S, H, dh))
        k = hold(_mm(h, p["wk"]).reshape(S, KV, dh))
        v = hold(_mm(h, p["wv"]).reshape(S, KV, dh))
        q = hold(_rope(_rms(q, p["q_norm"], eps), theta))
        k = hold(_rope(_rms(k, p["k_norm"], eps), theta))
        kc, vc = _on_grid(k, fmt, (-2, -1)), _on_grid(v, fmt, (-2, -1))
        o = hold(jnp.where(prompt, _attend(q, k, v, causal),
                           _attend(q, kc, vc, causal)))
        x = hold(x + _mm(o.reshape(S, H * dh), p["wo"]))
        h = hold(_rms(x, p["ln2"], eps))
        g = hold(_mm(h, p["w_gate"]))
        u = hold(_mm(h, p["w_up"]))
        x = hold(x + _mm(hold(jax.nn.silu(g) * u), p["w_down"]))
        return x, None

    layers = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                "q_norm", "k_norm", "w_gate", "w_up",
                                "w_down")}
    x, _ = jax.lax.scan(layer, x, layers)
    return hold(_rms(x, w["final_norm"], eps))


def logit_rows(w: dict, hidden, tokens):
    """For each row of `hidden` (R, d): the largest logit, the logit of
    `tokens[r]`, and the argmax token.  The LM head is the embedding."""
    logits = jnp.matmul(hidden, w["embed"].T, precision=HIGHEST)
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best, at, jnp.argmax(logits, axis=-1).astype(jnp.int32)
