"""Mesh-native execution of the packed VP datapath under shard_map.

The paper's packed words earn their keep twice on a mesh: the same
narrow int8/int16 words that halve HBM traffic also halve (or quarter)
COLLECTIVE bytes versus f32, so tensor-parallel shards exchange packed
words and dequantize after the gather, in-tile.  Three weight-sharded
execution modes, all bit-identical to the single-device oracle on the
ref backend (every collective here is a pure concatenation — no
cross-device reduction ever touches a float accumulation order):

  column  local dequant-matmul on the weight shard, then all-gather the
          OUTPUT activations.  The serving default: for decode the
          activation plane (M x N/tp floats) is far smaller than the
          weight shard, so this moves the fewest bytes.
  gather  all-gather the PACKED weight words, then one full dequant-
          matmul.  Moves int words (2-4x fewer bytes than f32 weights)
          but materializes the full unsharded weight on every device —
          the anti-pattern `analysis.jaxpr_lint` JX-SHGATH flags; kept
          as the non-overlapped baseline the sweep driver times.
  ring    collective matmul: per step, dequant-matmul the resident
          packed chunk into its owner's output columns, while ppermute
          rotates the NEXT packed chunk around the mesh.  Communication
          is packed words AND it hides behind compute; the full f32
          weight never exists on any device.

The datapath is trainable under the same mesh: `sharded_matmul_dx`
reduces dL/dx across the tensor axis (one psum, or a ring
reduce-scatter of row chunks overlapped with the per-chunk packed-word
backward kernels), `sharded_matmul_dw` computes each shard's weight
gradient purely locally, and `dp_compress_reduce` runs the
error-feedback gradient codec before the data-axis mean.

`shard_param_specs` places a whole quantized param tree for the model-
level wrappers: every quantized weight leaf shards its OUTPUT (last)
dim over the tensor axis, stacked MoE expert leaves shard their expert
axis instead (expert parallelism), scales/norms/biases/router stay
replicated.  `qdot`/`embed_lookup`/`moe_block` then all-gather their
local outputs when `QuantConfig.tp_axis` is set, so full-model prefill
and decode run under shard_map with no other model changes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, QuantConfig
from repro.kernels import autotune
from repro.kernels import ops as kops

MODES = ("column", "gather", "ring")

# Quantized-leaf member arrays whose trailing dim is the OUTPUT dim
# (every storage layout `quantize_weight` emits keeps d_out last).
_WEIGHT_MEMBERS = ("w_packed", "m", "w", "i_packed", "i_blk")


class ShardSpecError(ValueError):
    """A param tree cannot be placed on the requested tensor axis."""


# ---------------------------------------------------------------------------
# Op-level sharded execution (call INSIDE shard_map)
# ---------------------------------------------------------------------------

def sharded_dequant_matmul(x, w_packed, fmt, *, axis: str = "model",
                           mode: str = "ring", out_dtype=None):
    """x (M, K) replicated, w_packed (K, N/tp) local -> (M, N) replicated.

    Must run inside shard_map over `axis`.  All three modes return the
    bit-exact single-device result on the ref backend: `column`/`ring`
    compute each output column block from the same dequantized words in
    the same contraction order as the full matmul, and `gather`
    reassembles the identical full weight before one full matmul.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}: {mode!r}")
    tp = jax.lax.psum(1, axis)
    if mode == "column":
        with autotune.mesh_scope(f"{axis}{tp}.N"):
            y = kops.vp_dequant_matmul(x, w_packed, fmt, out_dtype=out_dtype)
        return jax.lax.all_gather(y, axis, axis=1, tiled=True)
    if mode == "gather":
        # The matmul runs on the REASSEMBLED full weight, so its tiling
        # geometry equals the single-device launch: no mesh scope.
        w_full = jax.lax.all_gather(w_packed, axis, axis=1, tiled=True)
        return kops.vp_dequant_matmul(x, w_full, fmt, out_dtype=out_dtype)
    # ring: overlap per-chunk dequant-matmul with the packed-word rotate.
    idx = jax.lax.axis_index(axis)
    n_loc = w_packed.shape[1]
    dtype = out_dtype if out_dtype is not None else x.dtype
    y = jnp.zeros((x.shape[0], n_loc * tp), dtype)
    chunk = w_packed
    perm = [(i, (i - 1) % tp) for i in range(tp)]
    with autotune.mesh_scope(f"{axis}{tp}.N"):
        for step in range(tp):
            owner = (idx + step) % tp
            y_loc = kops.vp_dequant_matmul(x, chunk, fmt,
                                           out_dtype=out_dtype)
            y = jax.lax.dynamic_update_slice(y, y_loc, (0, owner * n_loc))
            if step < tp - 1:
                chunk = jax.lax.ppermute(chunk, axis, perm=perm)
    return y


def sharded_matmul_dx(g, w_packed, fmt, *, axis: str = "model",
                      mode: str = "psum", out_dtype=jnp.float32,
                      gather: bool = True):
    """Backward of the column-sharded forward: dL/dx from a REPLICATED
    output cotangent g (M, N) and the LOCAL packed weight shard
    w_packed (K, N/tp) -> dx (M, K).  Must run inside shard_map.

    A shard owns N/tp output columns, so its contribution to dx is
    g[:, own cols] @ dequant(w_loc)^T — the packed-word backward kernel
    (`kernels.ops.vp_matmul_dx`); the f32 weight plane never exists on
    any device, mirroring the forward modes.

      psum  local partial dx, then one all-reduce of M*K floats.  The
            simple baseline (the backward analogue of `gather`).
      ring  reduce-scatter: dx is chunked along M; each step computes
            the partial for ONE rotating chunk while the accumulating
            buffer ppermutes around the mesh, so after tp steps device i
            holds its fully-reduced (M/tp, K) chunk — tp-fold fewer
            collective bytes, hidden behind the per-chunk kernels.
            `gather=True` all-gathers the chunks back to a replicated
            dx; False leaves dx row-sharded (ZeRO-style consumers).

    psum and ring add the same tp partials in different orders, so the
    modes agree to f32 reduction tolerance (each is deterministic on its
    own) — unlike the forward modes, which are concatenation-exact.
    """
    if mode not in ("psum", "ring"):
        raise ValueError(f"mode must be 'psum' or 'ring': {mode!r}")
    tp = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    n_loc = w_packed.shape[1]
    g_loc = jax.lax.dynamic_slice_in_dim(g, idx * n_loc, n_loc, axis=1)
    if mode == "psum":
        with autotune.mesh_scope(f"{axis}{tp}.N"):
            dx = kops.vp_matmul_dx(g_loc, w_packed, fmt,
                                   out_dtype=out_dtype)
        return jax.lax.psum(dx, axis)
    m = g.shape[0]
    if m % tp:
        raise ShardSpecError(
            f"ring dx reduce-scatter chunks the batch dim: M={m} is not "
            f"divisible by tensor-parallel size {tp}")
    m_loc = m // tp

    def chunk_term(c):
        g_c = jax.lax.dynamic_slice_in_dim(g_loc, c * m_loc, m_loc, axis=0)
        return kops.vp_matmul_dx(g_c, w_packed, fmt, out_dtype=out_dtype)

    # Invariant: after step s, device i's buf holds
    # sum_{d=i..i+s} T_d(chunk (i+1+s) % tp), where T_d(c) is device d's
    # partial for chunk c — so after tp-1 steps buf is chunk i, fully
    # reduced.  Same rotation as the forward ring.
    perm = [(i, (i - 1) % tp) for i in range(tp)]
    with autotune.mesh_scope(f"{axis}{tp}.N"):
        buf = chunk_term((idx + 1) % tp)
        for s in range(1, tp):
            buf = jax.lax.ppermute(buf, axis, perm=perm) \
                + chunk_term((idx + 1 + s) % tp)
    if gather:
        return jax.lax.all_gather(buf, axis, axis=0, tiled=True)
    return buf


def sharded_matmul_dw(a_w, g, fmt, *, axis: str = "model",
                      out_dtype=jnp.float32):
    """dL/dW shard for the column-sharded weight: dequant(a_w)^T @
    g[:, own cols] -> (K, N/tp).  Must run inside shard_map.

    Entirely LOCAL — each device's weight shard is touched only by its
    own output columns, so the weight gradient needs no tensor-axis
    collective at all (the DP-axis reduction is `dp_compress_reduce`).
    The packed residual a_w rides HBM at storage_bits per element.
    """
    tp = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    n_loc = g.shape[1] // tp
    g_loc = jax.lax.dynamic_slice_in_dim(g, idx * n_loc, n_loc, axis=1)
    with autotune.mesh_scope(f"{axis}{tp}.N"):
        return kops.vp_matmul_dw(a_w, g_loc, fmt, out_dtype=out_dtype)


def dp_compress_reduce(grads, state, *, axis: str = "data", config=None):
    """Error-feedback compressed data-parallel gradient mean.

    Must run inside shard_map over `axis`.  Each DP rank quantizes its
    LOCAL gradient tree (int8 or packed VP words per
    `CompressionConfig.codec`) carrying the residual in `state`; what
    crosses the wire is the reduction of the DEQUANTIZED planes —
    modeling the reduce-scatter-of-words fleets run, with the residual
    keeping SGD convergence (the compressor is a contraction).  Returns
    (mean grads, new state); per-rank residuals stay rank-local.
    """
    # Imported here: train.compression is a training-side module and
    # this one is imported by serving paths (no train deps at import).
    from repro.train.compression import (CompressionConfig,
                                         compress_decompress)

    if config is None:
        config = CompressionConfig()
    dp = jax.lax.psum(1, axis)
    deq, new_state = compress_decompress(grads, state, config)
    reduced = jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g, axis) / dp, deq)
    return reduced, new_state


def sharded_decode_attention(q, k_w, v_w, k_s, v_s, lengths, fmt, *,
                             axis: str = "model", mode: str = "seq",
                             window: Optional[int] = None,
                             rolling: bool = False):
    """Packed-KV decode attention under shard_map over `axis`.

    mode "seq":   caches sharded along the sequence dim (axis 1) — the
                  paged-KV layout; the shards are all-gathered as PACKED
                  words (+ their pow2 scales) and the unchanged op runs
                  on the reassembled cache.  The collective moves
                  storage_bits-per-element words, never f32 planes.
    mode "heads": q sharded along H, caches along KV — GQA groups are
                  independent, so each shard attends locally and the
                  outputs concatenate along the head dim.  No cache
                  collective at all.
    Both are bit-identical to the single-device op (concatenation-only
    collectives; softmax/contraction orders are untouched per position
    resp. per head group).
    """
    if mode == "seq":
        k_w = jax.lax.all_gather(k_w, axis, axis=1, tiled=True)
        v_w = jax.lax.all_gather(v_w, axis, axis=1, tiled=True)
        k_s = jax.lax.all_gather(k_s, axis, axis=1, tiled=True)
        v_s = jax.lax.all_gather(v_s, axis, axis=1, tiled=True)
        return kops.vp_decode_attention(q, k_w, v_w, k_s, v_s, lengths,
                                        fmt, window=window, rolling=rolling)
    if mode == "heads":
        tp = jax.lax.psum(1, axis)
        with autotune.mesh_scope(f"{axis}{tp}.H"):
            out = kops.vp_decode_attention(q, k_w, v_w, k_s, v_s, lengths,
                                           fmt, window=window,
                                           rolling=rolling)
        return jax.lax.all_gather(out, axis, axis=2, tiled=True)
    raise ValueError(f"mode must be 'seq' or 'heads': {mode!r}")


def sharded_flash_prefill(q, k, v, *, axis: str = "model",
                          pattern: str = "causal",
                          window: Optional[int] = None):
    """Flash prefill with q sharded along H and k/v along KV (axis 2).

    GQA head groups never interact, so the per-shard flash pass equals
    the corresponding head slice of the full pass bit-for-bit; outputs
    concatenate along the head dim.
    """
    from repro.models.attention import flash_attention

    tp = jax.lax.psum(1, axis)
    with autotune.mesh_scope(f"{axis}{tp}.H"):
        out = flash_attention(q, k, v, pattern=pattern, window=window)
    return jax.lax.all_gather(out, axis, axis=2, tiled=True)


# ---------------------------------------------------------------------------
# Param-tree placement
# ---------------------------------------------------------------------------

def _is_quant_leaf(node) -> bool:
    return isinstance(node, dict) and any(
        k in node for k in _WEIGHT_MEMBERS) and not any(
        isinstance(v, (dict, list)) for v in node.values())


def _leaf_specs(node: dict, path: str, axis: str, tp: int,
                expert: bool) -> dict:
    """Specs for one quantized leaf-dict (the `quantize_weight` output).

    Plain / layer-stacked weights ((d_in, d_out) or (L, d_in, d_out))
    shard d_out — the LAST dim of every storage member.  Expert-stacked
    MoE weights ((E, d_in, d_out) or (L, E, d_in, d_out), recognized by
    the sibling `w_router`) shard the expert axis (ndim-3) instead:
    expert parallelism keeps each expert's column dims whole, so the
    group-local dispatch math is untouched.
    """
    out = {}
    for k, v in node.items():
        if k in _WEIGHT_MEMBERS:
            dim = v.ndim - 3 if expert else v.ndim - 1
            if v.shape[dim] % tp:
                raise ShardSpecError(
                    f"{path}.{k}: dim {dim} of shape {tuple(v.shape)} is "
                    f"not divisible by tensor-parallel size {tp}; pick a "
                    f"mesh whose '{axis}' axis divides every quantized "
                    f"{'expert count' if expert else 'output dim'}")
            spec = [None] * v.ndim
            spec[dim] = axis
            out[k] = P(*spec)
        elif k == "scale" and expert:
            # per-expert scales ride the expert axis: (L, E) / (E,)
            out[k] = P(*([None] * (v.ndim - 1) + [axis]))
        else:
            out[k] = P()
    return out


def shard_param_specs(params, cfg: ModelConfig, *, axis: str = "model",
                      tp: int):
    """PartitionSpec tree mirroring a (quantized) param tree.

    Quantized leaf-dicts shard per `_leaf_specs`; every float leaf
    (norms, biases, router weights, unquantized models) is replicated —
    routing and layernorm math must be identical on every shard for the
    gathered outputs to be bit-exact.  Raises ShardSpecError with the
    offending path when a weight dim does not divide by `tp`.
    """
    if tp < 1:
        raise ShardSpecError(f"tensor-parallel size must be >= 1: {tp}")

    def walk(node, path, expert_ctx=False):
        if _is_quant_leaf(node):
            return _leaf_specs(node, path, axis, tp, expert_ctx) if tp > 1 \
                else {k: P() for k in node}
        if isinstance(node, dict):
            has_router = "w_router" in node
            return {k: walk(v, f"{path}.{k}" if path else k,
                            has_router and k in ("w_gate", "w_up", "w_down"))
                    for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v, f"{path}[{i}]") for i, v in enumerate(node)]
        return P()

    return walk(params, "")


def tp_quant(q: QuantConfig, axis: str = "model") -> QuantConfig:
    """The QuantConfig the shard_map'd forward runs under."""
    return dataclasses.replace(q, tp_axis=axis)


def tp_size(mesh, axis: str = "model") -> int:
    return mesh.shape[axis] if axis in mesh.shape else 1


def batch_dim_specs(tree, axis: str, dim: int):
    """Per-leaf specs sharding `dim` over `axis` (cache/view trees)."""
    return jax.tree_util.tree_map(
        lambda v: P(*[axis if d == dim else None
                      for d in range(v.ndim)]) if v.ndim > dim else P(),
        tree)


# ---------------------------------------------------------------------------
# Full-model shard_map wrappers
# ---------------------------------------------------------------------------

def sharded_forward_fns(params, cfg: ModelConfig, mesh, *,
                        axis: str = "model", data_axis: Optional[str] = None):
    """(prefill_fn, decode_fn) running the model under shard_map.

    Both take the SAME arguments as `models.prefill` / `decode_step`
    minus cfg; params must be placed per `shard_param_specs` (jit will
    reshard automatically if they are not).  Activations, caches and
    logits are replicated over the tensor axis; when `data_axis` is
    given the decode batch dim shards over it (the caller guarantees
    divisibility — serving buckets are powers of two).
    """
    from repro.models import model as M

    specs = shard_param_specs(params, cfg, axis=axis,
                              tp=tp_size(mesh, axis))
    cfg_sh = dataclasses.replace(cfg, quant=tp_quant(cfg.quant, axis))

    def prefill_body(p, tokens, caches, patches):
        return M.prefill(p, tokens, caches, cfg_sh, patches=patches)

    def chunk_body(p, tokens, caches, patches):
        return M.prefill(p, tokens, caches, cfg_sh, patches=patches,
                         chunked=True)

    def decode_body(p, token, caches, cross_kv):
        return M.decode_step(p, token, caches, cfg_sh, cross_kv=cross_kv)

    def wrap(body, example_caches=None, batch_sharded=False):
        if batch_sharded and data_axis is not None:
            cache_spec = batch_dim_specs(example_caches, data_axis, 1)
            arg_spec = P(data_axis)
            out0 = P(data_axis)
        else:
            cache_spec = jax.tree_util.tree_map(
                lambda _: P(), example_caches) if example_caches is not None \
                else P()
            arg_spec = P()
            out0 = P()
        return shard_map(
            body, mesh=mesh,
            in_specs=(specs, arg_spec, cache_spec, P()),
            out_specs=(out0, cache_spec), check_vma=False)

    def prefill_fn(p, tokens, caches, patches=None, chunked=False):
        body = chunk_body if chunked else prefill_body
        return wrap(body, caches)(p, tokens, caches, patches)

    def decode_fn(p, token, caches, cross_kv=None, batch_sharded=False):
        return wrap(decode_body, caches, batch_sharded=batch_sharded)(
            p, token, caches, cross_kv)

    return prefill_fn, decode_fn


def place_params(params, cfg: ModelConfig, mesh, *, axis: str = "model"):
    """device_put the param tree onto the mesh per `shard_param_specs`."""
    from jax.sharding import NamedSharding

    specs = shard_param_specs(params, cfg, axis=axis,
                              tp=tp_size(mesh, axis))
    return jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
        params, specs)
