"""Sharding rules: parameter/batch/cache PartitionSpecs per architecture.

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod
("pod" is pure data parallelism).  Rules are name+shape based:

  * embeddings / lm_head: vocab sharded over "model";
  * attention projections: head dim over "model" IF the head count divides
    the model-axis size, else replicated (qwen2 14H, whisper 6H — noted in
    DESIGN.md; the MLP still shards, so TP remains useful);
  * MLP: column-parallel in, row-parallel out;
  * MoE experts: expert axis over "model" when E % tp == 0 (qwen3-moe),
    else d_ff over "model" (mixtral: 8e < 16 devices);
  * Mamba2 / RWKV6: d_inner-style dims over "model" when divisible;
  * batch dims over ("pod", "data").

Activation entry points get explicit constraints; GSPMD propagates the
rest from the weight shardings.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# ---------------------------------------------------------------------------
# Wideband OFDM: subcarrier-axis data parallelism (mimo/ofdm.py)
# ---------------------------------------------------------------------------

def subcarrier_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D device mesh with a single "sc" (subcarrier) axis.

    The wideband equalizer is embarrassingly parallel across subcarriers
    (independent per-subcarrier MVM batches), so the fleet layout is pure
    data parallelism over the band: each device owns a contiguous slab of
    subcarriers and runs the batched VP kernel on its slab.
    """
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), ("sc",))


def shard_over_subcarriers(fn, mesh: Optional[Mesh] = None,
                           n_subcarriers: Optional[int] = None):
    """shard_map `fn` over the leading subcarrier axis of its args.

    `fn` maps (S_local, ...) arrays to (S_local, ...) arrays (the flat
    wideband path in mimo/ofdm.py).  Inputs/outputs are sharded over the
    mesh's "sc" axis; every other dim is replicated.  When the subcarrier
    count does not divide the mesh (or the mesh is a single device) this
    degrades gracefully to running `fn` unsharded — callers never need a
    divisibility check on the serving path.
    """
    from jax.sharding import PartitionSpec

    if mesh is None:
        mesh = subcarrier_mesh()
    n_dev = mesh.shape["sc"]
    if n_dev == 1 or (n_subcarriers is not None and n_subcarriers % n_dev):
        return fn
    spec = PartitionSpec("sc")
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


def tp_size(mesh: Mesh) -> int:
    return mesh.shape["model"]


def _div(n: int, k: int) -> bool:
    return n % k == 0


def _spec_for_path(path: str, leaf, cfg: ModelConfig, tp: int,
                   fsdp: Optional[str] = "data") -> P:
    """PartitionSpec for one parameter leaf (path = '/'-joined keys).

    2D layout: tensor-parallel dim over "model" + FSDP dim over "data"
    (weights are ZeRO-3-style gathered per layer; optimizer state inherits
    the same specs).  `fsdp=None` disables the data-axis dimension (small
    models / pure-TP serving).
    """
    name = path.split("/")[-1]
    # Quantized leaves ("m", "i_packed", "i_blk", packed serving words)
    # inherit the spec of their parent weight via the SAME rules keyed on
    # the parent name.
    parent = path.split("/")[-2] if "/" in path else ""
    if name in ("m", "i_packed", "i_blk", "w_packed"):
        name = parent
    elif name in ("scale", "b") or leaf.ndim <= 1:
        return P()
    nd = leaf.ndim
    in_groups = path.startswith("groups/")
    H, KV, d = cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    F = fsdp  # alias

    def with_stack(spec: P) -> P:
        if in_groups and nd == len(spec) + 1:
            return P(None, *spec)
        return spec

    if name == "embed":
        # vocab over "model" only: FSDP-sharding d makes the token gather
        # all-gather the ENTIRE table (5.6 GB f32 for gemma3) every step.
        return P("model", None)
    if name == "lm_head":
        # NO FSDP on d_in: a data-sharded contraction dim against batch-
        # sharded activations makes GSPMD emit partial-sum logit
        # all-reduces (10 GB/layer observed); vocab sharding alone keeps
        # the largest lm_head at ~176 MB/device.
        return P(None, "model")
    if name == "patch_proj":
        return P(F, "model")
    if name == "wq":
        return with_stack(P(F, "model") if _div(H, tp) else P(F, None))
    if name in ("wk", "wv"):
        return with_stack(P(F, "model") if _div(KV, tp) else P(F, None))
    if name == "wo":
        return with_stack(P("model", F) if _div(H, tp) else P(None, F))
    if name in ("w_gate", "w_up", "w_down"):
        is_expert = cfg.n_experts and nd - (1 if in_groups else 0) == 3
        if is_expert:
            if _div(cfg.n_experts, tp):   # true EP (qwen3-moe)
                return with_stack(P("model", F, None))
            # few big experts (mixtral): TP over d_ff + FSDP over d
            if name == "w_down":
                return with_stack(P(None, "model", F))
            return with_stack(P(None, F, "model"))
        if name == "w_down":
            return with_stack(P("model", F))
        return with_stack(P(F, "model"))
    if name == "w_router":
        return with_stack(P(F, None))
    if name == "w_in":   # whisper gelu mlp in
        return with_stack(P(F, "model"))
    if name == "w_out":  # whisper mlp out / mamba out-proj
        return with_stack(P("model", F))
    # Mamba2: d_inner over "model" (heads divide), d over FSDP
    if name in ("w_z", "w_x"):
        return with_stack(
            P(F, "model") if _div(cfg.ssm_nheads, tp) else P(F, None))
    if name in ("w_bc", "w_dt"):
        return with_stack(P(F, None))
    if name == "conv_w":
        return with_stack(P(None, None))
    # RWKV6 (2560 -> 40 heads, not divisible by 16: TP replicated, FSDP
    # still shards the d_in dim so params/optimizer fit)
    if name in ("w_r", "w_k", "w_v", "w_g", "w_o"):
        rh = d // 64
        return with_stack(P(F, "model") if _div(rh, tp) else P(F, None))
    if name == "w_ck":
        return with_stack(P(F, "model"))
    if name == "w_cv":
        return with_stack(P("model", F))
    if name == "w_cr":
        return with_stack(P(F, None))
    if name in ("w_dec_a", "w_dec_b"):
        return with_stack(P(F, None))
    # everything else (norms, biases, scalars): replicated
    return P()


def param_shardings(params, cfg: ModelConfig, mesh: Mesh,
                    fsdp: bool = True):
    """NamedSharding tree matching the params tree.

    fsdp=True shards the non-TP weight dim over "data" (ZeRO-3); disable
    for small models where replication is cheaper than the gathers.
    Sharded dims that do not divide evenly fall back to replicated.
    """
    tp = tp_size(mesh)
    fs = "data" if fsdp else None
    axis_sizes = dict(mesh.shape)

    def fix(spec_names, shape):
        """Drop axis assignments that don't divide the dim evenly."""
        out = []
        for dim, ax in zip(shape, spec_names):
            if ax is None:
                out.append(None)
            else:
                size = (axis_sizes[ax] if isinstance(ax, str)
                        else int(np.prod([axis_sizes[a] for a in ax])))
                out.append(ax if dim % size == 0 else None)
        return out

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}" if path else k)
                    for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v, f"{path}/{i}") for i, v in enumerate(node)]
        spec = _spec_for_path(path, node, cfg, tp, fs)
        names = list(spec) + [None] * (node.ndim - len(spec))
        names = fix(names[: node.ndim], node.shape)
        return NamedSharding(mesh, P(*names))

    def walk_top(node):
        out = {}
        for k, v in node.items():
            if k == "groups":
                out[k] = [walk(g, "groups") for g in v]
            else:
                out[k] = walk(v, k)
        return out

    return walk_top(params)


def batch_shardings(batch, mesh: Mesh):
    """Shard leading batch dim over (pod,)+data (replicate if too small)."""
    ax = batch_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in ax]))

    def leaf(x):
        first = ax if x.shape and x.shape[0] % n == 0 else None
        return NamedSharding(mesh, P(first, *([None] * (max(x.ndim, 1) - 1))))

    return jax.tree_util.tree_map(leaf, batch)


def cache_shardings(caches, cfg: ModelConfig, mesh: Mesh,
                    seq_axes=None):
    """Decode-cache sharding: (group-stack, B, S, KV, dh).

    B over data(+pod) when divisible.  KV heads over "model" when
    divisible; for cells whose cache would blow past HBM, `seq_axes`
    shards the SEQUENCE dim instead (e.g. ("model",) or ("data","model")
    for batch-1 long-context decode) — GSPMD then emits the distributed
    flash-decode combine for the masked softmax.
    """
    tp = tp_size(mesh)
    ax = batch_axes(mesh)
    axis_sizes = dict(mesh.shape)
    kv_div = _div(cfg.n_kv_heads, tp)

    def leaf_spec(path, x):
        name = path.split("/")[-1]
        nb = int(np.prod([axis_sizes[a] for a in ax]))
        bax = ax if x.ndim > 1 and x.shape[1] % nb == 0 else None
        if name in ("k", "v", "k_m", "k_i", "v_m", "v_i"):
            if seq_axes:
                nseq = int(np.prod([axis_sizes[a] for a in seq_axes]))
                seq = seq_axes if x.shape[2] % nseq == 0 else None
                return P(None, bax, seq, None, None)
            head_ax = "model" if kv_div else None
            return P(None, bax, None, head_ax, None)
        if name in ("k_s", "v_s"):
            seq = seq_axes if seq_axes else None
            return P(None, bax, seq, None, None)
        if name == "len":
            return P(None, bax)
        if name == "s":      # rwkv state (L, B, H, N, N)
            return P(None, bax, None, None, None)
        if name == "h":      # mamba state (L, B, H, P, N)
            hspec = "model" if _div(cfg.ssm_nheads, tp) else None
            return P(None, bax, hspec, None, None)
        if name == "conv":
            return P(None, bax, None, None)
        if name in ("last_tm", "last_cm"):
            return P(None, bax, None)
        return P(*([None] * x.ndim))

    def walk(node, path=""):
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v, f"{path}/{i}") for i, v in enumerate(node)]
        if node is None:
            return None
        spec = leaf_spec(path, node)
        names = list(spec)[: node.ndim]
        names += [None] * (node.ndim - len(names))
        return NamedSharding(mesh, P(*names))

    return walk(caches)
