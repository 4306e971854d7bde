"""Hot-path jaxpr linting: trace serving forwards, scan the IR for hazards.

`jax.make_jaxpr` over the model zoo's prefill / decode entry points (and
over the registered kernel ops) yields the exact primitive graph XLA
will compile — including every `pallas_call` when the trace runs under
`substrate.force_backend("interpret")`, which pins dispatch to the
Pallas path on any host so the lint sees the SERVING graph rather than
the pure-jnp ref oracles (whose full-tensor dequants are correct for an
oracle but would be serving-path findings).

Rules (severities are assigned by `analysis.rules`):

  JX-F64    a float64/complex128 value anywhere in the graph.  Nothing
            in this codebase wants doubles; one leaked `np.float64`
            scalar silently doubles bandwidth on its whole subtree (or
            crashes under jax's default x64-disabled config elsewhere).
  JX-WMAT   a float tensor with EXACTLY the shape of an integer weight
            leaf: the packed/planes weight was fully dequantized into an
            f32 matrix in HBM — the materialization the packed kernel
            path exists to avoid.  Not scanned inside pallas_call
            bodies, whose per-TILE dequants in VMEM are the design.
  JX-VOCAB  a float (vocab, d)-shaped tensor in a DECODE step: an
            O(vocab) dequant/gather per generated token (e.g. an
            embedding table dequantized before `jnp.take`); the packed
            layout gathers rows first, making this O(tokens * d).
  JX-JIT    a public `*_ref` oracle in `kernels.ref` that is not
            jit-wrapped: eager per-call dispatch cascades (the PR-2
            decode regression) — checked structurally, no trace needed.
  JX-SHGATH inside a shard_map body, an integer `all_gather` (packed
            weight words reassembled across the tensor axis) followed by
            a float tensor of exactly the gathered shape: the full
            UNSHARDED weight was dequantized on every device after the
            gather — sharding moved the bytes but bought no memory.
            The column/ring modes in `parallel.shard_ops` never do this
            (outputs resp. per-chunk tiles travel, not the whole
            weight); the `gather` baseline mode is the pattern flagged.
  JX-BWDMAT in a BACKWARD trace over the packed datapath, a float
            tensor of exactly a packed weight's shape produced by
            anything other than `dot_general` or a `pallas_call`: the
            VJP fell back to dequantize-then-autodiff, materializing the
            f32 weight plane the custom backward kernels
            (`kernels.vp_bwd_matmul`) exist to avoid.  dL/dW is
            legitimately weight-shaped, hence the producer exemptions
            (a contraction or a kernel launch stages tiles only).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Iterator, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr

# `*_ref` callables that are deliberately NOT jit-wrapped (mask builders
# and helpers called at trace time inside an enclosing jit, where a
# nested jit would only add dispatch overhead).
REF_JIT_EXCEPTIONS = frozenset({
    "tile_activity",
    "cspade_tile_masks",
    "cspade_tile_masks_batched",
    "_decode_attention_core",
})

# Below this element count a full-shape float match is ignored: tiny
# tensors (norm gains, scales) can coincide with tiny weight shapes.
_WMAT_MIN_ELEMS = 2048
_VOCAB_MIN = 32


def _subjaxprs(eqn) -> Iterator[Tuple[Any, bool]]:
    """Yield (jaxpr, entered_pallas) for every sub-jaxpr riding an eqn's
    params (scan/cond bodies, custom_vjp calls, pallas kernel bodies)."""
    is_pallas = eqn.primitive.name == "pallas_call"
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr, is_pallas
            elif hasattr(item, "eqns") and hasattr(item, "outvars"):
                yield item, is_pallas


def iter_eqns(jaxpr, in_pallas: bool = False) -> Iterator[Tuple[Any, bool]]:
    """Depth-first walk over every eqn in a (closed) jaxpr, tagging
    whether the eqn sits inside a pallas_call kernel body."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, in_pallas
        for sub, entered in _subjaxprs(eqn):
            yield from iter_eqns(sub, in_pallas or entered)


def _finding(rule: str, where: str, detail: str) -> Dict[str, str]:
    return {"rule": rule, "where": where, "detail": detail}


def int_weight_shapes(params) -> Set[Tuple[int, ...]]:
    """Shapes of quantized weight storage: every integer-dtype leaf with
    >= 2 dims, plus the per-layer shapes of stacked leaves (scanned
    groups see one layer's slice inside the scan body)."""
    shapes: Set[Tuple[int, ...]] = set()
    for leaf in jax.tree_util.tree_leaves(params):
        if not hasattr(leaf, "dtype") or not hasattr(leaf, "shape"):
            continue
        if not jnp.issubdtype(leaf.dtype, jnp.integer):
            continue
        if leaf.ndim < 2:
            continue
        shapes.add(tuple(leaf.shape))
        for lead in range(1, leaf.ndim - 1):
            shapes.add(tuple(leaf.shape[lead:]))
    return shapes


def lint_traced(
    jaxpr,
    weight_shapes: Sequence[Tuple[int, ...]] = (),
    vocab: Optional[int] = None,
    decode: bool = False,
    where: str = "",
) -> List[Dict[str, str]]:
    """Scan one traced graph for JX-F64 / JX-WMAT / JX-VOCAB."""
    findings: List[Dict[str, str]] = []
    wshapes = {tuple(s) for s in weight_shapes
               if int(np.prod(s)) >= _WMAT_MIN_ELEMS}
    seen: Set[Tuple[str, Tuple[int, ...]]] = set()
    for eqn, in_pallas in iter_eqns(jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            shape = tuple(getattr(aval, "shape", ()))
            if dtype is None:
                continue
            if dtype in (jnp.float64, jnp.complex128):
                key = ("f64", shape)
                if key not in seen:
                    seen.add(key)
                    findings.append(_finding(
                        "JX-F64", where,
                        f"{eqn.primitive.name} produces {dtype} {shape}"))
            if in_pallas or not jnp.issubdtype(dtype, jnp.floating):
                continue
            if shape in wshapes:
                key = ("wmat", shape)
                if key not in seen:
                    seen.add(key)
                    findings.append(_finding(
                        "JX-WMAT", where,
                        f"{eqn.primitive.name} materializes a float "
                        f"{shape} tensor matching a quantized weight "
                        f"leaf — full-weight dequant in HBM"))
            if (decode and vocab and vocab >= _VOCAB_MIN
                    and len(shape) >= 2 and shape[0] == vocab
                    and int(np.prod(shape[1:])) > 1):
                key = ("vocab", shape)
                if key not in seen:
                    seen.add(key)
                    findings.append(_finding(
                        "JX-VOCAB", where,
                        f"{eqn.primitive.name} produces a float {shape} "
                        f"tensor spanning the whole vocab in a decode "
                        f"step — O(vocab) work per generated token"))
    return findings


def _shard_map_bodies(jaxpr) -> Iterator[Any]:
    """Yield the body jaxpr of every shard_map eqn, at any nesting depth
    outside of one (shard_map does not nest in this codebase)."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        if "shard_map" in eqn.primitive.name:
            for sub, _ in _subjaxprs(eqn):
                yield sub
        else:
            for sub, _ in _subjaxprs(eqn):
                yield from _shard_map_bodies(sub)


def lint_sharded_traced(jaxpr, where: str = "") -> List[Dict[str, str]]:
    """JX-SHGATH over every shard_map body in a traced graph.

    Structural, so the verdict is mesh-size independent: an integer
    `all_gather` outvar (>= `_WMAT_MIN_ELEMS` elements) records its
    shape; any LATER float outvar of the identical shape in the same
    body is the full gathered weight dequantized in HBM.  Float matches
    inside pallas_call bodies are ignored (per-tile VMEM dequants are
    the design), so trace on the ref backend, where the full dequant is
    a visible jnp op.
    """
    findings: List[Dict[str, str]] = []
    seen: Set[Tuple[str, Tuple[int, ...]]] = set()
    for body in _shard_map_bodies(jaxpr):
        gathered: Set[Tuple[int, ...]] = set()
        for eqn, in_pallas in iter_eqns(body):
            is_gather = eqn.primitive.name == "all_gather"
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                dtype = getattr(aval, "dtype", None)
                shape = tuple(getattr(aval, "shape", ()))
                if dtype is None or int(np.prod(shape)) < _WMAT_MIN_ELEMS:
                    continue
                if is_gather and jnp.issubdtype(dtype, jnp.integer):
                    gathered.add(shape)
                elif (not in_pallas and shape in gathered
                      and jnp.issubdtype(dtype, jnp.floating)):
                    key = (where, shape)
                    if key not in seen:
                        seen.add(key)
                        findings.append(_finding(
                            "JX-SHGATH", where,
                            f"{eqn.primitive.name} materializes a float "
                            f"{shape} tensor matching an all-gathered "
                            f"integer shape inside a shard_map body — "
                            f"the full unsharded weight was dequantized "
                            f"on every device after the gather"))
    return findings


# Producers allowed to emit weight-shaped floats in a backward trace:
# a contraction IS the weight gradient, and a kernel launch's HBM output
# (dL/dW from `vp_matmul_dw_pallas`) stages tiles on chip only.  The
# call-like wrappers merely FORWARD a sub-jaxpr's result — `iter_eqns`
# descends into their bodies, so the true producer inside is still
# linted (a jitted dequant chain is flagged on its elementwise eqns; a
# jitted backward kernel is exempt on its pallas_call).
_BWD_LEGIT_PRODUCERS = frozenset({
    "dot_general", "pallas_call",
    "pjit", "closed_call", "core_call", "remat", "remat2",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "custom_jvp_call",
})


def lint_bwd_traced(
    jaxpr,
    weight_shapes: Sequence[Tuple[int, ...]] = (),
    where: str = "",
) -> List[Dict[str, str]]:
    """JX-BWDMAT over one BACKWARD trace (a `jax.grad` jaxpr).

    Any float outvar with exactly a packed-weight shape whose producer
    is not in `_BWD_LEGIT_PRODUCERS` means the VJP dequantized the full
    weight plane (autodiff through `dequant_words`) instead of running
    the packed backward kernel.  Eqns inside pallas_call bodies are
    exempt — on the interpret backend tiles clamp to the full (small)
    test shape, and per-tile VMEM dequants are the design.
    """
    findings: List[Dict[str, str]] = []
    wshapes = {tuple(s) for s in weight_shapes
               if int(np.prod(s)) >= _WMAT_MIN_ELEMS}
    seen: Set[Tuple[str, Tuple[int, ...]]] = set()
    for eqn, in_pallas in iter_eqns(jaxpr):
        if in_pallas or eqn.primitive.name in _BWD_LEGIT_PRODUCERS:
            continue
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            shape = tuple(getattr(aval, "shape", ()))
            if dtype is None or shape not in wshapes:
                continue
            if not jnp.issubdtype(dtype, jnp.floating):
                continue
            key = (where, shape)
            if key not in seen:
                seen.add(key)
                findings.append(_finding(
                    "JX-BWDMAT", where,
                    f"{eqn.primitive.name} materializes a float {shape} "
                    f"tensor matching a packed weight in a backward "
                    f"trace — the VJP dequantized the full weight plane "
                    f"instead of running the packed backward kernel"))
    return findings


def lint_ref_jit() -> List[Dict[str, str]]:
    """JX-JIT: every public `*_ref` oracle must be jit-wrapped."""
    from repro.kernels import ref

    findings = []
    for name in dir(ref):
        if not name.endswith("_ref") or name in REF_JIT_EXCEPTIONS:
            continue
        fn = getattr(ref, name)
        if not callable(fn):
            continue
        # jax.jit wrappers expose .lower / .trace; plain functions don't.
        if not hasattr(fn, "lower"):
            findings.append(_finding(
                "JX-JIT", f"kernels/ref.py::{name}",
                "ref oracle is not jit-wrapped: every call re-dispatches "
                "its op cascade eagerly (the PR-2 decode regression "
                "pattern)"))
    return findings


# ---------------------------------------------------------------------------
# Model-zoo tracing
# ---------------------------------------------------------------------------

def model_traces(cfg, layout: str = "packed"):
    """Trace one model config's serving entry points.

    Returns a list of (name, jaxpr, decode?) plus the quantized-weight
    shape set.  Params are built and quantized on the default backend
    (cheap ref math); the TRACES run under
    `force_backend("interpret")` so the graphs contain the pallas_call
    launches of the serving path.  Tracing never executes the kernels.
    """
    from repro.kernels import substrate
    from repro.models import model as M

    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    qparams = M.quantize_params(params, cfg, layout=layout)
    caches = M.init_cache(cfg, B=1, max_len=32)
    tokens = jnp.zeros((1, 8), jnp.int32)
    token = jnp.zeros((1, 1), jnp.int32)
    wshapes = int_weight_shapes(qparams)

    extra = None
    if cfg.family == "encdec":
        enc = jnp.zeros((1, 8, cfg.d_model), M.model_dtype(cfg))
        extra = M._cross_kv(qparams, enc, cfg)

    traces = []
    with substrate.force_backend("interpret"):
        prefill_jaxpr = jax.make_jaxpr(
            functools.partial(
                lambda p, t, c, x: M.prefill(p, t, c, cfg, patches=x)))(
            qparams, tokens, caches, extra)
        traces.append(("prefill", prefill_jaxpr, False))
        decode_jaxpr = jax.make_jaxpr(
            lambda p, t, c, x: M.decode_step(p, t, c, cfg, cross_kv=x))(
            qparams, token, caches, extra)
        traces.append(("decode", decode_jaxpr, True))
    return traces, wshapes


def lint_model(cfg, name: str = "", layout: str = "packed"):
    """All jaxpr rules over one model config's prefill + decode."""
    traces, wshapes = model_traces(cfg, layout=layout)
    findings: List[Dict[str, str]] = []
    for stage, jaxpr, decode in traces:
        findings.extend(lint_traced(
            jaxpr, weight_shapes=wshapes, vocab=cfg.vocab,
            decode=decode, where=f"{name or cfg.family}:{stage}"))
    return findings


def lint_kernel_ops(pairs) -> List[Dict[str, str]]:
    """JX-F64 over the registered kernel ops' traced graphs.

    `pairs`: [(name, callable-of-no-args)] where the callable runs one
    op at a representative shape; the trace runs on the interpret
    backend so the pallas_call launches are in-graph.
    """
    from repro.kernels import substrate

    findings: List[Dict[str, str]] = []
    with substrate.force_backend("interpret"):
        for name, thunk in pairs:
            jaxpr = jax.make_jaxpr(thunk)()
            findings.extend(lint_traced(jaxpr, where=f"ops.{name}"))
    return findings
