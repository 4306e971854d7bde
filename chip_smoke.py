"""Smoke run of VP serving on a TPU: qwen3-0.6b at its published widths.

    python chip_smoke.py               # one chip: phases (a) to (d)
    python chip_smoke.py --four-chips  # four chips: tensor-parallel serving

One process runs every phase, in this order:

  (a) serving    8 requests (prompts of 128 and 512 tokens, budgets of
                 32 to 64 new tokens) through `ServingEngine` with packed
                 VP weights and a packed VP KV cache, greedy, bf16
                 activations; every request must end `ok` with its whole
                 budget.
  (b) reference  prefill logits of two prompts and 4 decode steps on the
                 static path with the Pallas kernels, against the same
                 packed parameters run through the jnp reference oracles
                 in float32 at the highest matmul precision.  The largest
                 logit error must stay inside `logit_bound`, and greedy
                 tokens must agree wherever the reference's top-2 margin
                 exceeds that bound.
  (c) kernels    the engine's compiled prefill and decode steps must
                 contain Pallas kernels (`tpu_custom_call`), and no op
                 outside (b) may have resolved to a jnp oracle.
  (d) equalizer  the paper's Table-I B-VP design (B=64 antennas, U=8
                 users) equalizes 1024 channel realizations on the fused
                 batched kernel, one product a lane, against the
                 fake-quant model within rtol 2e-4.

`--four-chips` runs only this: the same model served on a (data, model) =
(1, 4) mesh, compared with the one-device engine on the same prompts.

Weights and prompts are random, drawn from `--seed`.  Every line before
the last starts with `[smoke]`: the times, sizes and errors of one smoke
run, not benchmark metrics.  The last line is one JSON object naming the
device.  A failed check raises, so the JSON line is never printed.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import math
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen3-0.6b"
PAGE_SIZE = 16
PROMPT_LENS = (128, 512)
GENS = (64, 32, 48, 40, 56, 36, 60, 44)     # ragged budgets, 32..64
CAPACITY = max(PROMPT_LENS) + max(GENS)      # 576 = 36 pages of 16
REF_DECODE_STEPS = 4
MVM_REALIZATIONS = 1024

# bf16 keeps 8 significant bits: unit roundoff u = 2**-9.
BF16_U = 2.0 ** -9
# Tensors rounded to bf16 per layer on the way into the residual stream:
# two norms, q/k/v, rope, attention out, o-proj, gate, up, the gated
# product, down-proj, and the two residual adds.
ROUNDINGS_PER_LAYER = 14
# A Gaussian's maximum over ~10 rows x 151936 logits sits near 5.3 sigma.
MAX_OVER_LOGITS = 6.0


class SmokeFailure(RuntimeError):
    """A smoke check failed."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def logit_bound(ref_logits, n_layers: int) -> float:
    """Largest |kernel - reference| logit difference the run accepts.

    The kernel path rounds activations to bf16 at every op boundary and
    the reference keeps float32; the packed weights and the VP cache
    formats are the same on both sides, and the VP significands are exact
    in bf16, so the rounding of activations is the only source of
    difference.  Each layer rounds `ROUNDINGS_PER_LAYER` tensors that
    feed the residual stream; independent roundings of relative size u
    add up like a random walk, so the final hidden state, and each logit
    with it, carries a relative error of about sqrt(14 L) u of the
    logits' RMS.  `MAX_OVER_LOGITS` covers the maximum over all logits.
    """
    import numpy as np

    rms = float(np.sqrt(np.mean(np.square(ref_logits))))
    return MAX_OVER_LOGITS * math.sqrt(ROUNDINGS_PER_LAYER * n_layers) \
        * BF16_U * rms


def top2_margin(rows):
    import numpy as np

    part = np.partition(rows, -2, axis=-1)
    return part[..., -1] - part[..., -2]


class CompileMeter:
    """Seconds JAX spent in backend compiles, and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.cache_hits


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter):
    t0 = time.perf_counter()
    c0, h0 = meter.snapshot()
    yield
    c, h = meter.snapshot()
    log(f"phase {name}: wall {time.perf_counter() - t0:.3f} s, of which "
        f"backend compile {c - c0:.3f} s ({h - h0} persistent-cache hits)")


def build_model(seed: int):
    """qwen3-0.6b with packed VP weights, a packed VP KV cache and bf16
    activations; parameters initialised from `seed`, quantized on the
    device in one program."""
    import jax

    from repro.configs import registry
    from repro.configs.base import QuantConfig
    from repro.models import init_params, quantize_params

    cfg = registry.get_config(
        ARCH, QuantConfig(mode="vp", quantize_kv_cache=True))
    make = jax.jit(lambda k: quantize_params(init_params(k, cfg), cfg,
                                             layout="packed"))
    params = jax.block_until_ready(make(jax.random.PRNGKey(seed)))
    return cfg, params


def make_traffic(cfg, seed: int, n: int):
    """`n` requests alternating 128- and 512-token prompts, with the
    ragged budgets of `GENS`."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab, PROMPT_LENS[i % 2]).tolist(),
             GENS[i % len(GENS)]) for i in range(n)]


def make_engine(params, cfg, max_slots: int, mesh=None):
    """The engine `launch/serve.py --engine` builds, keeping every
    request's host logits for comparison."""
    import numpy as np

    from repro.serving import ServingEngine, WallClock

    class RecordingEngine(ServingEngine):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.logits = collections.defaultdict(list)

        def _screen(self, phase, run, logits):
            arr = np.asarray(logits)
            self.logits[run.req.rid].append(arr.reshape(-1, arr.shape[-1]))
            return super()._screen(phase, run, logits)

    return RecordingEngine(
        params, cfg, max_slots=max_slots, capacity=CAPACITY,
        page_size=PAGE_SIZE, temperature=0.0, clock=WallClock(),
        check_finite=True, on_nonfinite="raise", degrade=False, mesh=mesh)


def serve(engine, traffic):
    """Serve `traffic`; every request must end `ok` with its budget."""
    for prompt, gen in traffic:
        engine.submit(prompt, gen)
    t0 = time.perf_counter()
    recs = engine.run()
    wall = time.perf_counter() - t0
    check(len(recs) == len(traffic),
          f"{len(recs)} records for {len(traffic)} requests")
    for rec, (prompt, gen) in zip(recs, traffic):
        check(rec["outcome"] == "ok",
              f"request {rec['rid']} ended {rec['outcome']!r}")
        check(len(rec["tokens"]) == gen,
              f"request {rec['rid']} emitted {len(rec['tokens'])} of "
              f"{gen} tokens")
    return recs, wall


def phase_serving(cfg, params, traffic):
    engine = make_engine(params, cfg, max_slots=len(traffic))
    recs, wall = serve(engine, traffic)
    n_tok = sum(len(r["tokens"]) for r in recs)
    log(f"served {len(recs)} requests, all ok, {n_tok} tokens "
        f"(prompt lengths {sorted({len(p) for p, _ in traffic})}) in "
        f"{wall:.3f} s wall, compiles included")
    return engine


def phase_reference(cfg, params, prompts):
    """Kernel path (bf16) vs the jnp oracles (float32, highest precision)
    on the static prefill/decode path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import substrate
    from repro.models import decode_step, init_cache, prefill

    tokens = jnp.asarray(prompts, jnp.int32)
    B = tokens.shape[0]
    cfg_ref = dataclasses.replace(cfg, dtype="float32")

    def steps(c):
        return (jax.jit(lambda p, t, k: prefill(p, t, k, c)),
                jax.jit(lambda p, t, k: decode_step(p, t, k, c)))

    kern_pre, kern_dec = steps(cfg)
    kern_cache = init_cache(cfg, B, CAPACITY)
    with substrate.force_backend("ref"), \
            jax.default_matmul_precision("highest"):
        ref_pre, ref_dec = steps(cfg_ref)
        ref_cache = init_cache(cfg_ref, B, CAPACITY)
        ref_logits, ref_cache = ref_pre(params, tokens, ref_cache)
    kern_logits, kern_cache = kern_pre(params, tokens, kern_cache)
    ref_rows, kern_rows = [np.asarray(ref_logits)], [np.asarray(kern_logits)]
    for _ in range(REF_DECODE_STEPS):
        # Both sides are fed the reference's greedy token.
        tok = jnp.asarray(ref_rows[-1].argmax(-1)[:, None], jnp.int32)
        with substrate.force_backend("ref"), \
                jax.default_matmul_precision("highest"):
            ref_logits, ref_cache = ref_dec(params, tok, ref_cache)
        kern_logits, kern_cache = kern_dec(params, tok, kern_cache)
        ref_rows.append(np.asarray(ref_logits))
        kern_rows.append(np.asarray(kern_logits))
    ref_all = np.concatenate(ref_rows).astype(np.float64)
    kern_all = np.concatenate(kern_rows).astype(np.float64)
    check(bool(np.isfinite(kern_all).all()), "non-finite kernel logits")
    err = np.abs(kern_all - ref_all)
    max_abs = float(err.max())
    max_rel = max_abs / float(np.abs(ref_all).max())
    bound = logit_bound(ref_all, cfg.n_layers)
    log(f"reference: {ref_all.shape[0]} logit rows ({B} prompts x "
        f"(prefill + {REF_DECODE_STEPS} decode steps)), max |err| "
        f"{max_abs:.6g}, max |err| / max |ref| {max_rel:.6g}, bound "
        f"{bound:.6g} (= {MAX_OVER_LOGITS} x sqrt({ROUNDINGS_PER_LAYER} x "
        f"{cfg.n_layers}) x 2^-9 x RMS of the reference logits)")
    check(max_abs <= bound,
          f"kernel logits differ from the reference by {max_abs:.6g} > "
          f"bound {bound:.6g}")
    decided = top2_margin(ref_all) > bound
    agree = kern_all.argmax(-1) == ref_all.argmax(-1)
    log(f"reference: greedy top-1 agrees at {int(agree[decided].sum())} of "
        f"{int(decided.sum())} rows whose top-2 margin exceeds the bound "
        f"({int(agree.sum())} of {agree.size} rows overall)")
    check(bool(agree[decided].all()),
          "greedy top-1 differs where the reference margin exceeds the bound")


def phase_kernels(engine):
    """Compile the engine's jitted prefill and decode steps for the
    argument shapes they ran with; count the Pallas kernels in each."""
    counts = {name: text.count("tpu_custom_call")
              for name, text in engine.runner.compiled_text().items()}
    for name, n in counts.items():
        log(f"kernels: engine {name}: {n} tpu_custom_call")
    check(any(k.startswith("prefill") for k in counts)
          and any(k.startswith("decode") for k in counts),
          f"engine compiled steps missing: {sorted(counts)}")
    for name, n in counts.items():
        check(n > 0, f"engine {name} holds no Pallas kernel")


def phase_equalizer(seed: int):
    """Table-I B-VP equalization on the fused batched kernel vs the
    fake-quant model (`equalize_quantized`), rtol 2e-4."""
    import jax
    import numpy as np

    from repro import tracing
    from repro.mimo import ChannelConfig, table1_specs
    from repro.mimo.equalizer import equalize_quantized
    from repro.mimo.mvm_engine import equalize_vp_kernel
    from repro.mimo.sim import calibrate_specs, make_ensemble

    ch = ChannelConfig()
    ens = make_ensemble(jax.random.PRNGKey(seed), ch, MVM_REALIZATIONS, 10.0)
    spec = {s.name: s for s in calibrate_specs(table1_specs(), ens)}["B-VP"]
    tracing.enable()
    try:
        s_kernel = np.asarray(jax.block_until_ready(equalize_vp_kernel(
            spec, ens.w_beam, ens.y_beam, fused=True)))
    finally:
        tracing.disable()
    lanes = tracing.drain()[1].get("kernels.vp_quant_matmul_batched.lanes", 0)
    check(lanes >= 1, "the (2U, B) x (B, 2) products did not take the "
                      "batched kernel's lane body")
    with jax.default_matmul_precision("highest"):
        s_model = np.asarray(equalize_quantized(spec, ens.w_beam, ens.y_beam))
    err = np.abs(s_kernel - s_model)
    worst = float((err / (2e-4 + 2e-4 * np.abs(s_model))).max())
    log(f"equalizer: B={ch.B} U={ch.U} n={MVM_REALIZATIONS}, lane-body "
        f"lowerings {lanes}, max |err| "
        f"{float(err.max()):.6g}, worst err / (2e-4 + 2e-4 |model|) "
        f"{worst:.6g} (must be <= 1)")
    check(worst <= 1.0, "equalizer kernel outside rtol 2e-4 of the model")


def phase_four_chips(cfg, params, seed: int):
    """Tensor-parallel serving on a (1, 4) mesh vs the one-device engine
    on the same prompts."""
    import jax
    import numpy as np

    from repro.launch.mesh import elastic_mesh

    mesh = elastic_mesh(1, 1, 4)
    traffic = make_traffic(cfg, seed + 1, 4)
    one = make_engine(params, cfg, max_slots=len(traffic))
    serve(one, traffic)
    tp = make_engine(params, cfg, max_slots=len(traffic), mesh=mesh)
    recs, wall = serve(tp, traffic)
    log(f"four chips: served {len(recs)} requests on mesh "
        f"{dict(mesh.shape)}, all ok, in {wall:.3f} s wall, compiles "
        "included")
    devices = set(mesh.devices.flat)
    for what, tree in (("params", tp.params), ("pages", tp.kv.pools)):
        leaves = jax.tree_util.tree_leaves(tree)
        spread = {len(x.sharding.device_set) for x in leaves}
        log(f"four chips: {what}: {len(leaves)} arrays, each on "
            f"{sorted(spread)} devices")
        check(all(x.sharding.device_set == devices for x in leaves),
              f"four chips: {what} are not placed on all four devices")
    worst, bound_all, compared, split = 0.0, [], 0, 0
    for rid, (_, gen) in enumerate(traffic):
        ref = np.concatenate(one.logits[rid]).astype(np.float64)
        got = np.concatenate(tp.logits[rid]).astype(np.float64)
        bound = logit_bound(ref, cfg.n_layers)
        bound_all.append(bound)
        same = ref.argmax(-1) == got.argmax(-1)
        # Greedy tokens feed the next step, so rows stay comparable up to
        # and including the first row whose tokens differ.
        n = gen if same.all() else int(np.argmin(same)) + 1
        err = float(np.abs(got[:n] - ref[:n]).max())
        worst = max(worst, err / bound)
        compared += n
        check(err <= bound, f"four chips: request {rid} logits differ by "
              f"{err:.6g} > bound {bound:.6g}")
        if not same.all():
            split += 1
            margin = float(top2_margin(ref[n - 1]))
            check(margin <= bound,
                  f"four chips: request {rid} greedy token differs at step "
                  f"{n - 1} where the one-device margin {margin:.6g} "
                  f"exceeds the bound {bound:.6g}")
    log(f"four chips: {compared} logit rows compared with the one-device "
        f"engine, worst err / bound {worst:.6g} (bounds "
        f"{min(bound_all):.6g}..{max(bound_all):.6g}), {split} requests "
        "split at a near-tie")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only tensor-parallel serving on four chips, "
                         "against the one-device engine")
    args = ap.parse_args(argv)

    # Tilings come from committed code: an empty autotune cache inside
    # the checkout, never one left in a home directory.
    tune_cache = ROOT / ".autotune" / "chip_smoke.json"
    tune_cache.parent.mkdir(exist_ok=True)
    tune_cache.unlink(missing_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(tune_cache)

    import jax

    from repro.kernels import substrate
    from repro.launch import runtime

    dev = runtime.require_tpu(allow_cpu_if_requested=False)
    n_dev = len(jax.devices())
    want = 4 if args.four_chips else 1
    check(n_dev >= want, f"{want} chips needed, JAX sees {n_dev}")
    cache_dir = runtime.enable_compile_cache()
    meter = CompileMeter()
    log(f"device {dev.device_kind} x {n_dev}, jax {jax.__version__}, "
        f"compile cache {cache_dir}, autotune cache {tune_cache} (empty)")

    with phase("setup (init + quantize)", meter):
        cfg, params = build_model(args.seed)
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads x {cfg.head_dim}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}; packed weights "
        f"{sum(x.nbytes for x in jax.tree_util.tree_leaves(params)):,} B")

    if args.four_chips:
        with phase("four-chip serving", meter):
            phase_four_chips(cfg, params, args.seed)
    else:
        traffic = make_traffic(cfg, args.seed, len(GENS))
        with phase("(a) serving", meter):
            engine = phase_serving(cfg, params, traffic)
        ref_before = substrate.resolved["ref"]
        with phase("(b) reference", meter):
            short = [p for p, _ in traffic if len(p) == PROMPT_LENS[0]]
            phase_reference(cfg, params, short[:2])
        ref_in_b = substrate.resolved["ref"] - ref_before
        with phase("(c) kernels", meter):
            phase_kernels(engine)
        with phase("(d) equalizer", meter):
            phase_equalizer(args.seed)
        outside = substrate.resolved["ref"] - ref_in_b
        log(f"backend resolutions: {dict(substrate.resolved)} "
            f"({ref_in_b} ref inside (b), {outside} outside)")
        check(outside == 0,
              f"{outside} ops resolved to the jnp oracles outside (b)")
    stats = dev.memory_stats() or {}
    log(f"device peak bytes in use {stats.get('peak_bytes_in_use')}, "
        f"compile total {meter.seconds:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
