"""Serving CLI: static batch driver + continuous-batching paged engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --batch 4 --prompt-len 32 --gen 32 --quant vp

With --quant vp the weights are served as PACKED VP words (sign +
significand + exponent index in one int8/int16 per element,
`core.packing`), and every weight matmul routes through the Pallas
`vp_dequant_matmul` kernel — the packed words are consumed directly
in-tile, never materializing an f32 weight matrix in HBM.  This is the
paper's technique as a serving feature.

  --engine          serve via the continuous-batching PAGED engine
                    (`repro.serving`): fixed-size pages of packed VP
                    words + per-request block tables, FIFO admission
                    under the page budget, interleaved prefill/decode.
                    The static path (default) is retained as the parity
                    oracle — on the ref backend both emit bit-identical
                    tokens.
  --layout planes   legacy two-plane jnp-dequant serving (the golden
                    baseline the parity suite pins the kernel against)
  --kv-quant        additionally VP-quantizes the KV cache into PACKED
                    words consumed by the `vp_decode_attention` kernel
  --kv-layout planes  legacy two-plane KV cache (golden baseline)
  --tune-decode     run the M=1..B skinny-decode autotune profile over
                    the model's weight panels (and, with --kv-quant, the
                    decode-attention cache geometries) before serving
  --json F          write a serving report (tokens/sec, latency) to F
  --smoke           reduced config; also CHECKS finite logits end to end
                    (a real raise, not an assert — survives `python -O`)

It runs on a TPU and raises if JAX finds none, unless the CPU was asked
for with JAX_PLATFORMS=cpu (tests, rehearsals: kernels then run on their
jnp refs).  Compiles persist in `launch.runtime.enable_compile_cache`'s
directory.

All wall-clock numbers come from `time.perf_counter()` — never
`time.time()`, whose NTP steps skewed the committed tokens/sec reports —
and token sampling happens INSIDE the jitted decode step, so "decode
time" measures the model, not a host-side Python sampling loop.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import QuantConfig
from repro.launch import runtime
from repro.models import (
    init_params, init_cache, prefill, decode_step, quantize_params,
)
from repro.models.layers import canonical_formats
from repro.serving.profile import quantized_bytes, tune_decode_profile


def _require_finite(logits, what: str) -> None:
    """Raise if any logit is NaN/inf.

    This is a runtime serving check on real model output, not an
    internal invariant — it must fire under `python -O` too, where
    `assert` statements are stripped, so it raises explicitly.
    """
    if not bool(jnp.isfinite(logits).all()):
        raise FloatingPointError(f"non-finite {what} logits")


def _percentile(xs, p: float) -> float:
    """Nearest-rank percentile of a small latency list."""
    ys = sorted(xs)
    i = min(len(ys) - 1, max(0, int(round(p / 100 * (len(ys) - 1)))))
    return ys[i]


def _ragged_gens(gen: int, n: int):
    """Deterministic ragged generation lengths in [gen/2, gen]."""
    span = max(1, gen // 2)
    return [max(1, gen - (i * 7) % (span + 1)) for i in range(n)]


def _run_engine(args, params, cfg, prompt_key, report):
    """Serve --batch requests through the paged continuous-batching
    engine (deterministic virtual clock charged with measured compute)."""
    from repro.serving import SLO_CLASSES, ServingEngine, VirtualClock

    n_req = args.batch
    gens = _ragged_gens(args.gen, n_req) if args.ragged_gen \
        else [args.gen] * n_req
    if args.arrival_gap > 0:
        arrivals = [i * args.arrival_gap for i in range(n_req)]
    else:
        arrivals = [0.0] * n_req
    ps = args.page_size
    capacity = -(-(args.prompt_len + max(gens)) // ps) * ps
    max_slots = args.max_slots or min(n_req, 4)
    engine = ServingEngine(
        params, cfg, max_slots=max_slots, capacity=capacity, page_size=ps,
        prefill_chunk=args.prefill_chunk, temperature=args.temperature,
        decode_lookahead=args.lookahead,
        clock=VirtualClock(), check_finite=args.smoke,
        hbm_budget_bytes=args.hbm_budget or None,
        policy=args.policy, preempt=args.preempt,
        max_queue=args.max_queue or None,
        on_nonfinite=args.on_nonfinite, degrade=args.degrade)
    slo = SLO_CLASSES[args.slo] if args.slo != "none" else None
    for i in range(n_req):
        prompt = jax.random.randint(
            jax.random.fold_in(prompt_key, i), (args.prompt_len,), 0,
            cfg.vocab)
        engine.submit(
            [int(t) for t in prompt], gens[i], arrivals[i],
            deadline=(arrivals[i] + args.deadline) if args.deadline else None,
            slo=slo)
    recs = engine.run()
    done = [r for r in recs if r["outcome"] in ("ok", "retried", "degraded")]
    total_tokens = sum(len(r["tokens"]) for r in done)
    ends = [r["finish_time"] for r in done if r["finish_time"] is not None]
    makespan = (max(ends) - min(r["arrival_time"] for r in recs)) \
        if ends else 0.0
    lats = [r["finish_time"] - r["arrival_time"] for r in done
            if r["finish_time"] is not None]
    tok_s = total_tokens / max(makespan, 1e-9)
    outcomes = {}
    for r in recs:
        outcomes[r["outcome"]] = outcomes.get(r["outcome"], 0) + 1
    report.update({
        "mode": "engine", "n_requests": n_req, "max_slots": max_slots,
        "page_size": ps, "capacity": capacity,
        "prefill_chunk": args.prefill_chunk,
        "decode_lookahead": args.lookahead,
        "hbm_cache_bytes": engine.kv.hbm_bytes(),
        "total_tokens": total_tokens, "makespan_s": makespan,
        "tokens_per_s": tok_s,
        "p50_latency_s": _percentile(lats, 50) if lats else None,
        "p99_latency_s": _percentile(lats, 99) if lats else None,
        "policy": args.policy, "outcomes": outcomes,
        "slo_met": sum(1 for r in recs if r.get("slo_met")),
        "stats": dict(engine.stats),
    })
    print(f"[engine] {n_req} requests x {max_slots} slots "
          f"(pages of {ps}): {total_tokens} tokens in {makespan:.2f}s "
          f"({tok_s:.1f} tok/s, p50 {report['p50_latency_s']}s, "
          f"p99 {report['p99_latency_s']}s)")
    if set(outcomes) - {"ok"}:
        print(f"[engine] outcomes: {outcomes}")
    print("[sample tokens]", [r["tokens"][:8] for r in recs[:4]])


def _run_static(args, params, cfg, prompt_key, sample_key, report):
    """The original fixed-batch driver: prefill once, decode N steps.
    Kept as the engine's parity oracle and padding-loss baseline."""
    B = args.batch
    prompts = jax.random.randint(
        prompt_key, (B, args.prompt_len), 0, cfg.vocab)
    caches = init_cache(cfg, B, args.prompt_len + args.gen)

    extra = None
    cross_kv = None
    if cfg.family == "vlm":
        extra = jnp.zeros((B, cfg.n_patches, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        from repro.models.model import _encoder_forward, _cross_kv
        frames = jax.random.normal(
            jax.random.fold_in(prompt_key, 1),
            (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
        enc = _encoder_forward(params, frames, cfg)
        cross_kv = _cross_kv(params, enc, cfg)
        extra = cross_kv

    t0 = time.perf_counter()
    logits, caches = prefill(params, prompts, caches, cfg, patches=extra)
    jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0
    report["prefill_s"] = prefill_s
    print(f"[prefill] {B}x{args.prompt_len} in {prefill_s:.2f}s")
    if args.smoke:
        _require_finite(logits, f"prefill ({args.arch}, {args.quant})")

    temperature = args.temperature

    @jax.jit
    def decode(p, t, c, key):
        if cfg.family == "encdec":
            lg, c = decode_step(p, t, c, cfg, cross_kv=cross_kv)
        else:
            lg, c = decode_step(p, t, c, cfg)
        # Sampling INSIDE the jitted step: the decode timer must not
        # include a host round-trip + Python argmax per token.
        if temperature > 0:
            nxt = jax.random.categorical(key, lg / temperature)
        else:
            nxt = jnp.argmax(lg, -1)
        return nxt.astype(jnp.int32)[:, None], lg, c

    out_tokens = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    t0 = time.perf_counter()
    for i in range(args.gen):
        out_tokens.append(tok)
        tok, logits, caches = decode(
            params, tok, caches, jax.random.fold_in(sample_key, i))
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0
    if args.smoke:
        _require_finite(logits, f"decode ({args.arch}, {args.quant})")
    gen = jnp.concatenate(out_tokens, axis=1)
    tok_s = B * args.gen / dt
    report["decode_s"] = dt
    report["tokens_per_s"] = tok_s
    print(f"[decode] {args.gen} steps x batch {B}: {dt:.2f}s "
          f"({tok_s:.1f} tok/s)")
    print("[sample tokens]", np_preview(gen))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b",
                    choices=registry.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--quant", default="none",
                    choices=["none", "fxp", "vp", "vp_block"])
    ap.add_argument("--layout", default="packed",
                    choices=["packed", "planes"],
                    help="VP weight storage: packed kernel words (default)"
                         " or the legacy jnp-dequant two-plane baseline")
    ap.add_argument("--M", type=int, default=7,
                    help="VP significand bits; M+E <= 8 packs weights "
                         "into int8 words (half the bytes of bf16)")
    ap.add_argument("--E", type=int, default=2,
                    help="VP exponent-index bits (2^E exponent options)")
    ap.add_argument("--block", type=int, default=256,
                    help="vp_block index granularity; must divide the "
                         "contraction dims to engage the int8-MXU path "
                         "(non-tileable weights fall back to per-element "
                         "packed VP)")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--kv-layout", default="packed",
                    choices=["packed", "planes"],
                    help="VP KV-cache storage: packed kernel words "
                         "(default) or the legacy two-plane jnp-dequant "
                         "baseline")
    ap.add_argument("--tune-decode", action="store_true",
                    help="autotune the serving kernel at M=1..batch first")
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="write a serving report (tokens/sec) to FILE")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # Continuous-batching engine mode
    ap.add_argument("--engine", action="store_true",
                    help="serve --batch requests through the paged "
                         "continuous-batching engine instead of one "
                         "static batch")
    ap.add_argument("--max-slots", type=int, default=0,
                    help="concurrent requests resident in the paged "
                         "cache (default min(batch, 4))")
    ap.add_argument("--page-size", type=int, default=16,
                    help="cache positions per page")
    ap.add_argument("--lookahead", type=int, default=1,
                    help="fused decode run-ahead: decode this many "
                         "tokens per jitted dispatch (one gather + one "
                         "scatter amortized over the steps; tokens are "
                         "bit-identical to --lookahead 1)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split prompt prefill into chunks of this many "
                         "tokens interleaved with decode steps "
                         "(full-causal models only)")
    ap.add_argument("--ragged-gen", action="store_true",
                    help="engine mode: vary per-request generation "
                         "lengths (deterministic ragged traffic)")
    ap.add_argument("--arrival-gap", type=float, default=0.0,
                    help="engine mode: stagger request arrivals by this "
                         "many virtual seconds")
    ap.add_argument("--hbm-budget", type=int, default=0,
                    help="engine mode: HBM byte budget sizing the page "
                         "pool (0 = fully committed)")
    # Resilience / scheduling (engine mode)
    ap.add_argument("--policy", default="fifo", choices=["fifo", "edf"],
                    help="engine admission: FIFO head-of-line or "
                         "earliest-deadline-first")
    ap.add_argument("--preempt", action="store_true",
                    help="EDF: allow preemption-by-eviction of later-"
                         "deadline running requests (re-admission "
                         "re-prefills, tokens are preserved)")
    ap.add_argument("--slo", default="none",
                    choices=["none", "interactive", "standard", "batch"],
                    help="attach this SLO class to every request "
                         "(derives per-request deadlines)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request completion deadline, seconds after "
                         "arrival (0 = none); expiry cancels with full "
                         "page reclamation")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded submit queue: arrivals beyond this "
                         "many waiting requests are shed (0 = unbounded)")
    ap.add_argument("--on-nonfinite", default="raise",
                    choices=["raise", "quarantine"],
                    help="smoke finite-check action: hard stop (default "
                         "for the CLI) or per-request quarantine")
    ap.add_argument("--degrade", action="store_true",
                    help="re-run repeatedly-quarantined requests on the "
                         "static golden-baseline path instead of "
                         "dropping them")
    args = ap.parse_args()
    runtime.require_tpu()
    runtime.enable_compile_cache()

    quant = QuantConfig(mode=args.quant, M=args.M, E=args.E,
                        block=args.block,
                        quantize_kv_cache=args.kv_quant,
                        kv_layout=args.kv_layout)
    cfg = (registry.get_smoke_config(args.arch, quant) if args.smoke
           else registry.get_config(args.arch, quant))
    # Independent streams: model init, prompt draws, and sampling must
    # never share a key (weights correlated with benchmark activations).
    k_params, k_prompt, k_sample = jax.random.split(
        jax.random.PRNGKey(args.seed), 3)
    params = init_params(k_params, cfg)
    report = {"arch": args.arch, "quant": args.quant, "layout": args.layout,
              "kv_quant": bool(args.kv_quant), "kv_layout": args.kv_layout,
              "smoke": bool(args.smoke), "batch": args.batch,
              "prompt_len": args.prompt_len, "gen": args.gen}
    if args.kv_quant and args.kv_layout == "packed":
        from repro.models.attention import kv_cache_formats
        _, kv_vp = kv_cache_formats(cfg.quant)
        print(f"[serve] packed VP KV cache: {kv_vp.storage_bits} "
              f"bits/element ({kv_vp.M}+{kv_vp.E} info bits), "
              "kernel-backed decode attention")
    if args.quant != "none":
        params = quantize_params(params, cfg, layout=args.layout)
        qbytes = quantized_bytes(params)
        report["quantized_bytes"] = qbytes
        if args.quant == "vp" and args.layout == "packed":
            _, vp = canonical_formats(cfg.quant)
            print(f"[serve] packed VP words: {qbytes/1e6:.2f} MB "
                  f"({vp.storage_bits} bits/param, kernel-backed qdot)")
        else:
            print(f"[serve] quantized planes: {qbytes/1e6:.2f} MB")
    # Tunable decode surfaces: packed-word weight panels (vp + packed
    # layout) and/or the packed KV decode-attention cache — the latter is
    # independent of the weight quantization mode.
    tunable = (args.quant == "vp" and args.layout == "packed") or \
        (args.kv_quant and args.kv_layout == "packed")
    if args.tune_decode and tunable:
        t0 = time.perf_counter()
        prof = tune_decode_profile(
            params, cfg, args.batch,
            max_len=args.prompt_len + args.gen)
        if prof:
            n_entries = sum(
                len(v) if isinstance(v, dict) else 1
                for v in prof.values())
            print(f"[serve] decode autotune profile: "
                  f"{n_entries} entries over "
                  f"{len(prof)} shapes in {time.perf_counter()-t0:.1f}s")

    if args.engine:
        _run_engine(args, params, cfg, k_prompt, report)
    else:
        _run_static(args, params, cfg, k_prompt, k_sample, report)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"[serve] wrote report to {args.json}")


def np_preview(x):
    import numpy as np
    a = np.asarray(x)
    return a[:, :12].tolist()


if __name__ == "__main__":
    main()
