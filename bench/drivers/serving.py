"""Serving driver: a dense decoder served by `ServingEngine` under a
closed loop, checked against the plain float32 reference.

Set-up makes the weights on the device from the seed (the reference's
own `make_weights`, bf16), hands them to the program, which quantizes
them to packed VP words in the same jitted call, builds the engine as
`launch/serve.py --engine` does (greedy, default knobs) on the run's
clock, and warms every prompt length of the mix and every decode bucket.

The window drives the engine step by step.  After each step the host
stamps the tokens that appeared, and each client whose request finished
submits its next one.

`correct` compares what the window served: a sample of finished
requests drawn from the seed, with the longest among them, is run
through the reference (prompt plus served tokens, one forward pass).
At each served token the gap by which its logit lies below the
reference's best at that position is read; the widest gap and the mean
gap are each held to the mix's limit.  A control run (`bench.control`)
puts the reference at the next precision down in the program's place:
at the same positions it takes the token that the reference computed in
fp8 ranks first, and the same two gaps of those tokens are judged
instead.
"""
from __future__ import annotations

import gc
import math
from typing import Dict, List, Optional

import numpy as np

from bench import stats, traffic, work
from bench.harness import Check, Context, Outcome, Readings, \
    memory_peak_bytes, seed_key
from bench.reference import qwen3
from bench.spec import Cell

CHECK_BLOCK = 256       # reference logit rows per LM-head call
# A time read from the host's clock spans at least this many seconds: a
# request whose tokens inside the window lie closer together (one
# submitted in the window's last steps) gives no time per output token.
# A mix may set its own (`tpot_min_span_s`).
MIN_SPAN_S = 0.25
OK = ("ok", "retried")


def program_config(config: dict):
    """The program's `ModelConfig` for the configuration file."""
    from repro.configs.base import ModelConfig, QuantConfig

    serve = config["serving"]
    if config["rms_norm_eps"] != 1e-6 or config["hidden_act"] != "silu":
        raise ValueError("the program's dense block has RMSNorm eps 1e-6 "
                         "and a SiLU gate")
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        qk_norm=True, rope_theta=float(config["rope_theta"]),
        dtype=serve["activation_dtype"], quant=QuantConfig(**serve["quant"]))


def program_tree(w: dict) -> dict:
    """The benchmark's weights in the program's parameter layout.  The
    program's norms scale by (1 + gamma); its LM head is a table of its
    own, here the tied embedding's transpose."""
    import jax.numpy as jnp

    def gamma(g):
        return g.astype(jnp.float32) - 1.0

    layer = {
        "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"],
                 "q_norm": gamma(w["q_norm"]), "k_norm": gamma(w["k_norm"])},
        "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                "w_down": w["w_down"]},
        "ln1": gamma(w["ln1"]), "ln2": gamma(w["ln2"]),
    }
    return {"embed": w["embed"], "final_norm": gamma(w["final_norm"]),
            "lm_head": w["embed"].T, "groups": [{"sub0": layer}]}


def build_params(config: dict, mcfg, seed: int):
    import jax

    from repro.models import quantize_params

    def make(key):
        w = qwen3.make_weights(config, key)
        return quantize_params(program_tree(w), mcfg, layout="packed")

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))


def storage_bits(mcfg) -> tuple:
    """(weight word bits, KV word bits) of the program's VP formats."""
    from repro.models.attention import kv_cache_formats
    from repro.models.layers import canonical_formats

    return (canonical_formats(mcfg.quant)[1].storage_bits,
            kv_cache_formats(mcfg.quant)[1].storage_bits)


class Served:
    """Per-request host record of the window: token stamps, due time."""

    def __init__(self):
        self.token_times: Dict[int, List[float]] = {}
        self.due: Dict[int, float] = {}
        self.runs: Dict[int, object] = {}

    def stamp(self, runs, t: float) -> None:
        for run in runs:
            rid = run.req.rid
            ts = self.token_times.setdefault(rid, [])
            n = len(run.tokens)
            if n > len(ts):
                first = run.first_token_time
                if not ts and first is not None:
                    ts.append(first)
                ts.extend([t] * (n - len(ts)))
            self.runs[rid] = run


def instrument(engine, ctx: Context) -> None:
    """Host spans around the engine's calls into its scheduler and its
    runner; each decode span carries its rows and live lengths."""
    runner, sched = engine.runner, engine.scheduler
    decode, prefill, admit = runner.decode_batch, runner.prefill_commit, \
        sched.admit

    def decode_batch(params, slot_tokens, key, steps=1):
        live = [len(sched.running[s].prefill_source)
                + len(sched.running[s].tokens) for s in sorted(slot_tokens)]
        with ctx.spans.span("decode", rows=len(slot_tokens), live=live):
            return decode(params, slot_tokens, key, steps)

    def prefill_commit(params, prompt, slot, key):
        with ctx.spans.span("prefill", tokens=int(prompt.shape[-1])):
            return prefill(params, prompt, slot, key)

    def admit_(now):
        with ctx.spans.span("admit"):
            return admit(now)

    runner.decode_batch = decode_batch
    runner.prefill_commit = prefill_commit
    sched.admit = admit_


def warm_up(engine, mix: dict, vocab: int, seed: int) -> None:
    """Compile every program the window will run: one request per slot,
    prompts cycling over the mix's grid.  The engine prefills one request
    a step, so with budgets of slots + 1 + 2 i the running batch grows to
    every slot and then shrinks by one every other step, through every
    power-of-two decode bucket both ways."""
    rng = np.random.default_rng(seed)
    grid = mix["prompt_grid"]
    slots = engine.kv.max_slots
    if slots < len(grid):
        raise ValueError("warm-up needs a slot per prompt length")
    for i in range(slots):
        engine.submit(rng.integers(0, vocab, grid[i % len(grid)]).tolist(),
                      slots + 1 + 2 * i, 0.0)
    recs = engine.run()
    bad = [r for r in recs if r["outcome"] not in OK]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad[:2]}")
    engine.finished.clear()
    engine.stats.clear()


def drive(engine, ctx: Context, mix: dict, seed: int, vocab: int,
          seconds: float) -> Served:
    """The measured window: a closed loop of `clients` requests."""
    served = Served()
    clock, sched = ctx.clock, engine.scheduler
    source = traffic.requests(mix, seed, vocab)
    todo = [next(source) for _ in range(mix["clients"])]
    ctx.open_window(seconds)
    for r in todo:
        req = engine.submit(r.prompt, r.max_new_tokens, 0.0)
        served.due[req.rid] = req.arrival_time
    n_fin = 0
    while True:
        now = clock.now()
        if now >= seconds:
            break
        ctx.tick(now)
        with ctx.spans.span("step"):
            engine.step()
        t = clock.now()
        fresh = engine.finished[n_fin:]
        n_fin = len(engine.finished)
        served.stamp(list(sched.running.values()) + fresh, t)
        for _ in fresh:
            r = next(source)
            req = engine.submit(r.prompt, r.max_new_tokens, t)
            served.due[req.rid] = t
    ctx.close_window()
    return served


def end_to_end(served: Served, seconds: float,
               min_span: float = MIN_SPAN_S) -> Dict[str, float]:
    tt = served.token_times
    out = {"output_tok_s": stats.tokens_in_window(tt, 0.0, seconds)
           / seconds}
    tpot = stats.tpot_samples(tt, 0.0, seconds, min_span)
    if tpot:
        out["tpot_p95_ms"] = 1e3 * stats.percentile(tpot, 95)
    out["_n_tpot"] = len(tpot)
    return out


def sample_for_check(served: Served, seed: int, want_tokens: int):
    """Finished requests to compare: the longest, then others in an order
    drawn from the seed, until `want_tokens` served tokens are in."""
    done = [r for r in served.runs.values()
            if r.outcome in OK and len(r.tokens) == r.req.max_new_tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.req.rid)
    longest = max(done, key=lambda r: (len(r.req.prompt) + len(r.tokens),
                                       -r.req.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 1]).permutation(len(rest))
    pick, n = [longest], len(longest.tokens)
    for i in order:
        if n >= want_tokens:
            break
        pick.append(rest[i])
        n += len(rest[i].tokens)
    return [(list(r.req.prompt), list(r.tokens)) for r in pick]


def reference_gaps(config: dict, seed: int, sample, pad_to: int,
                   control: bool = False) -> Dict[str, Dict[str, float]]:
    """The gaps between the reference's best logit and its logit of each
    served token of `sample`: the widest, and the mean over all served
    tokens (under "program").  With `control`, the same two of the tokens
    that the fp8 control ranks first at those positions (under
    "control")."""
    import jax
    import jax.numpy as jnp

    w = jax.jit(lambda k: qwen3.served_weights(
        config, qwen3.make_weights(config, k)))(seed_key(seed))
    acts = (None, "fp8") if control else (None,)
    fwd = {a: jax.jit(lambda w, t, p, a=a: qwen3.hidden_states(
        config, w, t, p, a)) for a in acts}
    rows = jax.jit(qwen3.logit_rows)
    gaps = {"program": [], "control": []}
    for prompt, toks in sample:
        seq = prompt + toks[:-1]
        P, n = len(prompt), len(toks)
        padded = jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32)
        hid = {a: f(w, padded, P)[P - 1:P - 1 + n] for a, f in fwd.items()}
        for b in range(0, n, CHECK_BLOCK):
            m = min(CHECK_BLOCK, n - b)
            pad = CHECK_BLOCK - m

            def block(h):
                return jnp.pad(h[b:b + m], ((0, pad), (0, 0)))

            tgt = jnp.asarray(toks[b:b + m] + [0] * pad, jnp.int32)
            best, at, _ = rows(w, block(hid[None]), tgt)
            gaps["program"].append(np.asarray(best - at)[:m])
            if control:
                _, _, ctop = rows(w, block(hid["fp8"]), tgt)
                _, at_c, _ = rows(w, block(hid[None]), ctop)
                gaps["control"].append(np.asarray(best - at_c)[:m])
        del hid
    out = {}
    for k, g in gaps.items():
        if g:
            g = np.concatenate(g)
            out[k] = {"widest": float(g.max()), "mean": float(g.mean()),
                      "n": len(g)}
    return out


def gap_checks(gaps: Optional[Dict[str, float]], limits: dict,
               failed: int) -> List[Check]:
    none = {"widest": math.inf, "mean": math.inf}
    g = gaps or none
    return [Check("served_logit_gap", g["widest"], limits["logit_gap"]),
            Check("served_mean_gap", g["mean"], limits["mean_gap"]),
            Check("failed_requests", float(failed), 0.0)]


def run(cell: Cell, seed: int, seconds: float, ctx: Context) -> Outcome:
    from repro.serving import ServingEngine

    config, mix = cell.config, cell.traffic
    mcfg = program_config(config)
    params = build_params(config, mcfg, seed)
    lm = work.DenseLM.from_config(config, *storage_bits(mcfg))
    geo = mix["engine"]
    engine = ServingEngine(
        params, mcfg, max_slots=geo["max_slots"], capacity=geo["capacity"],
        page_size=config["serving"]["page_size"], temperature=0.0,
        clock=ctx.clock)
    instrument(engine, ctx)
    warm_up(engine, mix, lm.vocab, seed)
    served = drive(engine, ctx, mix, seed, lm.vocab, seconds)
    peak = memory_peak_bytes()

    e2e = end_to_end(served, seconds,
                     mix.get("tpot_min_span_s", MIN_SPAN_S))
    ctx.log(f"window: {len(served.due)} requests submitted, "
            f"{e2e.pop('_n_tpot')} timed for tpot (n)")
    attempted = len(served.due)
    failed = sum(1 for run in served.runs.values()
                 if run.outcome is not None and run.outcome not in OK)
    readings = Readings(
        spans=list(ctx.spans.items), seconds=seconds, lm=lm, served=served,
        window=ctx.traced_window, compile_setup_s=ctx.compile_setup_s,
        trace=ctx.tracer.read() if ctx.tracer is not None else None)

    sample = sample_for_check(served, seed, mix["check_tokens"])
    del engine, params
    gc.collect()
    gaps = reference_gaps(config, seed, sample, geo["capacity"],
                          control=ctx.control) if sample else {}
    n_tok = gaps["program"]["n"] if gaps else 0
    ctx.log(f"reference: {len(sample)} requests, {n_tok} served tokens "
            f"compared")
    limits = mix["limits"]
    checks = gap_checks(gaps.get("program"), limits, failed)
    control = gap_checks(gaps.get("control"), limits, failed) \
        if ctx.control else None
    return Outcome(end_to_end=e2e, attempted=attempted, failed=failed,
                   checks=checks, readings=readings, memory_peak_bytes=peak,
                   notes={"compared_tokens": n_tok}, control_checks=control)
