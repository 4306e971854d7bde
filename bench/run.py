"""Run one cell of BENCHMARK.json and print its result as one JSON line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

With `--trace 0` the result holds the cell's end-to-end metrics; with
`--trace 1` its per-layer metrics, read from host spans, the program's
records and a profiler trace of the middle of the window.  Set-up
(weights from the seed, compiles, warm-up) ends when the window opens;
nothing compiles inside it, and the number of compiles there is printed.

The run needs an accelerator: without a TPU, or with fewer chips than
the cell asks for, it exits with an error and prints no result.  Log
lines go to standard error; the last lines there, and the last key of
the result, are the numbers compared for `correct` beside their limits.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from bench import spec  # noqa: E402

sys.path.insert(0, str(spec.ROOT / "src"))

TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def configure_caches() -> None:
    """The compile cache and the autotune cache live inside the checkout,
    at fixed paths.  Every program is cached, however fast it compiled,
    so that only a cell's first run compiles; the autotune cache starts
    empty, so tilings come from committed code."""
    import jax

    from repro.launch import runtime

    tune = spec.ROOT / ".autotune" / "bench.json"
    tune.parent.mkdir(exist_ok=True)
    tune.unlink(missing_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(tune)
    runtime.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    return info


def per_layer(cell, outcome, peaks) -> dict:
    """Each per-layer metric of the cell, from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m, read in ((m, spec.metric_reader(m.name)) for m in cell.per_layer):
        value = read(outcome.readings, peaks)
        if value is None:
            log(f"per-layer {m.name}: nothing to read, left out")
            continue
        out[m.name] = {"value": float(value), "unit": m.unit}
    return out


def finite(x: float, cap: float) -> float:
    """JSON has no infinity: a tail made infinite by requests that never
    came is printed as the longest wait the run could observe."""
    return x if math.isfinite(x) else cap


def number(x: float):
    """A JSON-printable reading: NaN and infinities as text."""
    return x if math.isfinite(x) else repr(x)


def execute(cell, seed: int, seconds: float, trace: bool, control=False):
    """Everything after the look for a chip: set-up, window, reference.
    Returns (result dict, outcome)."""
    from bench.harness import CompileMeter, Context
    from bench.peaks import peaks_for

    meter = CompileMeter()
    ctx = Context(T_PROCESS, meter, trace, TRACE_SECONDS, log=log,
                  control=control)
    info = device_info()
    drv = spec.driver(cell.kind)
    outcome = drv.run(cell, seed, seconds, ctx)
    log(f"set-up {ctx.setup_s:.3f} s, of which compile "
        f"{ctx.compile_setup_s:.3f} s; {meter.cache_hits} persistent-cache "
        f"hits; {ctx.window_compiles} compiles inside the window")
    cap = 1e3 * (seconds + 60.0)
    if trace:
        peaks = peaks_for(info["kind"])
        metrics = per_layer(cell, outcome, peaks)
        summary = outcome.readings.trace
        if summary is not None:
            info["busy_s"] = summary.busy_s()
            info["window_s"] = summary.window_s
    else:
        metrics = {}
        e2e = dict(outcome.end_to_end, setup_s=ctx.setup_s)
        for m in cell.end_to_end:
            if m.name not in e2e:
                raise RuntimeError(f"the {cell.kind} driver gave no "
                                   f"{m.name}")
            value = e2e[m.name] if m.unit != "ms" else finite(e2e[m.name],
                                                              cap)
            metrics[m.name] = {"value": value, "unit": m.unit}
    info["memory_peak_bytes"] = outcome.memory_peak_bytes
    # a control run is judged by the control's readings: it has to come
    # out as not correct
    checks = outcome.control_checks if control else outcome.checks
    result = {
        "correct": all(c.ok for c in checks),
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": metrics,
        "device": info,
    }
    if trace and outcome.readings.trace is not None:
        from bench.trace import as_breakdown

        result["breakdown"] = as_breakdown(outcome.readings.trace)
    result["notes"] = dict(outcome.notes, setup_compile_s=ctx.compile_setup_s,
                           window_compiles=ctx.window_compiles)
    if control:
        result["program_checks"] = as_dict(outcome.checks)
    result["checks"] = as_dict(checks)          # last: the numbers compared
    return result, outcome


def as_dict(checks) -> dict:
    return {c.name: {"value": number(c.value), "limit": c.limit}
            for c in checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax

    from repro.launch import runtime

    dev = runtime.require_tpu(allow_cpu_if_requested=False)
    n = len(jax.devices())
    if n < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips; JAX sees "
                         f"{n}")
    configure_caches()
    log(f"{cell.name}: {dev.device_kind} x {n}, seed {args.seed}, window "
        f"{args.seconds} s, trace {args.trace}")
    result, _ = execute(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
