"""Run-time plumbing shared by the drivers: the run's clock, host spans
around the calls into each layer, the compile meter and the profiler.
"""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import time
from typing import Any, Dict, List, Optional

from bench.spec import ROOT

TRACE_DIR = ROOT / ".bench_trace"


class Clock:
    """`perf_counter` seconds since `start()`; the engine's clock too
    (it asks only `now` and `wait_until`)."""

    def __init__(self):
        self._t0 = time.perf_counter()

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def wait_until(self, t: float) -> None:
        dt = t - self.now()
        if dt > 0:
            time.sleep(dt)


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    attrs: Dict[str, Any]
    call: int          # spans opened before this one: its id in a trace


class Spans:
    """Host spans on the run's clock.  While the profiler runs, each span
    is also a `TraceAnnotation`, so the trace puts it beside the device
    operations it caused."""

    def __init__(self, clock: Clock):
        self.clock = clock
        self.items: List[Span] = []
        self.annotate = False
        self._opened = 0

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        call = self._opened
        self._opened += 1
        t0 = self.clock.now()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(f"bench.{name}", call=call):
                yield attrs
        else:
            yield attrs
        self.items.append(Span(name, t0, self.clock.now(), attrs, call))

    def clear(self) -> None:
        self.items.clear()
        self._opened = 0


class CompileMeter:
    """Seconds JAX spent compiling, the number of compiles and the
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Tracer:
    """The profiler over a part of the window, written inside the
    checkout at a fixed path and removed once it has been read."""

    def __init__(self, spans: Spans):
        self.spans = spans
        self.active = False
        self.window: Optional[tuple] = None    # run-clock (t0, t1)
        self._ann = None

    def start(self) -> None:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.spans.annotate = True
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.active = True
        self._t0 = self.spans.clock.now()

    def stop(self) -> None:
        import jax

        self.window = (self._t0, self.spans.clock.now())
        self._ann.__exit__(None, None, None)
        self.spans.annotate = False
        jax.profiler.stop_trace()
        self.active = False

    def read(self):
        """Reduce the trace (bench/trace.py), then delete it."""
        from bench import trace

        try:
            return trace.summarize(trace.find_xplane(TRACE_DIR))
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)


class Context:
    """One run's clock, spans, compile meter and (with `--trace 1`)
    profiler.  The profiler covers `trace_seconds` in the middle of the
    window; the harness's spans there become trace annotations."""

    def __init__(self, t_process: float, meter: CompileMeter, trace: bool,
                 trace_seconds: float, log=print, control: bool = False):
        self.t_process = t_process
        self.meter = meter
        self.log = log
        self.control = control
        self.clock = Clock()
        self.spans = Spans(self.clock)
        self.tracer = Tracer(self.spans) if trace else None
        self.trace_seconds = trace_seconds
        self.setup_s = None
        self.compile_setup_s = None
        self.window_compiles = None
        self._seconds = None

    def open_window(self, seconds: float) -> None:
        """Set-up ends here: everything before counts in `setup_s`."""
        self.setup_s = time.perf_counter() - self.t_process
        self.compile_setup_s = self.meter.seconds
        self._compiles0 = self.meter.compiles
        self._seconds = seconds
        self.spans.clear()
        self.clock.start()

    def tick(self, now: float) -> None:
        """Start or stop the profiler as the window passes its middle."""
        tr = self.tracer
        if tr is None or tr.window is not None:
            return
        length = min(self.trace_seconds, self._seconds)
        start = 0.5 * (self._seconds - length)
        if not tr.active and now >= start:
            tr.start()
        elif tr.active and now >= start + length:
            tr.stop()

    def close_window(self) -> None:
        if self.tracer is not None and self.tracer.active:
            self.tracer.stop()
        self.window_compiles = self.meter.compiles - self._compiles0
        self.log(f"compiles inside the window: {self.window_compiles}")

    @property
    def traced_window(self):
        return None if self.tracer is None else self.tracer.window


class Readings:
    """What a cell's per-layer readers read: whatever its driver set."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def spans_in_window(self, name: str):
        """Harness spans of `name` inside the traced window."""
        t0, t1 = self.window
        return [s for s in self.spans if s.name == name and s.t0 >= t0
                and s.t1 <= t1]

    def traced(self, name: str):
        """Harness spans of `name` that the trace holds, paired with the
        trace's own record of each: [(span, trace span)]."""
        if self.trace is None:
            return []
        mine = {s.call: s for s in self.spans if s.name == name}
        return [(mine[t.call], t)
                for t in self.trace.spans_named("bench." + name)
                if t.call in mine]


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit: the run is correct only if
    `value <= limit` (a NaN is never within it)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to `bench.run`."""
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    readings: Any                      # what the per-layer readers read
    memory_peak_bytes: Optional[int]
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # a control run: the same checks, of the reference computed one
    # precision down in the program's place
    control_checks: Optional[List[Check]] = None


def seed_key(seed: int):
    """A PRNG key from any whole-number seed, wider than 32 bits too."""
    import jax
    import numpy as np

    word = int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1))
    return jax.random.PRNGKey(word)


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, where JAX reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
