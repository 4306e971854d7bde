"""The program's own spans and counters: where a serving step spends its
host time, on the profiler's clock.

A span records its name, start and end on `time.perf_counter`, its own
id, its parent's id and its attributes, in memory.  It also opens a
`jax.profiler.TraceAnnotation` named `repro.<name>` with `call` (its id)
and `parent`, so a profiler trace shows it beside the device operations
it caused.  Counters count events, such as each program the serving
runner builds (`compiles.<program>`).

The recorder is on while a `jax.profiler` trace is being collected, and
from `enable()` to `disable()` whether or not one is; a span opened
inside a recorded span is recorded too.  Off, `span` returns one shared
no-op context: no clock read, no record, no annotation; `count` does
nothing.  `spans()` and `counters()` read what was recorded; `drain()`
hands it back and forgets it.  Spans nest per thread.

    with tracing.span("engine.decode", rows=16) as attrs:
        ...
        attrs["rids"] = rids     # dropped when the recorder is off
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

PREFIX = "repro."

_Annotation = jax.profiler.TraceAnnotation
_profiling = _Annotation.is_enabled

_on = False
_spans: List["Span"] = []
_counters: collections.Counter = collections.Counter()
_ids = itertools.count()
_local = threading.local()
_lock = threading.Lock()          # over `_spans` and `_counters`


@dataclasses.dataclass
class Span:
    """One recorded span: the fields of the benchmark harness's own span
    records (`bench/harness.py`), and the id of the span it nests in."""
    name: str
    t0: float
    t1: float
    attrs: Dict[str, Any]
    call: int                     # this span's id
    parent: Optional[int] = None  # the enclosing span's id


class _Discard(dict):
    """Attributes of a span that is not recorded: writes are dropped."""

    def __setitem__(self, key, value):
        pass


_DISCARD = _Discard()


class _Off:
    """The one no-op span handed out while the recorder is off."""
    __slots__ = ()

    def __enter__(self):
        return _DISCARD

    def __exit__(self, *exc):
        return False


OFF = _Off()


def _stack() -> List[int]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Recorded:
    __slots__ = ("name", "attrs", "call", "parent", "t0", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = _stack()
        self.call = next(_ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.call)
        ids = {"call": self.call}
        if self.parent is not None:
            ids["parent"] = self.parent
        self._ann = _Annotation(PREFIX + self.name, **ids)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self.attrs

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _stack().pop()
        done = Span(self.name, self.t0, t1, self.attrs, self.call,
                    self.parent)
        with _lock:
            _spans.append(done)
        return False


def active() -> bool:
    """Whether a span opened now would be recorded."""
    return _on or _profiling() or bool(getattr(_local, "stack", None))


def span(name: str, **attrs):
    """A context that records `name` over its body while the recorder is
    on; it yields the span's attributes, which the body may add to."""
    if not active():
        return OFF
    return _Recorded(name, attrs)


def count(name: str, n: int = 1) -> None:
    if active():
        with _lock:
            _counters[name] += n


def enable() -> None:
    """Record from now on, with or without a profiler trace."""
    global _on
    _on = True


def disable() -> None:
    """Record only while a profiler trace is being collected."""
    global _on
    _on = False


def spans() -> List[Span]:
    with _lock:
        return list(_spans)


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def drain() -> Tuple[List[Span], Dict[str, int]]:
    """What was recorded so far, which the recorder then forgets."""
    with _lock:
        out = (list(_spans), dict(_counters))
        _spans.clear()
        _counters.clear()
    return out
