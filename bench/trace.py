"""Reduction from a profiler trace to the numbers the per-layer metrics
read: device busy time, time per kernel, and idle gaps labelled by what
the harness was doing on the host.

The trace is the `.xplane.pb` that `jax.profiler` writes; `ProfileData`
reads it with nothing but JAX.  Device operations are the events of the
"XLA Ops" line of each `/device:` plane; each is named by its HLO
instruction, without the `%` and the numeric suffix (a Pallas kernel by
its jitted function, e.g. `vp_dequant_matmul_pallas`).  Operations nest
(a `while` holds its body's operations), so time per operation is self
time: its duration less that of the operations inside it.  Host spans
are the harness's `TraceAnnotation`s (`bench.<name>`), and the traced
window is the `bench.window` span.  All times are in seconds from the
window's start.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench.stats import clip, gaps, union_length

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(slots=True)
class Op:
    name: str
    t0: float
    t1: float
    device: str
    self_s: float = 0.0


def short_name(hlo: str) -> str:
    """`%vp_dequant_matmul_pallas.3 = f32[...] custom-call(...)` ->
    `vp_dequant_matmul_pallas`."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.(\d+|clone))+$", "", head)


def _self_times(ops: List[Op]) -> None:
    """Self time of each operation of one device: its duration less the
    durations of the operations directly inside it."""
    stack: List[Op] = []
    for op in sorted(ops, key=lambda o: (o.t0, -(o.t1 - o.t0))):
        while stack and stack[-1].t1 <= op.t0:
            stack.pop()
        op.self_s = op.t1 - op.t0
        if stack and op.t1 <= stack[-1].t1:
            stack[-1].self_s -= op.t1 - op.t0
        stack.append(op)


@dataclasses.dataclass
class HostSpan:
    name: str
    t0: float
    t1: float
    call: Optional[int]


@dataclasses.dataclass
class Summary:
    window_s: float
    devices: List[str]
    ops: List[Op]
    spans: List[HostSpan]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        per = collections.defaultdict(list)
        for op in self.ops:
            per[op.device].append((op.t0, op.t1))
        return sum(union_length(clip(per[d], 0.0, self.window_s))
                   for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def spans_named(self, name: str) -> List[HostSpan]:
        return [s for s in self.spans if s.name == name]

    def op_seconds(self, match, within: Optional[Sequence[HostSpan]] = None
                   ) -> float:
        """Device seconds of the operations whose name `match` accepts,
        counting only what lies inside `within` (host spans that do not
        overlap each other), if given."""
        ops = [op for op in self.ops if match(op.name)]
        if within is None:
            return sum(op.t1 - op.t0 for op in ops)
        spans = sorted((s.t0, s.t1) for s in within)
        starts = [s0 for s0, _ in spans]
        total = 0.0
        for op in ops:
            i = max(0, bisect.bisect_right(starts, op.t0) - 1)
            while i < len(spans) and spans[i][0] < op.t1:
                lo, hi = max(op.t0, spans[i][0]), min(op.t1, spans[i][1])
                if hi > lo:
                    total += hi - lo
                i += 1
        return total

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device self seconds per operation name, most first (averaged
        over devices)."""
        acc = collections.Counter()
        for op in self.ops:
            acc[op.name] += op.self_s
        k = max(1, len(self.devices))
        return [(name, secs / k) for name, secs in acc.most_common(n)]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """Idle device seconds, summed by the innermost harness span that
        covered each gap's midpoint ("host: other" where none did),
        longest first.  The first device stands for the others."""
        if not self.devices:
            return []
        dev = self.devices[0]
        busy = [(op.t0, op.t1) for op in self.ops if op.device == dev]
        # spans of one name never overlap: find each name's cover by bisect
        by_name = collections.defaultdict(list)
        for s in self.spans:
            if s.name != SPAN_PREFIX + "window":
                by_name[s.name].append((s.t0, s.t1))
        index = {k: (sorted(v), sorted(t0 for t0, _ in v))
                 for k, v in by_name.items()}
        acc = collections.Counter()
        for g0, g1 in gaps(busy, 0.0, self.window_s):
            mid = 0.5 * (g0 + g1)
            best, label = None, "host: other"
            for name, (spans, starts) in index.items():
                i = bisect.bisect_right(starts, mid) - 1
                if i >= 0 and mid < spans[i][1]:
                    length = spans[i][1] - spans[i][0]
                    if best is None or length < best:
                        best, label = length, name
            acc[label] += g1 - g0
        return acc.most_common(n)


def find_xplane(folder: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(folder).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {folder}")
    return found[-1]


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def summarize(path: pathlib.Path) -> Summary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    raw_ops, raw_spans, devices = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.append(plane.name)
                for e in line.events:
                    raw_ops.append((short_name(e.name), e.start_ns,
                                    e.duration_ns, plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        call = _stat(e, "call")
                        raw_spans.append((e.name, e.start_ns, e.duration_ns,
                                          None if call is None
                                          else int(call)))
    windows = [s for s in raw_spans if s[0] == SPAN_PREFIX + "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one {SPAN_PREFIX}window span, found "
                         f"{len(windows)}")
    w0, wlen = windows[0][1], windows[0][2]
    sec = 1e-9
    ops = [Op(n, (s - w0) * sec, (s + d - w0) * sec, dev)
           for n, s, d, dev in raw_ops]
    for dev in set(devices):
        _self_times([op for op in ops if op.device == dev])
    spans = [HostSpan(n, (s - w0) * sec, (s + d - w0) * sec, c)
             for n, s, d, c in raw_spans]
    return Summary(window_s=wlen * sec, devices=sorted(set(devices)),
                   ops=ops, spans=spans)


def kernel_matcher(names: Sequence[str]):
    """Accept an operation whose name contains any of `names`."""
    def match(op_name: str) -> bool:
        return any(n in op_name for n in names)
    return match


def as_breakdown(summary: Summary) -> Dict[str, list]:
    return {"device_ops": [[n, s] for n, s in summary.top_ops()],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps()]}
