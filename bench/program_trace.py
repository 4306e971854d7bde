"""What the program records about itself, reduced for the per-layer
metrics: its own spans, and its device time by compiled program and by
named scope.

The program's spans (`repro.tracing`) are on while the profiler runs, so
in a `--trace 1` run they cover the traced part of the window.  A span
carries its name, start and end on `time.perf_counter`, its id (`call`)
and its parent's id; each is also a `repro.<name>` annotation in the
profiler's trace.  A program without `repro.tracing` records nothing,
and every reader of these spans then returns None.

The trace side reads the `.xplane.pb` as `bench/trace.py` does, with
three more things: the `repro.` annotations with `call` and `parent`;
the "XLA Modules" line, one event per execution of a compiled program
(`jit_<name>(<fingerprint>)`); and each device operation under its full
instruction name (`fusion.3`, not `fusion`).  A compiled program's text
(`compiled.as_text()`) gives each instruction its named-scope path, from
the `op_name` of its metadata, so device time splits by scope.

    python3 -m bench.program_trace <trace.xplane.pb> [<program.hlo> ...]

prints idle seconds by innermost program span and, for each program
text given, its device time per execution and its share by scope.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace

PROGRAM_PREFIX = "repro."
MODULES_LINE = "XLA Modules"
# the calls of a runner step, whose time is not the engine's own
RUNNER_CALLS = ("runner.decode", "runner.prefill")


# -- the program's spans, in memory ------------------------------------------

def recorded() -> list:
    """The program's recorded spans (`repro.tracing.Span`), or [] where
    the program has no recorder."""
    try:
        from repro import tracing
    except ImportError:
        return []
    return tracing.spans()


def mean_ms(spans: Iterable, name: str) -> Optional[float]:
    """Mean milliseconds of the spans named `name`; None if none."""
    took = [s.t1 - s.t0 for s in spans if s.name == name]
    return 1e3 * sum(took) / len(took) if took else None


def engine_host_ms(spans: Sequence) -> Optional[float]:
    """Mean milliseconds per `engine.step` of its own host work: the
    step's time less that of the runner calls (`runner.decode`,
    `runner.prefill`) nested in it."""
    by_call = {s.call: s for s in spans}
    steps = {s.call: s.t1 - s.t0 for s in spans if s.name == "engine.step"}
    if not steps:
        return None

    def step_of(s):
        p = s.parent
        while p is not None and p in by_call:
            if by_call[p].name == "engine.step":
                return p
            p = by_call[p].parent
        return None

    own = dict(steps)
    for s in spans:
        if s.name in RUNNER_CALLS:
            st = step_of(s)
            if st is not None:
                own[st] -= s.t1 - s.t0
    return 1e3 * sum(own.values()) / len(own)


# -- compiled text: instruction -> named-scope path ---------------------------

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def module_name(hlo_text: str) -> str:
    """`HloModule jit_decode_b16_s1, ...` -> `jit_decode_b16_s1`."""
    m = _MODULE.match(hlo_text.lstrip())
    if m is None:
        raise ValueError("not an HLO module's text")
    return m.group(1)


def scope_map(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """Each instruction of a compiled module's text mapped to its path:
    the parts of its `op_name` before the operation itself
    (`jit(f)/gather/gather` -> ('jit(f)', 'gather')).

    The compiler makes instructions of its own, which carry no
    `op_name`: the loop it expands a gather into, a buffer it
    initializes for that loop, a copy.  Such an instruction takes the
    path of the instruction that calls its computation (a loop's body
    takes the loop's), else the path that all its users share, else
    none."""
    comp, named, users, caller = None, {}, collections.defaultdict(set), {}
    home = {}
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ", "\t") and line.rstrip().endswith("{"):
            m = _HEADER.match(line)
            comp = m.group(1) if m else None
            continue
        m = _LINE.match(line)
        if m is None or comp is None:
            continue
        name, rhs = m.groups()
        home[name] = comp
        op = _OP_NAME.search(rhs)
        named[name] = None if op is None else op.group(1)
        called = set(_CALLED.findall(rhs))
        for b in _BRANCHES.findall(rhs):
            called.update(_REF.findall(b))
        for c in called:
            caller.setdefault(c, name)
        for ref in _REF.findall(rhs):
            if ref not in called:
                users[ref].add(name)
    out: Dict[str, Tuple[str, ...]] = {}

    def resolve(name: str) -> Tuple[str, ...]:
        if name in out:
            return out[name]
        out[name] = ()                       # guards a cycle
        op = named[name]
        if op is not None:
            path = tuple(op.split("/")[:-1])
        elif home[name] in caller:
            path = resolve(caller[home[name]])
        else:
            paths = {resolve(u) for u in users[name]
                     if u in home and home[u] == home[name]}
            path = paths.pop() if len(paths) == 1 else ()
        out[name] = path
        return path

    for name in named:
        resolve(name)
    return out


def innermost(path: Sequence[str], scopes: Sequence[str]) -> Optional[str]:
    """The innermost part of `path` that is one of `scopes`."""
    for part in reversed(path):
        if part in scopes:
            return part
    return None


# -- the trace, with program spans, module executions and full names ----------

@dataclasses.dataclass
class ProgramSpan:
    name: str
    t0: float
    t1: float
    call: Optional[int]
    parent: Optional[int]


@dataclasses.dataclass
class Execution:
    module: str          # `jit_decode_b16_s1`, the fingerprint dropped
    t0: float
    t1: float
    device: str


@dataclasses.dataclass
class ProgramTrace:
    window_s: float
    devices: List[str]
    ops: List[trace.Op]           # under their full instruction names
    spans: List[ProgramSpan]      # the program's `repro.` spans
    executions: List[Execution]

    def idle_by_span(self, n: int = 10) -> List[Tuple[str, float]]:
        """Idle device seconds by the innermost program span over each
        gap ("host: other" where none was open)."""
        return trace.Summary(self.window_s, self.devices, self.ops,
                             self.spans).idle_gaps(n)

    def executions_of(self, prefix: str) -> List[Execution]:
        return [e for e in self.executions if e.module.startswith(prefix)]

    def scope_seconds(self, prefix: str, hlo_texts: Iterable[str],
                      scopes: Sequence[str]) -> Dict[Optional[str], float]:
        """Device self seconds of the operations inside executions of the
        modules named `prefix*`, by innermost scope of `scopes` (None:
        under none of them).  `hlo_texts` are the compiled texts of
        those modules."""
        maps = {module_name(t): scope_map(t) for t in hlo_texts}
        per_dev = collections.defaultdict(list)
        for e in self.executions_of(prefix):
            per_dev[e.device].append(e)
        starts = {}
        for dev, runs in per_dev.items():
            runs.sort(key=lambda e: e.t0)
            starts[dev] = [e.t0 for e in runs]
        acc: Dict[Optional[str], float] = collections.Counter()
        for op in self.ops:
            runs = per_dev.get(op.device)
            if not runs:
                continue
            i = bisect.bisect_right(starts[op.device], op.t0) - 1
            if i < 0 or op.t1 > runs[i].t1:
                continue
            path = maps.get(runs[i].module, {}).get(op.name, ())
            acc[innermost(path, scopes)] += op.self_s
        return dict(acc)


def _instruction(hlo: str) -> str:
    """`%fusion.3 = f32[8] fusion(...)` -> `fusion.3`."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def collect(path: pathlib.Path) -> ProgramTrace:
    """Read a trace for the program's spans, its module executions and
    its operations under full names; times in seconds from the start of
    the harness's `bench.window` span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    raw_ops, raw_runs, raw_spans, devices, window = [], [], [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    devices.append(plane.name)
                    raw_ops += [(_instruction(e.name), e.start_ns,
                                 e.duration_ns, plane.name)
                                for e in line.events]
                elif line.name == MODULES_LINE:
                    raw_runs += [(e.name.split("(", 1)[0], e.start_ns,
                                  e.duration_ns, plane.name)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.SPAN_PREFIX + "window":
                        window.append((e.start_ns, e.duration_ns))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        call, parent = (trace._stat(e, k)
                                        for k in ("call", "parent"))
                        raw_spans.append((
                            e.name[len(PROGRAM_PREFIX):], e.start_ns,
                            e.duration_ns,
                            None if call is None else int(call),
                            None if parent is None else int(parent)))
    if len(window) != 1:
        raise ValueError(f"expected one {trace.SPAN_PREFIX}window span, "
                         f"found {len(window)}")
    (w0, wlen), = window
    sec = 1e-9
    ops = [trace.Op(n, (s - w0) * sec, (s + d - w0) * sec, dev)
           for n, s, d, dev in raw_ops]
    for dev in set(devices):
        trace._self_times([op for op in ops if op.device == dev])
    return ProgramTrace(
        window_s=wlen * sec, devices=sorted(set(devices)), ops=ops,
        spans=[ProgramSpan(n, (s - w0) * sec, (s + d - w0) * sec, c, p)
               for n, s, d, c, p in raw_spans],
        executions=[Execution(m, (s - w0) * sec, (s + d - w0) * sec, dev)
                    for m, s, d, dev in raw_runs])


SCOPES = ("gather", "model", "kv_append", "sample", "commit", "operands",
          "pad", "combine")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("xplane", type=pathlib.Path)
    ap.add_argument("hlo", type=pathlib.Path, nargs="*")
    args = ap.parse_args(argv)
    pt = collect(args.xplane)
    print(f"window {pt.window_s:.6f} s; idle by program span:")
    for name, secs in pt.idle_by_span():
        print(f"  {name}: {secs:.6f} s")
    for path in args.hlo:
        text = path.read_text()
        mod = module_name(text)
        runs = pt.executions_of(mod)
        if not runs:
            print(f"{mod}: no execution in the trace")
            continue
        by = pt.scope_seconds(mod, [text], SCOPES)
        total = sum(by.values())
        took = sum(e.t1 - e.t0 for e in runs)
        print(f"{mod}: {len(runs)} executions, {1e3 * took / len(runs):.4f}"
              f" ms each on the device, {total:.6f} s of op self time")
        for scope, secs in sorted(by.items(), key=lambda kv: -kv[1]):
            print(f"  {scope or '(no scope)'}: {secs:.6f} s, "
                  f"{100.0 * secs / total:.2f}%")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
