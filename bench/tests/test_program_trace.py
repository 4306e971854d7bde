"""The reduction of what the program records about itself: its spans in
memory and in a profiler trace, its module executions, its device time
by named scope, and the readers of the program-span metrics."""
import pathlib
import sys

import pytest

from bench import program_trace as pt
from bench import spec, trace
from bench.harness import Readings

DATA = pathlib.Path(__file__).parent / "data" / "eq_small.xplane.pb"


@pytest.fixture(scope="module")
def eq_trace():
    return pt.collect(DATA)


def test_collect_reads_module_executions_and_full_names(eq_trace):
    summary = trace.summarize(DATA)
    assert eq_trace.window_s == pytest.approx(summary.window_s)
    assert eq_trace.devices == summary.devices
    assert len(eq_trace.ops) == len(summary.ops)
    assert {e.module for e in eq_trace.executions} == {"jit_step"}
    assert len(eq_trace.executions) > 100
    # the same operations, under their instruction names
    names = {op.name for op in eq_trace.ops}
    assert "select_maximum_fusion.1" in names
    assert {trace.short_name(n) for n in names} == \
        {op.name for op in summary.ops}
    assert eq_trace.spans == []                # recorded before spans were


def test_collect_reads_program_spans_with_their_parents(tmp_path):
    import jax

    from repro import tracing

    tracing.drain()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with tracing.span("engine.step"):
                with tracing.span("runner.decode", rows=2):
                    jax.block_until_ready(jax.numpy.ones(8) * 2)
    finally:
        jax.profiler.stop_trace()
    mine = {s.name: s for s in tracing.drain()[0]}
    got = {s.name: s for s in pt.collect(trace.find_xplane(tmp_path)).spans}
    assert set(got) == {"engine.step", "runner.decode"}
    for name, s in got.items():
        assert (s.call, s.parent) == (mine[name].call, mine[name].parent)
    assert got["runner.decode"].parent == got["engine.step"].call
    step, dec = got["engine.step"], got["runner.decode"]
    assert 0.0 <= step.t0 <= dec.t0 <= dec.t1 <= step.t1
    assert dec.t1 - dec.t0 == pytest.approx(
        mine["runner.decode"].t1 - mine["runner.decode"].t0, abs=2e-3)


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %m = f32[4]{0} maximum(f32[4]{0} %p, f32[4]{0} %p), metadata={op_name="jit(step)/operands/max"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %g = f32[4]{0} get-tuple-element(%t), index=1
  %dynamic-update-slice.2 = f32[4]{0} dynamic-update-slice(%g, %g, %i)
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%i, %dynamic-update-slice.2)
}

%cond (t: (s32[], f32[4])) -> pred[] {
  %t = (s32[], f32[4]{0}) parameter(0)
  ROOT %c = pred[] constant(false)
}

ENTRY %main.9 (w: f32[4]) -> f32[4] {
  %w = f32[4]{0} parameter(0), metadata={op_name="w"}
  %select_maximum_fusion.1 = f32[4]{0} fusion(f32[4]{0} %w), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/operands/jit(stack)/max" source_file="ofdm.py" source_line=3}
  %copy.39 = f32[4]{0} copy(%select_maximum_fusion.1)
  %broadcast.9 = f32[4]{0} broadcast(%w), dimensions={}
  %while.1 = (s32[], f32[4]{0}) while(%broadcast.9), condition=%cond, body=%body, metadata={op_name="jit(step)/gather/gather"}
  %copy.40 = f32[4]{0} copy(%w)
  %add.1 = f32[4]{0} add(%copy.40, %copy.39), metadata={op_name="jit(step)/combine/add"}
  ROOT %reshape.23 = f32[4]{0} reshape(%copy.39), metadata={op_name="jit(step)/operands/pad/while/body/reshape"}
}
"""


def test_scope_map_reads_op_names():
    got = pt.scope_map(HLO)
    assert pt.module_name(HLO) == "jit_step"
    assert got["select_maximum_fusion.1"] == ("jit(step)", "operands",
                                              "jit(stack)")
    pad = ("jit(step)", "operands", "pad", "while", "body")
    assert got["reshape.23"] == pad
    assert got["m"] == ("jit(step)", "operands")
    assert got["w"] == ()
    scopes = ("operands", "pad", "gather")
    assert pt.innermost(got["reshape.23"], scopes) == "pad"
    assert pt.innermost(got["select_maximum_fusion.1"], scopes) == \
        "operands"
    assert pt.innermost(got["w"], scopes) is None
    with pytest.raises(ValueError):
        pt.module_name("not a module")


def test_scope_map_places_what_the_compiler_made():
    got = pt.scope_map(HLO)
    # a loop's body takes the loop's path, the loop's buffer its user's
    gather = ("jit(step)", "gather")
    assert got["dynamic-update-slice.2"] == gather
    assert got["tuple.1"] == gather
    assert got["broadcast.9"] == gather
    # a copy takes the path all its users share, and none if they differ
    assert got["copy.40"] == ("jit(step)", "combine")
    assert got["copy.39"] == ()


def test_scope_seconds_split_a_module_by_scope(eq_trace):
    by = eq_trace.scope_seconds("jit_step", [HLO], ("operands", "pad"))
    inside = [op for op in eq_trace.ops
              if any(e.t0 <= op.t0 and op.t1 <= e.t1
                     for e in eq_trace.executions)]

    def secs(name):
        return sum(op.self_s for op in inside if op.name == name)

    assert by["operands"] == pytest.approx(secs("select_maximum_fusion.1"))
    assert by["pad"] == pytest.approx(secs("reshape.23"))
    assert sum(by.values()) == pytest.approx(
        sum(op.self_s for op in inside))
    assert eq_trace.scope_seconds("jit_decode", [HLO], ("pad",)) == {}


def test_idle_by_program_span():
    """Each idle gap goes to the innermost span open at its midpoint."""
    ops = [trace.Op("fusion.1", 0.0, 1.0, "d"),
           trace.Op("fusion.2", 3.0, 4.0, "d"),
           trace.Op("fusion.3", 6.0, 7.0, "d")]
    spans = [pt.ProgramSpan("runner.decode", 0.0, 6.5, 1, 0),
             pt.ProgramSpan("runner.decode.fetch", 1.0, 3.5, 3, 1),
             pt.ProgramSpan("engine.step", 0.0, 8.0, 0, None)]
    got = dict(pt.ProgramTrace(8.0, ["d"], ops, spans, []).idle_by_span())
    assert got == pytest.approx({"runner.decode.fetch": 2.0,
                                 "runner.decode": 2.0, "engine.step": 1.0})


# -- the readers of the program-span metrics ---------------------------------

def _span(name, t0, t1, call, parent=None):
    return pt.ProgramSpan(name, t0, t1, call, parent)


# two engine steps; the second prefills and decodes
SPANS = [
    _span("engine.step", 0.000, 0.100, 0),
    _span("engine.decode", 0.010, 0.095, 1, 0),
    _span("runner.decode", 0.011, 0.094, 2, 1),
    _span("runner.decode.dispatch", 0.011, 0.013, 3, 2),
    _span("runner.decode.wait", 0.013, 0.090, 4, 2),
    _span("runner.decode.fetch", 0.090, 0.094, 5, 2),
    _span("engine.step", 0.100, 0.300, 6),
    _span("engine.prefill", 0.105, 0.200, 7, 6),
    _span("runner.prefill", 0.106, 0.199, 8, 7),
    _span("engine.decode", 0.200, 0.290, 9, 6),
    _span("runner.decode", 0.201, 0.289, 10, 9),
    _span("runner.decode.dispatch", 0.201, 0.205, 11, 10),
    _span("runner.decode.wait", 0.205, 0.280, 12, 10),
    _span("runner.decode.fetch", 0.280, 0.289, 13, 10),
    # a decode whose step began before the recorder was on
    _span("runner.decode", 0.400, 0.480, 14),
]

READERS = {"decode_dispatch_ms.decode": (0.002 + 0.004) / 2 * 1e3,
           "decode_fetch_ms.decode": (0.004 + 0.009) / 2 * 1e3,
           "engine_host_ms.decode":
               ((0.100 - 0.083) + (0.200 - 0.093 - 0.088)) / 2 * 1e3}


def _readings():
    return Readings(spans=[], window=(0.0, 4.0), trace=None)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_the_program_spans(monkeypatch, name):
    monkeypatch.setattr(pt, "recorded", lambda: list(SPANS))
    read = spec.metric_reader(name)
    assert read(_readings(), None) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_without_program_spans_reads_nothing(monkeypatch, name):
    read = spec.metric_reader(name)
    monkeypatch.setattr(pt, "recorded", lambda: [])
    assert read(_readings(), None) is None
    # a program without the recorder (the one before it) records nothing
    monkeypatch.undo()
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert pt.recorded() == []
    assert read(_readings(), None) is None


def test_every_program_span_metric_has_its_reader():
    bench = spec.load_benchmark()
    names = {m["name"] for m in bench["per_layer"]
             if m["source"] == "program_span"}
    assert names == set(READERS)
