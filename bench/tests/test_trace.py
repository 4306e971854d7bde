"""The trace reduction, on a small trace recorded on one TPU v5e: the
equalizer slot step at 96 subcarriers, 0.16 s traced."""
import pathlib

import pytest

from bench import trace
from bench.stats import union_length

DATA = pathlib.Path(__file__).parent / "data" / "eq_small.xplane.pb"


@pytest.fixture(scope="module")
def raw():
    """The device ops and host spans, read straight from the file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(DATA))
    ops, spans = [], {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name.startswith("/device:") and \
                        line.name == "XLA Ops":
                    ops.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
                elif e.name.startswith("bench."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9))
    return ops, spans


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(DATA)


def test_window_and_devices(summary, raw):
    (w0, w1), = raw[1]["bench.window"]
    assert summary.window_s == pytest.approx(w1 - w0)
    assert summary.devices == ["/device:TPU:0"]
    assert len(summary.ops) == len(raw[0]) > 1000


def test_busy_is_the_union_of_op_intervals(summary, raw):
    (w0, w1), = raw[1]["bench.window"]
    clipped = [(max(a, w0), min(b, w1)) for _, a, b in raw[0] if b > w0
               and a < w1]
    assert summary.busy_s() == pytest.approx(union_length(clipped),
                                             rel=1e-9)
    assert 0.0 < summary.busy_s() < summary.window_s
    assert 0.0 < summary.idle_share() < 1.0


def test_kernel_time_sums_its_events(summary, raw):
    want = sum(b - a for n, a, b in raw[0]
               if "vp_quant_matmul_batched_pallas" in n)
    match = trace.kernel_matcher(["vp_quant_matmul_batched"])
    assert summary.op_seconds(match) == pytest.approx(want, rel=1e-9)
    # every launch sits inside the harness span of its slot
    slots = summary.spans_named("bench.slot")
    assert len(slots) == len(raw[1]["bench.slot"]) > 100
    assert summary.op_seconds(match, slots) == pytest.approx(want, rel=1e-6)
    assert summary.op_seconds(match, slots[:10]) < want / 5


def test_top_ops_are_self_times_that_sum_to_busy_time(summary):
    top = dict(summary.top_ops(n=1000))
    assert max(top, key=top.get) == "vp_quant_matmul_batched_pallas"
    assert all(v >= 0 for v in top.values())
    total = sum(op.t1 - op.t0 for op in summary.ops)
    assert sum(top.values()) <= total + 1e-12


def test_self_time_of_nested_ops():
    outer = trace.Op("while", 0.0, 10.0, "d")
    inner = [trace.Op("a", 1.0, 3.0, "d"), trace.Op("b", 4.0, 5.0, "d")]
    deep = trace.Op("c", 1.5, 2.0, "d")
    trace._self_times([outer, *inner, deep])
    assert outer.self_s == pytest.approx(7.0)
    assert inner[0].self_s == pytest.approx(1.5)
    assert deep.self_s == pytest.approx(0.5)


def test_idle_gaps_are_labelled_by_host_spans(summary):
    gaps = dict(summary.idle_gaps(n=100))
    idle = summary.window_s - summary.busy_s()
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    # the host spends the window in its slot calls
    assert gaps["bench.slot"] > 0.9 * idle


def test_short_names():
    assert trace.short_name(
        "%vp_dequant_matmul_pallas.3 = f32[8,8] custom-call(%x)") == \
        "vp_dequant_matmul_pallas"
    assert trace.short_name("%fusion.17.clone.clone = s32[] fusion()") == \
        "fusion"
