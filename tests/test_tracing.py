"""The program's span-and-counter recorder (`repro.tracing`), the spans
the serving engine and runner record, the names of the runner's
programs, and the named scopes of the compiled decode and equalizer
steps."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench.program_trace import innermost, module_name, scope_map
from repro import tracing
from repro.configs.base import ModelConfig, QuantConfig
from repro.models import init_params, quantize_params
from repro.serving import ServingEngine, VirtualClock


class FakeAnnotation:
    """Stands in for `jax.profiler.TraceAnnotation`; logs each use."""
    opened = []

    def __init__(self, name, **kw):
        self.opened.append((name, kw))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def recorder(monkeypatch):
    """The recorder off, empty, with its annotations logged."""
    FakeAnnotation.opened = []
    monkeypatch.setattr(tracing, "_Annotation", FakeAnnotation)
    tracing.disable()
    tracing.drain()
    yield tracing
    tracing.disable()
    tracing.drain()


def test_off_records_nothing_and_opens_no_annotation(recorder):
    assert not tracing.active()
    with tracing.span("engine.step", rows=3) as attrs:
        attrs["later"] = 1                  # dropped
        with tracing.span("runner.decode"):
            pass
    tracing.count("compiles.x")
    assert tracing.span("a") is tracing.OFF
    assert tracing.span("b", k=1) is tracing.span("c")
    assert FakeAnnotation.opened == []
    assert tracing.drain() == ([], {})


def test_on_nests_by_parent_id_and_counts(recorder):
    tracing.enable()
    with tracing.span("engine.step") as outer:
        outer["rows"] = 2
        with tracing.span("runner.decode", Bp=2):
            with tracing.span("runner.decode.dispatch"):
                pass
        tracing.count("compiles.decode_b2_s1")
        tracing.count("compiles.decode_b2_s1", 2)
    tracing.disable()
    spans, counts = tracing.drain()
    by = {s.name: s for s in spans}
    step, dec = by["engine.step"], by["runner.decode"]
    assert step.parent is None and step.attrs == {"rows": 2}
    assert dec.parent == step.call and dec.attrs == {"Bp": 2}
    assert by["runner.decode.dispatch"].parent == dec.call
    assert step.t0 <= dec.t0 <= dec.t1 <= step.t1
    assert counts == {"compiles.decode_b2_s1": 3}
    assert FakeAnnotation.opened == [
        ("repro.engine.step", {"call": step.call}),
        ("repro.runner.decode", {"call": dec.call, "parent": step.call}),
        ("repro.runner.decode.dispatch",
         {"call": by["runner.decode.dispatch"].call, "parent": dec.call})]
    assert tracing.spans() == []


def test_span_inside_a_recorded_span_is_recorded(recorder):
    tracing.enable()
    with tracing.span("engine.step"):
        tracing.disable()
        with tracing.span("engine.retire"):
            pass
    assert [s.name for s in tracing.drain()[0]] == ["engine.retire",
                                                     "engine.step"]


def test_on_while_the_profiler_traces(tmp_path):
    tracing.drain()
    assert tracing.span("x") is tracing.OFF
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("engine.step"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert tracing.span("y") is tracing.OFF
    assert [s.name for s in tracing.drain()[0]] == ["engine.step"]


# -- the engine and the runner ------------------------------------------------

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                  dtype="float32",
                  quant=QuantConfig(mode="vp", quantize_kv_cache=True,
                                    kv_layout="packed"))


@pytest.fixture(scope="module")
def served():
    """A small engine run with the recorder on: two requests, then a
    third that reuses their programs."""
    params = quantize_params(init_params(jax.random.PRNGKey(0), CFG), CFG)
    eng = ServingEngine(params, CFG, max_slots=2, capacity=32, page_size=8,
                        clock=VirtualClock())
    tracing.disable()
    tracing.drain()
    tracing.enable()
    try:
        eng.submit([1, 2, 3, 4], 4, 0.0)
        eng.submit([5, 6, 7], 3, 0.0)
        eng.run()
        eng.submit([9, 8, 7, 6], 3, 0.0)
        eng.run()
    finally:
        tracing.disable()
    spans, counts = tracing.drain()
    return eng, spans, counts


def _ancestors(span, by_call):
    out = []
    while span.parent is not None:
        span = by_call[span.parent]
        out.append(span.name)
    return out


def test_each_decode_call_has_dispatch_wait_and_fetch(served):
    _, spans, _ = served
    by_call = {s.call: s for s in spans}
    decodes = [s for s in spans if s.name == "runner.decode"]
    assert decodes
    for dec in decodes:
        kids = sorted(s.name for s in spans if s.parent == dec.call)
        assert kids == ["runner.decode.dispatch", "runner.decode.fetch",
                        "runner.decode.wait"]
        assert _ancestors(dec, by_call) == ["engine.decode", "engine.step"]
        assert dec.attrs["rows"] <= dec.attrs["Bp"]
    engine_decodes = [s for s in spans if s.name == "engine.decode"]
    assert len(engine_decodes) == len(decodes)
    assert all(len(s.attrs["rids"]) == s.attrs["rows"] == len(s.attrs["live"])
               for s in engine_decodes)
    prefills = [s for s in spans if s.name == "runner.prefill"]
    assert len(prefills) == 3
    for pre in prefills:
        assert _ancestors(pre, by_call) == ["engine.prefill", "engine.step"]
        assert {s.name for s in spans if s.parent == pre.call} == {
            "runner.prefill.dispatch", "runner.prefill.wait",
            "runner.prefill.fetch"}
    for name in ("engine.admit", "engine.retire"):
        assert all(_ancestors(s, by_call) == ["engine.step"]
                   for s in spans if s.name == name)


def test_compiles_count_one_per_program_built(served):
    eng, spans, counts = served
    built = {k: v for k, v in eng.stats.items() if k.startswith("compiles.")}
    assert built == counts
    assert set(built) == {"compiles." + n for n in eng.runner._programs}
    assert set(built.values()) == {1}
    # the third request reuses a prompt length: no new prefill program
    assert sum(k.startswith("compiles.prefill_s") for k in built) == 2
    for kind in ("runner.decode", "runner.prefill"):
        firsts = [s for s in spans if s.name == kind and s.attrs["first"]]
        prefix = "compiles.decode_b" if kind == "runner.decode" \
            else "compiles.prefill_s"
        assert len(firsts) == sum(k.startswith(prefix) for k in built)


@pytest.fixture(scope="module")
def texts(served):
    return served[0].runner.compiled_text()


def test_programs_are_named(texts):
    assert set(texts) == {"decode_b1_s1", "decode_b2_s1", "prefill_s3",
                          "prefill_s4"}
    for name, text in texts.items():
        assert module_name(text) == f"jit_{name}"


def _scopes_of(text, scopes):
    return {innermost(path, scopes) for path in scope_map(text).values()}


def test_decode_text_maps_instructions_to_its_scopes(texts):
    scopes = ("gather", "model", "kv_append", "sample", "commit")
    found = _scopes_of(texts["decode_b2_s1"], scopes)
    assert {"kv_append", "commit", "sample", "model"} <= found
    # packed pages are read in place: the decode gathers nothing
    assert "gather" not in found
    assert "commit" in _scopes_of(texts["prefill_s4"], scopes)


def test_gathered_decode_keeps_its_gather_scope():
    """The planes cache (a golden baseline) keeps the gathered view."""
    cfg = dataclasses.replace(CFG, quant=dataclasses.replace(
        CFG.quant, kv_layout="planes"))
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg), cfg)
    eng = ServingEngine(params, cfg, max_slots=2, capacity=32, page_size=8,
                        clock=VirtualClock())
    assert not eng.runner.paged_decode
    eng.submit([1, 2, 3], 2, 0.0)
    eng.run()
    found = _scopes_of(eng.runner.compiled_text()["decode_b1_s1"],
                       ("gather", "model", "kv_append", "sample", "commit"))
    assert {"gather", "kv_append", "commit", "sample", "model"} <= found


def test_decode_span_counts_the_pages_it_reads(served):
    """`runner.decode` says it read pages in place, and how many pages
    its rows occupy after the call: ceil((cache length + steps) / page)
    summed over the active rows, the cache length being the live tokens
    less the one fed this step."""
    eng, spans, _ = served
    ps = eng.kv.page_size
    by_call = {s.call: s for s in spans}
    decodes = [s for s in spans if s.name == "runner.decode"]
    assert decodes
    for dec in decodes:
        live = by_call[dec.parent].attrs["live"]
        steps = dec.attrs["steps"]
        assert dec.attrs["paged"] is True
        assert dec.attrs["pages"] == sum(
            -(-(n - 1 + steps) // ps) for n in live)


def test_equalizer_text_maps_instructions_to_its_scopes():
    from repro.mimo import table1_specs
    from repro.mimo.ofdm import equalize_wideband

    base = next(s for s in table1_specs() if s.name == "B-VP")
    S, n, U, B = 2, 2, 8, 64
    specs = [dataclasses.replace(base, w_gain=1.0 + s, y_gain=2.0)
             for s in range(S)]
    w = jnp.zeros((S, n, U, B), jnp.complex64)
    y = jnp.zeros((S, n, B), jnp.complex64)
    # the chip's tiles: K and N padded to 128 lanes, as on a TPU
    step = jax.jit(lambda w, y: equalize_wideband(
        specs, w, y, how="flat", interpret=True, blocks=(16, 128, 128)))
    text = step.lower(w, y).compile().as_text()
    found = _scopes_of(text, ("operands", "pad", "combine"))
    assert {"operands", "pad", "combine"} <= found
