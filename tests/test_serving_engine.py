"""Continuous-batching engine suite (PR 7).

The contract under test: the paged engine is a SCHEDULER, not a second
model — every token it emits must be bit-identical (on the jnp ref
backend) to the static per-request driver it replaces, for every quant
mode x KV-cache layout of the PR-4 golden matrix, for ragged prompts,
staggered arrivals, slot eviction/readmission, chunked prefill, and
fused decode run-ahead.  Alongside parity: allocator properties (page
disjointness, eviction returns pages, ragged lengths never read freed
or unwritten storage — pinned by poisoning page 0 and the whole free
list) and the PRNG-hygiene regressions from the serve-path fixes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ModelConfig, QuantConfig
from repro.kernels import substrate
from repro.models import (
    init_params, init_cache, prefill, decode_step, quantize_params,
)
from repro.serving import PagedKVCache, ServingEngine, VirtualClock
from repro.serving.profile import panel_keys

REF_BACKEND = substrate.resolve_backend(None) == "ref"

# Ragged prompts, ragged budgets, one late arrival; max_slots=2 forces
# queueing, eviction, and slot reuse with 3 requests.
REQS = [([1, 2, 3, 4, 5], 4, 0.0),
        (list(range(7)), 5, 0.0),
        ([9, 8, 7], 3, 0.05)]
CAP, PAGE, SLOTS = 24, 8, 2

# Weight-quant mode x KV-cache storage: the PR-4 golden matrix extended
# with the KV axis (KV quantization is independent of weight mode).
MATRIX = [(mode, kv)
          for mode in ("none", "fxp", "vp", "vp_block")
          for kv in ("float", "packed", "planes")]


def _quant(mode: str, kv: str) -> QuantConfig:
    kw = dict(mode=mode)
    if mode == "vp_block":
        kw["block"] = 16
    if kv != "float":
        kw.update(quantize_kv_cache=True, kv_layout=kv)
    return QuantConfig(**kw)


def _tiny_cfg(quant: QuantConfig) -> ModelConfig:
    return ModelConfig(name="tiny", family="dense", n_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab=128, dtype="float32", quant=quant)


def _params(cfg):
    p = init_params(jax.random.PRNGKey(0), cfg)
    return quantize_params(p, cfg) if cfg.quant.mode != "none" else p


def _oracle_tokens(params, cfg, prompt, gen, cap=CAP):
    """Static per-request driver: B=1 prefill + greedy decode loop at
    max_len == the engine capacity (same mask span => same bits)."""
    caches = init_cache(cfg, 1, cap)
    logits, caches = prefill(
        params, jnp.asarray([prompt], jnp.int32), caches, cfg)
    toks = [int(np.asarray(logits).reshape(1, -1).argmax(-1)[0])]
    for _ in range(gen - 1):
        logits, caches = decode_step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), caches, cfg)
        toks.append(int(np.asarray(logits).reshape(1, -1).argmax(-1)[0]))
    return toks


def _engine_tokens(params, cfg, reqs=REQS, **kw):
    kw.setdefault("max_slots", SLOTS)
    kw.setdefault("capacity", CAP)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("clock", VirtualClock())
    eng = ServingEngine(params, cfg, **kw)
    for prompt, gen, at in reqs:
        eng.submit(prompt, gen, at)
    return eng, [r["tokens"] for r in eng.run()]


def _assert_token_parity(got, reqs, params, cfg, cap=CAP):
    for (prompt, gen, _), toks in zip(reqs, got):
        assert len(toks) == gen
        if REF_BACKEND:
            want = _oracle_tokens(params, cfg, prompt, gen, cap)
            assert toks == want, (toks, want)


# -- engine == static, over the full quant x KV matrix -------------------


@pytest.mark.parametrize("mode,kv", MATRIX)
def test_engine_static_parity_matrix(mode, kv):
    cfg = _tiny_cfg(_quant(mode, kv))
    params = _params(cfg)
    _, got = _engine_tokens(params, cfg)
    _assert_token_parity(got, REQS, params, cfg)


@pytest.mark.parametrize("arch", ["zamba2-7b", "rwkv6-3b",
                                  "mixtral-8x22b", "qwen3-moe-30b-a3b"])
def test_engine_family_parity(arch):
    """Hybrid (mamba+attn), pure SSM, sliding-window+MoE: the dense
    ring / recurrent-state rows must round-trip through the engine's
    slot gather/commit exactly."""
    cfg = registry.get_smoke_config(arch)
    params = _params(cfg)
    _, got = _engine_tokens(params, cfg)
    _assert_token_parity(got, REQS, params, cfg)


def test_engine_rejects_encdec():
    cfg = registry.get_smoke_config("whisper-tiny")
    params = _params(cfg)
    with pytest.raises(ValueError, match="encoder-decoder"):
        ServingEngine(params, cfg, max_slots=SLOTS, capacity=CAP,
                      page_size=PAGE)


@pytest.mark.parametrize("mode,kv",
                         [("none", "float"), ("vp", "packed")])
def test_chunked_prefill_token_match(mode, kv):
    """Chunked prefill reassociates the prompt attention reduction, so
    the contract is token-level agreement, not bit-identity."""
    cfg = _tiny_cfg(_quant(mode, kv))
    params = _params(cfg)
    _, got = _engine_tokens(params, cfg, prefill_chunk=4)
    _assert_token_parity(got, REQS, params, cfg)


def test_chunked_prefill_rejected_for_windowed():
    cfg = registry.get_smoke_config("mixtral-8x22b")
    params = _params(cfg)
    with pytest.raises(ValueError, match="full-causal"):
        ServingEngine(params, cfg, max_slots=SLOTS, capacity=CAP,
                      page_size=PAGE, prefill_chunk=4)


def test_decode_lookahead_parity():
    """Fused run-ahead is dispatch amortization, not different math:
    any lookahead must emit the same tokens, with over-generation
    trimmed to each request's budget."""
    cfg = _tiny_cfg(_quant("vp", "packed"))
    params = _params(cfg)
    outs = [_engine_tokens(params, cfg, decode_lookahead=la)[1]
            for la in (1, 3, 4)]
    assert outs[0] == outs[1] == outs[2]
    _assert_token_parity(outs[0], REQS, params, cfg)


# -- allocator / isolation properties ------------------------------------


def test_poisoned_free_pages_never_read():
    """Garbage in the dummy page 0 AND in every free page must be
    invisible: pages are handed out as-is (admission never clears or
    copies), so any read past a request's committed span — or from a
    page freed by eviction and reused by a later request — would change
    tokens here."""
    cfg = _tiny_cfg(_quant("vp", "packed"))
    params = _params(cfg)
    _, clean = _engine_tokens(params, cfg)

    eng = ServingEngine(params, cfg, max_slots=SLOTS, capacity=CAP,
                        page_size=PAGE, clock=VirtualClock())
    pages = jnp.asarray([0] + list(eng.kv.free_pages), jnp.int32)
    for k, pool in eng.kv.pools.items():
        poison = (jnp.iinfo(pool.dtype).max
                  if jnp.issubdtype(pool.dtype, jnp.integer) else 1e30)
        eng.kv.pools[k] = pool.at[:, pages].set(poison)
    for prompt, gen, at in REQS:
        eng.submit(prompt, gen, at)
    got = [r["tokens"] for r in eng.run()]
    assert got == clean


def test_allocated_page_sets_disjoint():
    cfg = _tiny_cfg(_quant("vp", "packed"))
    kv = PagedKVCache(cfg, max_slots=3, capacity=CAP, page_size=PAGE)
    total = kv.n_pages - 1
    owned = {}
    for total_len in (5, 16, 24):
        slot = kv.alloc(total_len)
        row = np.asarray(kv.block_table[slot])
        used = row[:kv.pages_needed(total_len)]
        assert (used > 0).all(), "allocated a reserved/dummy page"
        owned[slot] = set(used.tolist())
    sets = list(owned.values())
    for i in range(len(sets)):
        for j in range(i + 1, len(sets)):
            assert not (sets[i] & sets[j]), "page aliasing across slots"
    assert len(kv.free_pages) == total - sum(len(s) for s in sets)


def test_eviction_returns_pages():
    cfg = _tiny_cfg(_quant("vp", "packed"))
    params = _params(cfg)
    eng, _ = _engine_tokens(params, cfg)
    # every request retired => allocator fully drained back
    assert len(eng.kv.free_pages) == eng.kv.n_pages - 1
    assert not eng.scheduler.running and not eng.scheduler.waiting


def test_oversized_request_rejected():
    cfg = _tiny_cfg(_quant("vp", "packed"))
    params = _params(cfg)
    eng = ServingEngine(params, cfg, max_slots=SLOTS, capacity=CAP,
                        page_size=PAGE, clock=VirtualClock())
    eng.submit(list(range(CAP)), 8, 0.0)   # prompt + gen > capacity
    with pytest.raises(ValueError, match="capacity"):
        eng.run()


def test_check_finite_raises_on_overflow():
    cfg = _tiny_cfg(QuantConfig(mode="none"))
    params = dict(_params(cfg))
    # inf weights -> nan logits (signed-inf cancellation in the matmul)
    params["lm_head"] = jnp.full_like(params["lm_head"], jnp.inf)
    eng = ServingEngine(params, cfg, max_slots=SLOTS, capacity=CAP,
                        page_size=PAGE, clock=VirtualClock(),
                        check_finite=True, on_nonfinite="raise")
    eng.submit([1, 2, 3], 4, 0.0)
    with pytest.raises(FloatingPointError):
        eng.run()


# -- serve-path PRNG hygiene (the bugs the engine flushed out) -----------


def test_panel_keys_distinct_folds():
    """Every benchmark panel gets its own fold and every tensor within
    a panel its own split — no draw may correlate with any other (the
    old serve path reused ONE PRNGKey(0) for params, prompts, and every
    tuning panel)."""
    base = jax.random.PRNGKey(0)
    seen = set()
    for idx in range(4):
        for k in panel_keys(base, idx):
            seen.add(tuple(np.asarray(jax.random.key_data(k)).tolist()))
    seen.add(tuple(np.asarray(jax.random.key_data(base)).tolist()))
    assert len(seen) == 9, "panel key folds collided"


def test_engine_temperature_keys_advance():
    """Sampled decoding must fold a fresh key per step (greedy decoding
    legitimately reuses one key — argmax never consumes it)."""
    cfg = _tiny_cfg(QuantConfig(mode="none"))
    params = _params(cfg)
    eng = ServingEngine(params, cfg, max_slots=SLOTS, capacity=CAP,
                        page_size=PAGE, temperature=0.7,
                        clock=VirtualClock())
    k1, k2 = eng._next_key(), eng._next_key()
    assert not np.array_equal(np.asarray(jax.random.key_data(k1)),
                              np.asarray(jax.random.key_data(k2)))
    greedy = ServingEngine(params, cfg, max_slots=SLOTS, capacity=CAP,
                           page_size=PAGE, clock=VirtualClock())
    assert np.array_equal(
        np.asarray(jax.random.key_data(greedy._next_key())),
        np.asarray(jax.random.key_data(greedy._next_key())))


# -- decode that reads pages in place == decode on the gathered view ------


def _decode_logits(params, cfg, in_place: bool, lookahead: int):
    eng = ServingEngine(params, cfg, max_slots=SLOTS, capacity=32,
                        page_size=PAGE, clock=VirtualClock(),
                        decode_lookahead=lookahead)
    assert eng.runner.paged_decode
    eng.runner.paged_decode = in_place
    calls = []
    decode = eng.runner.decode_batch

    def record(*args):
        out = decode(*args)
        calls.append({s: (toks, np.asarray(lg))
                      for s, (toks, lg) in out.items()})
        return out

    eng.runner.decode_batch = record
    for prompt, gen, at in REQS:
        eng.submit(prompt, gen + 2, at)
    eng.run()
    return calls


@pytest.mark.parametrize("arch,lookahead,E", [
    ("qwen3-0.6b", 1, 2),
    ("qwen3-0.6b", 3, 2),       # run-ahead: the in-flight tail
    ("qwen3-moe-30b-a3b", 1, 2),
    ("zamba2-7b", 3, 2),        # hybrid: SSM rows beside the pages
    ("gemma3-27b", 1, 2),       # dense local rings beside global pages
    ("qwen2-0.5b", 3, 1),       # 8-bit words (in place on this backend)
])
def test_paged_decode_matches_gathered_view(arch, lookahead, E):
    """Every model with paged packed layers reads its pages in place,
    and on the ref backend every decode call's tokens and logits are
    bit-identical to the gathered view's."""
    cfg = dataclasses.replace(
        registry.get_smoke_config(arch),
        quant=QuantConfig(mode="vp", quantize_kv_cache=True,
                          kv_layout="packed", E=E))
    params = _params(cfg)
    got = _decode_logits(params, cfg, True, lookahead)
    want = _decode_logits(params, cfg, False, lookahead)
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        for slot in g:
            assert g[slot][0] == w[slot][0]
            if REF_BACKEND:
                np.testing.assert_array_equal(g[slot][1], w[slot][1])


@pytest.mark.parametrize("E,d_head,in_place", [
    (2, 128, True),             # 16-bit words, 2 heads of 128 lanes
    (1, 128, False),            # 8-bit words: 2 heads fill no 32-bit row
    (2, None, False),           # 16-lane heads: Mosaic copies no page
])
def test_runner_reads_in_place_only_layouts_the_kernel_tiles(
        E, d_head, in_place):
    """On the chip's backend the runner keeps the gathered view for a
    layout the paged kernel cannot tile, instead of reading it wrong."""
    from repro.serving.runner import ModelRunner

    cfg = dataclasses.replace(
        registry.get_smoke_config("qwen3-0.6b"), d_head=d_head,
        quant=QuantConfig(mode="vp", quantize_kv_cache=True,
                          kv_layout="packed", E=E))
    kv = PagedKVCache(cfg, max_slots=SLOTS, capacity=CAP, page_size=PAGE)
    with substrate.force_backend("native"):
        assert ModelRunner(cfg, kv).paged_decode is in_place
