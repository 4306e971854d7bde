"""A run with its timed path broken underneath must come out not
correct.  Each test drives the rest of a run (set-up, window, reference
comparison) on the CPU at a small size, past the harness's look for a
chip, with one fault planted where the program produces its answers.

Faults the cells can have: a token or an answer altered where it is
produced; a decode step that returns its cache state unchanged; half of
a slot's band left out.  Neither cell exchanges anything between chips.
"""
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import small


def _serve(cell, seed=3):
    result, _ = run.execute(cell, seed, 1.5, False)
    return result


def test_sound_serving_run_is_correct():
    result = _serve(small.serving_cell())
    assert result["correct"], result["checks"]


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving import runner

    decode = runner.ModelRunner.decode_batch
    calls = []

    def altered(self, params, slot_tokens, key, steps=1):
        out = decode(self, params, slot_tokens, key, steps)
        calls.append(1)
        # one slot a call, in turn: every request gets some tokens that
        # are the least likely one, not the argmax
        slot = sorted(out)[len(calls) % len(out)]
        toks, logits = out[slot]
        out[slot] = ([int(logits[0].argmin())] + toks[1:], logits)
        return out

    monkeypatch.setattr(runner.ModelRunner, "decode_batch", altered)
    result = _serve(small.serving_cell())
    assert calls
    assert not result["correct"], result["checks"]


def test_decode_that_keeps_its_state_is_not_correct(monkeypatch):
    from repro.serving import runner

    decode = runner.ModelRunner.decode_batch

    def stale(self, params, slot_tokens, key, steps=1):
        kv = self.kv
        pools, lengths = dict(kv.pools), kv.lengths
        out = decode(self, params, slot_tokens, key, steps)
        kv.pools, kv.lengths = pools, lengths     # cache never committed
        return out

    monkeypatch.setattr(runner.ModelRunner, "decode_batch", stale)
    result = _serve(small.serving_cell())
    assert not result["correct"], result["checks"]


def _equalize(cell, seed=4):
    result, _ = run.execute(cell, seed, 0.5, False)
    return result


def test_sound_equalizer_run_is_correct():
    result = _equalize(small.equalizer_cell())
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("fault", ["altered_answer", "half_band_left_out"])
def test_broken_equalizer_is_not_correct(monkeypatch, fault):
    from repro.mimo import ofdm

    equalize = ofdm.equalize_wideband

    def broken(*args, **kwargs):
        s = equalize(*args, **kwargs)               # (S, T, U)
        if fault == "altered_answer":
            return s.at[0, 0, 0].multiply(-1.0)
        half = s.shape[0] // 2
        return s.at[half:].set(jnp.zeros_like(s[half:]))

    monkeypatch.setattr(ofdm, "equalize_wideband", broken)
    result = _equalize(small.equalizer_cell())
    assert not result["correct"], result["checks"]
