"""The traffic generator: same seed, same requests; every seed the same
sizes; lengths on the grid."""
import itertools
import json

import pytest

from bench import spec, traffic

MIXES = ["decode-heavy"]


def mix(name):
    return json.loads(spec.traffic_path(name).read_text())


def take(m, seed, n):
    return list(itertools.islice(traffic.requests(m, seed, 151936), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    m = mix(name)
    assert take(m, 2 ** 33 + 7, 70) == take(m, 2 ** 33 + 7, 70)
    assert take(m, 1, 70) != take(m, 2, 70)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_on_grid_and_in_range(name):
    m = mix(name)
    for r in take(m, 5, 3 * m["block"]):
        assert len(r.prompt) in m["prompt_grid"]
        assert m["output"]["min"] <= r.max_new_tokens <= m["output"]["max"]
        assert len(r.prompt) + r.max_new_tokens <= m["engine"]["capacity"]
        assert all(0 <= t < 151936 for t in r.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes_per_block(name):
    m = mix(name)
    n = m["block"]
    a, b = take(m, 11, 2 * n), take(m, 12, 2 * n)
    for k in range(2):
        blk_a, blk_b = a[k * n:(k + 1) * n], b[k * n:(k + 1) * n]
        assert sorted(len(r.prompt) for r in blk_a) == \
            sorted(len(r.prompt) for r in blk_b)
        assert sorted(r.max_new_tokens for r in blk_a) == \
            sorted(r.max_new_tokens for r in blk_b)


def test_heavy_tail_toward_short_prompts():
    m = mix("decode-heavy")
    counts = traffic._grid_counts(m["prompt_weights"], m["block"])
    assert sum(counts) == m["block"]
    assert counts == sorted(counts, reverse=True)


def test_lognormal_median_and_clip():
    q = traffic._lognormal_quantiles(384, 0.7, 64, 1536, 32)
    assert q == sorted(q)
    assert q[15] <= 384 <= q[16]
    assert q[0] >= 64 and q[-1] == 1536
