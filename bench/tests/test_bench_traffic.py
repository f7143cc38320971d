"""The traffic generator: deterministic per seed, the same work for every
seed in another order, inside the mix's stated ranges and loop type."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.generator import Traffic, exp_gaps, length_set, median_of

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
BIG_SEED = 2**31 + 12345          # seeds may pass 32 signed bits


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def sample(t: Traffic, n: int = 200):
    if t.loop == "open":
        return t.schedule()
    out = []
    for c in range(t.clients):
        g = t.client_requests(c)
        out += [next(g) for _ in range(max(1, n // t.clients))]
    return out


def as_tuples(reqs):
    return [(r.prompt.tolist(), r.max_new, r.send_at) for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = sample(Traffic(mix(name), BIG_SEED, 50304, 40))
    b = sample(Traffic(mix(name), BIG_SEED, 50304, 40))
    assert as_tuples(a) == as_tuples(b)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_order_same_work(name):
    m = mix(name)
    a = Traffic(m, 11, 50304, 40)
    b = Traffic(m, 12, 50304, 40)
    assert as_tuples(sample(a)) != as_tuples(sample(b))
    # the same multiset of lengths (and, open loop, of gaps) for every seed
    assert sorted(a._lengths) == sorted(b._lengths)
    if a.loop == "open":
        np.testing.assert_allclose(np.sort(np.diff(a._send_at, prepend=0)),
                                   np.sort(np.diff(b._send_at, prepend=0)))


@pytest.mark.parametrize("name", MIXES)
def test_inside_stated_ranges(name):
    m = mix(name)
    vocab = 50280
    t = Traffic(m, BIG_SEED, vocab, 40)
    assert t.loop == m["loop"] and t.clients == m["clients"]
    for r in sample(t):
        p, o = m["prompt_tokens"], m["output_tokens"]
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert 1 <= r.max_new <= o["max"]
        assert len(r.prompt) + r.max_new <= m["max_total_tokens"]
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < vocab
        if t.loop == "open":
            assert 0 < r.send_at < t.preroll_s + t.seconds
        else:
            assert r.send_at is None


def test_open_loop_rate_and_window():
    m = mix("chat")
    t = Traffic(m, 3, 50304, 40)
    s = t.schedule()
    span = t.preroll_s + t.seconds
    assert abs(len(s) - m["rate_per_s"] * span) <= 2
    assert all(a.send_at < b.send_at for a, b in zip(s, s[1:]))


def test_closed_loop_requests_are_distinct():
    t = Traffic(mix("decode"), 3, 50304, 40)
    g = t.client_requests(0)
    # past the end of the pool: same lengths again, fresh prompts
    reqs = [next(g) for _ in range(70)]
    prompts = {tuple(r.prompt.tolist()) for r in reqs}
    assert len(prompts) == len(reqs)


def test_quantile_sets():
    spec = {"published_median": 400, "sigma": 0.5, "min": 10, "max": 400}
    v = length_set(spec, 101, 0.25)
    assert v[50] == 100 and v.min() >= 10 and v.max() <= 400
    assert np.all(np.diff(v) >= 0)
    g = exp_gaps(2.0, 1000)
    assert abs(g.mean() - 0.5) < 0.02


def test_published_mean_gives_the_lognormal_median():
    spec = {"published_mean": 100.0, "sigma": 0.8}
    assert median_of(spec, 1.0) == pytest.approx(100.0 * np.exp(-0.32))
    v = length_set(dict(spec, min=1, max=10**6), 20001, 1.0)
    assert v.mean() == pytest.approx(100.0, rel=0.01)


@pytest.mark.parametrize("name", MIXES)
def test_one_scale_keeps_the_published_ratio(name):
    """Prompt and answer are cut by the same stated scale, the largest at
    which the published 95th-percentile prompt plus 95th-percentile answer
    fits the positions served, rounded down to a hundredth."""
    m = mix(name)
    scale = m["length_scale"]
    assert set(m["reduced"]) == {"length_scale"}
    p, o = m["prompt_tokens"], m["output_tokens"]
    assert median_of(p, scale) / median_of(o, scale) == \
        pytest.approx(median_of(p, 1.0) / median_of(o, 1.0))
    p95 = sum(median_of(s, 1.0) * np.exp(1.645 * s["sigma"]) for s in (p, o))
    assert scale <= m["max_total_tokens"] / p95 < scale + 0.01
    assert p["max"] < m["max_total_tokens"] and o["min"] >= 1
