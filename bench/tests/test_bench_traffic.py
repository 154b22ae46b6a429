"""The traffic generator: one stream of work for every seed, its own ids."""

import json
from pathlib import Path

import numpy as np

from bench.generators import load_generator

BENCH = Path(__file__).resolve().parents[1]
MIX = json.loads((BENCH / "traffic" / "azure_mixed.json").read_text())
gen = load_generator(MIX["generator"])


def _stream(seed, until=20.0, slice_s=0.25, mix=MIX):
    t = gen.Traffic(mix, seed, 151936)
    reqs, now = [], 0.0
    while now < until:
        got = t.take(now + slice_s)
        reqs += [(now + dt, c, toks, d) for dt, c, toks, d in got]
        now += slice_s
    return reqs, t


def test_every_seed_gets_the_same_sizes_and_arrivals():
    a, _ = _stream(11)
    b, _ = _stream(2**31 + 5)
    assert len(a) == len(b) > 100
    assert [(round(t, 9), c, len(x), d) for t, c, x, d in a] == [
        (round(t, 9), c, len(x), d) for t, c, x, d in b]


def test_the_seed_draws_the_token_ids():
    a, _ = _stream(11, until=2.0)
    b, _ = _stream(12, until=2.0)
    again, _ = _stream(11, until=2.0)
    assert any(not np.array_equal(x[2], y[2]) for x, y in zip(a, b))
    assert all(np.array_equal(x[2], y[2]) for x, y in zip(a, again))
    assert all(0 <= x[2].min() and x[2].max() < 151936 for x in a)


def test_lengths_respect_the_cap_and_the_shares():
    t = gen.Traffic(MIX, 1, 151936)
    draws = [t._draw(0.0) for _ in range(20000)]
    cls = np.array([d[1] for d in draws])
    P = np.array([d[2] for d in draws])
    D = np.array([d[3] for d in draws])
    assert (P + D <= MIX["max_total_len"]).all()
    assert (P >= MIX["min_prompt"]).all() and (D >= MIX["min_decode"]).all()
    shares = np.bincount(cls) / len(cls)
    assert np.allclose(shares, [c["share"] for c in MIX["classes"]],
                       atol=0.02)
    for i, c in enumerate(MIX["classes"]):
        assert abs(np.mean(D[cls == i]) / c["mean_decode"] - 1) < 0.1
    clipped = np.mean([d[4] for d in draws])
    assert 0 < clipped < 0.05


def test_clipped_requests_are_counted_as_taken():
    reqs, t = _stream(3, until=60.0)
    assert t.clipped == sum(1 for _, _, x, d in reqs
                            if len(x) + d == MIX["max_total_len"])


def test_arrivals_are_poisson_at_the_rate():
    reqs, _ = _stream(5, until=400.0)
    gaps = np.diff([0.0] + [r[0] for r in reqs])
    rate = 1.0 / gaps.mean()
    assert abs(rate / MIX["rate_per_s"] - 1) < 0.05
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05  # exponential: cv 1


def test_slicing_does_not_change_the_stream():
    coarse, _ = _stream(5, slice_s=1.0)
    fine, _ = _stream(5, slice_s=0.1)
    assert [(round(t, 9), c, d) for t, c, _, d in coarse] == [
        (round(t, 9), c, d) for t, c, _, d in fine]
