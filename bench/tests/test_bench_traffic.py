"""The seeded generators: deterministic per seed, stratified as stated."""
from __future__ import annotations

import numpy as np
import pytest

from bench import traffic

SEEDS = (1, 2, 3_000_000_123)


def _kind(name):
    return traffic.kind_module(traffic.load(name))


@pytest.mark.parametrize("name", ["chat-smollm-360m"])
def test_open_loop_is_deterministic_and_stratified(name):
    spec = traffic.load(name)
    assert spec["kind"] == "open_loop" and "rate_per_s" in spec
    kind = _kind(name)
    seconds = 30.0
    runs = {s: kind.jobs(spec, s, seconds, vocab=1000) for s in SEEDS}
    again = kind.jobs(spec, SEEDS[0], seconds, vocab=1000)
    a = runs[SEEDS[0]]
    assert [len(j.prompt) for j in a] == [len(j.prompt) for j in again]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, again))
    assert [j.offset for j in a] == [j.offset for j in again]
    n = int(spec["rate_per_s"] * seconds)
    want_p = sorted(traffic.stratified(spec["prompt"], n))
    want_o = sorted(traffic.stratified(spec["output"], n))
    for jobs in runs.values():
        assert len(jobs) == n
        # every seed sends the same lengths, in another order
        assert sorted(len(j.prompt) for j in jobs) == want_p
        assert sorted(j.max_new for j in jobs) == want_o
        offs = [j.offset for j in jobs]
        assert offs == sorted(offs) and 0 < offs[0] and offs[-1] < seconds
    # one schedule for every seed; the seed draws the token ids
    orders = {tuple((len(j.prompt), j.max_new, j.offset) for j in jobs)
              for jobs in runs.values()}
    assert len(orders) == 1
    assert not (runs[SEEDS[0]][0].prompt == runs[SEEDS[1]][0].prompt).all()


def test_open_loop_gaps_are_stratified():
    spec = traffic.load("chat-smollm-360m")
    kind = _kind("chat-smollm-360m")
    n = int(spec["rate_per_s"] * 40.0)
    offs = [j.offset for j in kind.jobs(spec, 9, 40.0, vocab=100)]
    gaps = sorted(np.diff([0.0] + offs))
    want = sorted(np.asarray(traffic.stratified_gaps(
        n, spec["rate_per_s"])) * (n - 0.5) / n)
    assert np.allclose(gaps, want)


def test_closed_loop_waves():
    spec = traffic.load("decode")
    kind = _kind("decode")
    n = spec["clients"]
    w0 = kind.wave(spec, 7, 0, vocab=1000)
    assert len(w0) == n
    again = kind.wave(spec, 7, 0, vocab=1000)
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(w0, again))
    for i in range(3):
        waves = [kind.wave(spec, s, i, vocab=1000) for s in SEEDS]
        w = waves[0]
        assert sorted(len(j.prompt) for j in w) == sorted(
            traffic.stratified(spec["prompt"], n))
        assert sorted(j.max_new for j in w) == sorted(
            traffic.stratified(spec["output"], n))
        # every seed: the same lengths in the same order, other token ids
        assert len({tuple((len(j.prompt), j.max_new) for j in v)
                    for v in waves}) == 1
        assert not (waves[0][0].prompt == waves[1][0].prompt).all()
    assert ([len(j.prompt) for j in kind.wave(spec, 1, 0, 1000)]
            != [len(j.prompt) for j in kind.wave(spec, 1, 1, 1000)])
    assert kind.running_range(spec, 32) == (32, 32)
    with pytest.raises(ValueError):
        kind.running_range(spec, 16)


@pytest.mark.parametrize("dist, n", [
    ({"median": 256, "sigma": 1.0, "min": 32, "max": 1024}, 24),
    ({"median": 384, "sigma": 0.6, "min": 128, "max": 1024}, 32),
])
def test_stratified_lengths(dist, n):
    xs = traffic.stratified(dist, n)
    assert xs == sorted(xs) and len(xs) == n
    assert dist["min"] <= xs[0] and xs[-1] <= dist["max"]
    mid = xs[n // 2 - 1: n // 2 + 1]
    assert min(mid) <= dist["median"] <= max(mid) * 1.2


def test_stratified_gaps_sum():
    g = traffic.stratified_gaps(10, 0.5)
    assert sum(g) == pytest.approx(20.0)
    assert g == sorted(g)


def test_pool_positions_and_base_merge():
    chat = traffic.load("chat-smollm-360m")
    assert traffic.max_positions(chat) == 1024 + 512
    assert traffic.max_positions(traffic.load("decode")) == 512 + 1024
    base = traffic.load("chat")
    assert {k: v for k, v in chat.items() if k != "rate_per_s"} == base
