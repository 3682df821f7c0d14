"""Closed loop: ``clients`` clients with no think time; each sends its next
request as soon as its last one finishes.

Requests come in waves of ``clients``, each wave with the same stratified
lengths in a fixed order of its own.  Set-up submits the first wave and prefills it,
so the window opens in steady decoding.  ``attempted`` counts the requests
in flight at any time in the window; ``failed`` those that errored.  The
window's metrics count only the tokens observed inside it, so there is no
drain.
"""
from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

from bench import traffic as traffic_lib
from bench.session import Job, Window, clock


def running_range(spec: dict, max_batch: int) -> tuple:
    n = int(spec["clients"])
    if n > max_batch:
        raise ValueError(f"{n} clients but max_batch {max_batch}")
    return n, n


def wave(spec: dict, seed: int, index: int, vocab: int) -> list:
    """The ``index``-th wave of ``clients`` requests."""
    n = int(spec["clients"])
    p_len = traffic_lib.shuffled(traffic_lib.stratified(spec["prompt"], n),
                                 4, index)
    o_len = traffic_lib.shuffled(traffic_lib.stratified(spec["output"], n),
                                 5, index)
    prompts = traffic_lib.prompts(np.random.default_rng([seed, 4, index]),
                                  [int(x) for x in p_len], vocab)
    return [Job(prompt=p, max_new=int(o)) for p, o in zip(prompts, o_len)]


class _Source:
    """The next job, wave after wave."""

    def __init__(self, spec, seed, vocab):
        self.spec, self.seed, self.vocab = spec, seed, vocab
        self.index, self.pending = 0, []

    def next(self) -> Job:
        if not self.pending:
            self.pending = wave(self.spec, self.seed, self.index, self.vocab)
            self.index += 1
        return self.pending.pop(0)


def setup(ctx) -> None:
    """Submit the first wave and step until every request of it has its
    first token."""
    ctx.source = _Source(ctx.traffic, ctx.seed, ctx.vocab)
    s = ctx.session
    first = [s.submit(ctx.source.next(), due=clock())
             for _ in range(int(ctx.traffic["clients"]))]
    while any(r.first_token is None for r in first):
        for r in s.step().finished:
            s.submit(ctx.source.next(), due=clock())


def window(ctx) -> Window:
    s = ctx.session
    m_start = s.engine.metrics()
    n_steps0 = len(s.steps)
    counted = s.live()
    t0 = clock()
    t_end = t0 + ctx.seconds
    with TraceAnnotation("bench.window"):
        while clock() < t_end:
            step = s.step()
            if step.end < t_end:
                for _ in step.finished:
                    counted.append(s.submit(ctx.source.next(),
                                            due=step.end))
    t_last = s.steps[-1].end
    m_end = s.engine.metrics()
    lines = [f"clients: {ctx.traffic['clients']}, {len(counted)} requests "
             f"in flight during the window, "
             f"{len(s.steps) - n_steps0} steps"]
    return Window(t0=t0, t_end=t_end, t_last=t_last, records=counted,
                  steps=s.steps[n_steps0:], attempted=len(counted), failed=0,
                  m_start=m_start, m_end=m_end, closed=t_last, lines=lines)
