"""Open loop: requests arrive on a fixed schedule, whether or not earlier
ones are done.

``rate_per_s`` fixes the schedule: ``floor(rate x seconds)`` requests, with
stratified exponential gaps in a fixed order summing to less than the
window.  Each request is timed from when it was due.  After the window no
more are sent; the engine steps on until every request sent in it has its
first token, for at most ``drain_s`` seconds.  ``attempted`` counts the
requests sent; ``failed`` those with no first token by the drain's end.
"""
from __future__ import annotations

import math

import numpy as np
from jax.profiler import TraceAnnotation

from bench import traffic as traffic_lib
from bench.session import Job, Window, clock


def running_range(spec: dict, max_batch: int) -> tuple:
    """Fewest and most requests admitted at once."""
    return 1, max_batch


def jobs(spec: dict, seed: int, seconds: float, vocab: int) -> list:
    """The window's requests, in order of due time."""
    n = max(int(math.floor(spec["rate_per_s"] * seconds)), 1)
    p_len = traffic_lib.shuffled(traffic_lib.stratified(spec["prompt"], n), 1)
    o_len = traffic_lib.shuffled(traffic_lib.stratified(spec["output"], n), 2)
    # gaps sum to (n - 0.5) / rate < seconds: all n fall in the window
    gaps = traffic_lib.shuffled(
        traffic_lib.stratified_gaps(n, spec["rate_per_s"]), 3) * (n - 0.5) / n
    prompts = traffic_lib.prompts(np.random.default_rng([seed, 2]),
                                  [int(x) for x in p_len], vocab)
    due = np.cumsum(gaps)
    return [Job(prompt=p, max_new=int(o), offset=float(t))
            for p, o, t in zip(prompts, o_len, due)]


def setup(ctx) -> None:
    """Draw the schedule and run one short request through the smallest
    step shape, so the host's own first-use work is done before the
    window."""
    ctx.jobs = jobs(ctx.traffic, ctx.seed, ctx.seconds, ctx.vocab)
    rng = np.random.default_rng([ctx.seed, 3])
    warm = Job(prompt=rng.integers(0, ctx.vocab, (8,), dtype=np.int32),
               max_new=2)
    ctx.session.submit(warm, due=clock())
    while ctx.session.busy:
        ctx.session.step()


def window(ctx) -> Window:
    s, jobs_ = ctx.session, ctx.jobs
    m_start = s.engine.metrics()
    n_steps0 = len(s.steps)
    t0 = clock()
    t_end = t0 + ctx.seconds
    sent, i = [], 0
    with TraceAnnotation("bench.window"):
        while True:
            now = clock()
            while i < len(jobs_) and t0 + jobs_[i].offset <= now:
                sent.append(s.submit(jobs_[i], due=t0 + jobs_[i].offset))
                i += 1
            if now >= t_end:
                break
            if s.busy:
                s.step()
            else:
                nxt = t0 + jobs_[i].offset if i < len(jobs_) else t_end
                s.wait_until(min(nxt, t_end))
    t_last = s.steps[-1].end if len(s.steps) > n_steps0 else t0
    m_end = s.engine.metrics()
    steps = s.steps[n_steps0:]
    cap = clock() + float(ctx.traffic["drain_s"])
    while any(r.first_token is None for r in sent) and clock() < cap:
        s.step()
    closed = clock()
    failed = sum(r.first_token is None for r in sent)
    late = [r.sent - r.due for r in sent]
    lines = [f"generator: {len(sent)} requests sent, lateness (sent - due) "
             f"mean {np.mean(late) * 1e3:.1f} ms, max "
             f"{max(late) * 1e3:.1f} ms",
             f"drain: {closed - t_last:.2f} s after the window's last step, "
             f"{failed} without a first token"]
    return Window(t0=t0, t_end=t_end, t_last=t_last, records=sent,
                  steps=steps, attempted=len(sent), failed=failed,
                  m_start=m_start, m_end=m_end, closed=closed, lines=lines)
