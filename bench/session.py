"""The harness's side of the served path: it submits requests, steps the
engine and timestamps what it observes, all on its own clock.

Only the engine's public surface is used: ``submit``, ``step``, ``busy``,
``metrics`` and the fields of the ``Request`` objects it hands in.  Every
time is ``time.perf_counter()`` taken here, so a change to the program's
own timers cannot move the yardstick.

Host spans (``jax.profiler.TraceAnnotation``) mark what the harness is
doing, so that a traced run can say what the host did in each idle gap of
the device: ``bench.step`` around ``engine.step``, ``bench.generate``
around submissions, ``bench.wait`` while no request is due and
``bench.observe`` around the bookkeeping after a step.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

clock: Callable[[], float] = time.perf_counter


@dataclass
class Job:
    """One request as the traffic generator made it."""

    prompt: np.ndarray
    max_new: int
    offset: float = 0.0            # due time, seconds after the window opens


@dataclass
class Record:
    """What the harness observed of one request."""

    rid: int
    job: Job
    due: float                     # absolute clock time it was due
    sent: float = 0.0              # when it was handed to the engine
    admitted: Optional[float] = None   # start of the step that admitted it
    token_times: List[float] = field(default_factory=list)
    req: object = None             # the engine's Request

    @property
    def first_token(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None

    def tokens(self) -> List[int]:
        return [int(t) for t in self.req.output[:len(self.token_times)]]


@dataclass
class Step:
    """One engine step: host start/end, the per-sequence work
    ``[(q_len, kv_len)]`` it did, and the tokens it produced."""

    start: float
    end: float
    seqs: List[Tuple[int, int]]
    sampled: int
    finished: List[Record] = field(default_factory=list)


class Session:
    """Drives one engine and records every request and step."""

    def __init__(self, engine, request_cls, request_state):
        self.engine = engine
        self._Request = request_cls
        self._State = request_state
        self.records: List[Record] = []
        self.steps: List[Step] = []
        self._live: Dict[int, Record] = {}
        self._next_id = 0

    # ------------------------------------------------------------ requests
    def submit(self, job: Job, due: float) -> Record:
        with TraceAnnotation("bench.generate"):
            rec = Record(rid=self._next_id, job=job, due=due)
            self._next_id += 1
            rec.req = self._Request(req_id=rec.rid, prompt=job.prompt,
                                    max_new_tokens=job.max_new)
            rec.sent = clock()
            self.engine.submit(rec.req)
            self.records.append(rec)
            self._live[rec.rid] = rec
        return rec

    @property
    def busy(self) -> bool:
        return bool(self.engine.busy)

    def wait_until(self, t: float) -> None:
        """Sleep (idle: no request due) until clock time ``t``."""
        with TraceAnnotation("bench.wait"):
            dt = t - clock()
            if dt > 0:
                time.sleep(dt)

    # --------------------------------------------------------------- steps
    def step(self) -> Step:
        """One ``engine.step()``, then what it did to each live request."""
        W = self._State.WAITING
        before = {rid: (r.req.state, r.req.prefill_pos, len(r.req.output))
                  for rid, r in self._live.items()}
        t0 = clock()
        with TraceAnnotation("bench.step"):
            self.engine.step()
        t1 = clock()
        with TraceAnnotation("bench.observe"):
            seqs, sampled, done = [], 0, []
            for rid, r in self._live.items():
                state0, pos0, out0 = before[rid]
                req = r.req
                if state0 is W and req.state is not W and r.admitted is None:
                    r.admitted = t0
                new = len(req.output) - out0
                if new > 0:
                    r.token_times.extend([t1] * new)
                    sampled += new
                n_pre = max(req.prefill_pos - pos0, 0)
                decoding = state0 is self._State.DECODING and new > 0
                if n_pre or decoding:
                    kv = (req.prefill_pos if n_pre else len(req.prompt))
                    kv += out0 if decoding else 0
                    seqs.append((n_pre + int(decoding), kv))
                if req.state is self._State.FINISHED:
                    done.append(rid)
            finished = [self._live.pop(rid) for rid in done]
        s = Step(start=t0, end=t1, seqs=seqs, sampled=sampled,
                 finished=finished)
        self.steps.append(s)
        return s

    def live(self) -> List[Record]:
        return list(self._live.values())


@dataclass
class Window:
    """What one measured window produced.

    ``t0``/``t_end``: the window opens at ``t0`` and lasts until ``t_end``;
    ``t_last`` is the end of the last step started in it.  ``records`` are
    the requests the window's metrics count, ``steps`` the steps it ran,
    ``m_start``/``m_end`` the engine's own counters at its two ends, and
    ``closed`` the time after which no later observation was made (the
    drain's end in an open loop).
    """

    t0: float
    t_end: float
    t_last: float
    records: List[Record]
    steps: List[Step]
    attempted: int
    failed: int
    m_start: dict
    m_end: dict
    closed: float
    lines: List[str] = field(default_factory=list)
