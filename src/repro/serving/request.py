"""Serving request lifecycle: the explicit per-request state machine.

A request moves through

    WAITING -> PREFILLING -> DECODING -> FINISHED
       ^           |            |
       '--- PREEMPTED <---------'

* WAITING     queued; no slot, no KV blocks.
* PREFILLING  admitted; prompt KV is being written chunk-by-chunk (chunked
  prefill — chunks ride inside the fused decode step, they never stall the
  decode batch).
* DECODING    prompt fully cached; one token per engine step — or, with a
  speculative proposer resolved (``repro.serving.spec``), 1 to K+1 tokens
  per step: the engine carries the last token plus K drafts through one
  fused forward and commits the accepted prefix (the state machine is
  unchanged; only the per-step token count varies).
* PREEMPTED   evicted under block pressure; KV blocks were released and the
  request re-queued at the FRONT of the wait queue. On re-admission it
  recomputes KV for ``prompt + output`` (vLLM's recompute-style preemption),
  which reproduces the exact generation state — output tokens survive.
* FINISHED    hit ``max_new_tokens`` or EOS; blocks freed, metrics recorded.

This module is deliberately jax-free: it is pure host-side bookkeeping shared
by ``repro.serving.scheduler`` and ``repro.serving.engine``.
"""
from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


def bucket_pow2(n: int, lo: int = 8) -> int:
    """Round ``n`` up to a power of two, at least ``lo``.

    The serving stack's shape-bucketing helper (bounded jit-cache growth):
    the engine buckets token-lane and active-slot counts, the draft-model
    proposer buckets its context window.
    """
    b = lo
    while b < n:
        b *= 2
    return b


class RequestState(enum.Enum):
    WAITING = "waiting"
    PREFILLING = "prefilling"
    DECODING = "decoding"
    PREEMPTED = "preempted"
    FINISHED = "finished"


_LEGAL = {
    RequestState.WAITING: {RequestState.PREFILLING},
    RequestState.PREFILLING: {RequestState.DECODING, RequestState.PREEMPTED,
                              RequestState.FINISHED},
    RequestState.DECODING: {RequestState.PREEMPTED, RequestState.FINISHED},
    # PREEMPTED -> FINISHED: the async overlapped loop can resolve a
    # request's final token (EOS / max_new_tokens) after the scheduler
    # preempted it mid-flight — the stream is complete, recompute is moot.
    RequestState.PREEMPTED: {RequestState.PREFILLING, RequestState.FINISHED},
    RequestState.FINISHED: set(),
}


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling policy, applied batched inside the jit'd step.

    ``temperature <= 0`` means greedy; ``top_k <= 0`` / ``top_p >= 1``
    disable the respective filters.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray                  # (prompt_len,) int32
    max_new_tokens: int
    sampling: SamplingParams = field(default_factory=SamplingParams)
    arrival: float = field(default_factory=time.time)
    # Serving-policy inputs (repro.serving.policy): higher priority admits
    # first under the "priority" policy; deadline is an absolute time.time()
    # the "deadline-slo" policy schedules against (None = no SLO).
    priority: int = 0
    deadline: Optional[float] = None
    state: RequestState = RequestState.WAITING
    # first admission (the scheduler's time.time(), the clock of arrival);
    # a preempted request keeps it
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    output: List[int] = field(default_factory=list)
    slot: int = -1
    # chunked-prefill cursor into active_prompt (tokens whose KV is cached)
    prefill_pos: int = 0
    num_preemptions: int = 0
    # tokens satisfied from the prefix cache at (last) admission
    cached_prompt_tokens: int = 0
    # prompt + already-generated tokens; set at admission (recompute resume)
    _active_prompt: Optional[np.ndarray] = None

    # ------------------------------------------------------------ transitions
    def to_state(self, new: RequestState) -> None:
        assert new in _LEGAL[self.state], (
            f"illegal transition {self.state.name} -> {new.name} "
            f"(req {self.req_id})")
        self.state = new

    def resume_tokens(self) -> np.ndarray:
        """Tokens to (re)prefill: prompt + already-generated output.

        The single source for admission sizing, prefix-cache hashing AND the
        engine's chunk content — recompute-style preemption resume depends
        on all three seeing the same sequence.
        """
        if not self.output:
            return np.asarray(self.prompt, np.int32)
        return np.concatenate([np.asarray(self.prompt, np.int32),
                               np.asarray(self.output, np.int32)])

    def begin_prefill(self, slot: int, cached_tokens: int,
                      active_prompt: Optional[np.ndarray] = None) -> None:
        """WAITING/PREEMPTED -> PREFILLING on an engine slot."""
        self._active_prompt = (active_prompt if active_prompt is not None
                               else self.resume_tokens())
        self.to_state(RequestState.PREFILLING)
        self.slot = slot
        self.prefill_pos = cached_tokens
        self.cached_prompt_tokens = cached_tokens

    def preempt(self) -> None:
        self.to_state(RequestState.PREEMPTED)
        self.slot = -1
        self.prefill_pos = 0
        self._active_prompt = None
        self.num_preemptions += 1

    def finish(self, now: Optional[float] = None) -> None:
        self.to_state(RequestState.FINISHED)
        self.done_at = now if now is not None else time.time()
        self.slot = -1

    # ------------------------------------------------------------- accessors
    @property
    def active_prompt(self) -> np.ndarray:
        assert self._active_prompt is not None, "request not admitted"
        return self._active_prompt

    @property
    def prefill_remaining(self) -> int:
        return len(self.active_prompt) - self.prefill_pos

    @property
    def ttft(self) -> Optional[float]:
        return (self.first_token_at - self.arrival
                if self.first_token_at else None)

    @property
    def tpot(self) -> Optional[float]:
        if self.done_at is None or self.first_token_at is None:
            return None
        n = max(len(self.output) - 1, 1)
        return (self.done_at - self.first_token_at) / n
