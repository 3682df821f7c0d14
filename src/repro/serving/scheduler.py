"""Serving scheduler: admission, chunked-prefill budgeting, preemption.

The mechanism half of the serving control plane — the *policy* half lives in
``repro.serving.policy``: admission order and preemption-victim choice are
injected strategy objects, never hardcoded branches here (the same split the
operator registry gives kernels: this module is the resolver-user, not the
decider).

Per engine step the scheduler:

  1. **Compacts slots**: a long-lived request sitting on a high slot is
     remapped down into a freed lower slot, so the engine's power-of-two
     active-slot bucket can shrink back after a burst drains.
  2. **Admits** waiting requests in the admission policy's order while a
     batch slot is free and the allocator can hold the whole prompt
     (prefix-cached blocks are adopted at admission and don't count against
     free space).  Head-of-line semantics are per policy: if the policy's
     top pick does not fit, admission stops — no queue-jumping past it.
  3. **Budgets tokens**: every DECODING request always gets its decode
     lane — plus, under speculative decoding, one lane per drafted token
     (``StepPlan.spec``), charged against the per-step token budget ahead
     of prefill; PREFILLING requests share what remains of the budget
     (``token_budget``, vLLM's ``max_num_batched_tokens`` analogue) so long
     prompts are chunked across steps instead of stalling the decode batch.
  4. **Preempts under block pressure**: if the step's block demand (new
     decode/draft blocks + prefill-chunk blocks + copy-on-write copies)
     exceeds the pool, speculative drafts are shed first (losing a step's
     speedup beats recomputing a victim's KV); then the preemption policy's
     top-ranked victim is evicted — its blocks are released and it
     re-queues for recompute-style resume (see ``repro.serving.request``).
     The policy's least-preemptable request is never evicted, so one
     request always makes progress.

The scheduler owns the request queues and the slot free-list; it never
touches device state.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.core.paged_kv import BlockAllocator, OutOfBlocksError
from repro.serving import policy as policy_lib
from repro.serving.request import Request, RequestState


@dataclass
class StepPlan:
    """What the engine should run this step.

    ``spec`` maps a DECODING request's id to its drafted tokens for this
    step (speculative decoding): that request's lane count is ``1 +
    len(spec[req_id])`` instead of 1, and the extra lanes were budgeted by
    the scheduler (block demand AND token budget) like prefill chunks.
    """

    decode: List[Request] = field(default_factory=list)
    prefill: List[Tuple[Request, int]] = field(default_factory=list)  # (req, n)
    spec: Dict[int, "np.ndarray"] = field(default_factory=dict)

    def decode_tokens(self, req: Request) -> int:
        """Lane count of one decode request: 1 + its drafted tokens."""
        return 1 + len(self.spec.get(req.req_id, ()))

    @property
    def num_tokens(self) -> int:
        return (sum(self.decode_tokens(r) for r in self.decode)
                + sum(n for _, n in self.prefill))


class Scheduler:
    def __init__(self, alloc: BlockAllocator, *, max_batch: int,
                 token_budget: int,
                 admission: Optional[policy_lib.AdmissionPolicy] = None,
                 preemption: Optional[policy_lib.PreemptionPolicy] = None):
        self.alloc = alloc
        self.max_batch = max_batch
        self.token_budget = max(1, token_budget)
        self.admission = admission or policy_lib.resolve(policy_lib.ADMISSION)
        self.preemption = (preemption
                           or policy_lib.resolve(policy_lib.PREEMPTION))
        self.waiting: Deque[Request] = deque()
        self.running: Dict[int, Request] = {}
        self.free_slots: List[int] = list(range(max_batch - 1, -1, -1))
        self.num_preemptions = 0
        self.num_slot_compactions = 0
        self.num_spec_sheds = 0      # draft sets dropped under block pressure

    # ------------------------------------------------------------------ queue
    def submit(self, req: Request) -> None:
        assert req.state is RequestState.WAITING, req.state
        self.waiting.append(req)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # ------------------------------------------------------------------ slots
    def _compact_slots(self) -> None:
        """Remap running requests into freed lower slots (highest first).

        Slot ids only live in the host-built per-step arrays, so moving a
        request between steps is free — and ``max(slot) + 1`` is what the
        engine buckets to a power of two, so shrinking it shrinks the
        compiled program the next step runs.
        """
        if not self.free_slots:
            return
        # Sort even with nothing running: release() appends in finish order,
        # and admission pops from the end — unsorted, a fresh wave after a
        # drained burst would land on high slots and re-inflate the bucket.
        self.free_slots.sort(reverse=True)          # lowest slot at pop() end
        for req in sorted(self.running.values(),
                          key=lambda r: r.slot, reverse=True):
            low = self.free_slots[-1]
            if low >= req.slot:
                break                               # nobody below can improve
            self.free_slots[-1] = req.slot          # swap: give back the high
            req.slot = low
            self.free_slots.sort(reverse=True)
            self.num_slot_compactions += 1

    # -------------------------------------------------------------- admission
    def _admit(self) -> None:
        now = time.time()
        while self.waiting and self.free_slots:
            req = self.admission.select(self.waiting, now)
            # resume prompt includes generated tokens (recompute preemption)
            active = req.resume_tokens()
            bs = self.alloc.block_size
            cached = self.alloc.peek_prefix(active)
            total_blocks = max(1, -(-len(active) // bs))
            fresh = max(total_blocks - cached // bs, 0) + 1  # +1 decode slack
            if self.alloc.num_free < fresh:
                # Livelock breaker: the whole pool is free and still too
                # small — this request (e.g. one whose resume prompt grew
                # past the pool after preemption) will NEVER be admittable,
                # and as the policy's head-of-line it would starve everyone
                # behind it. Fail loudly instead of spinning.
                if (not self.running
                        and self.alloc.num_free == self.alloc.num_blocks):
                    raise OutOfBlocksError(
                        f"request {req.req_id} needs {fresh} blocks but the "
                        f"whole pool is only {self.alloc.num_blocks}")
                break                     # policy head-of-line: no jumping
            self.waiting.remove(req)
            slot = self.free_slots.pop()
            cached = self.alloc.allocate_prefix(req.req_id, active)
            req.begin_prefill(slot, cached, active_prompt=active)
            if req.admitted_at is None:
                req.admitted_at = now
            self.running[req.req_id] = req
            self.admission.on_admit(req, now)

    # -------------------------------------------------------------- capacity
    def _blocks_needed(self, plan: StepPlan) -> int:
        """Exact pool demand of the plan: new blocks + copy-on-write copies.

        A shared physical block written by several plan members costs
        ``min(#writers, refcount - 1)`` copies, not one per writer: each CoW
        drops the refcount, and the last writer at refcount 1 writes in
        place.
        """
        bs = self.alloc.block_size
        need = 0
        cow_writers: Dict[int, int] = {}     # physical block -> plan writers

        def span(req: Request, n: int) -> int:
            """New blocks + CoW writers for ``n`` tokens appended to req."""
            pos = self.alloc.seq_len(req.req_id)
            table = self.alloc.table(req.req_id)
            last_bi = (pos + n - 1) // bs
            fresh = max(last_bi + 1 - len(table), 0)         # new blocks
            for bi in range(pos // bs, min(last_bi, len(table) - 1) + 1):
                if self.alloc.ref_count(table[bi]) > 1:
                    cow_writers[table[bi]] = cow_writers.get(table[bi], 0) + 1
            return fresh

        for req in plan.decode:
            # a speculative decode lane appends 1 + K draft tokens, all of
            # which need reserved (possibly fresh / CoW'd) write slots
            need += span(req, plan.decode_tokens(req))
        for req, n in plan.prefill:
            need += span(req, n)
        for blk, writers in cow_writers.items():
            need += min(writers, self.alloc.ref_count(blk) - 1)
        return need

    def _pick_victim(self, now: float) -> Optional[Request]:
        """The preemption policy's top-ranked victim.

        The bottom of the ranking (least preemptable) is protected: with
        fewer than two running requests there is no victim, which guarantees
        at least one request keeps making progress.
        """
        ranked = self.preemption.rank(list(self.running.values()),
                                      self.alloc, now)
        if len(ranked) < 2:
            return None
        return ranked[0]

    def release(self, req: Request) -> None:
        """Return a running request's blocks and slot (finish or preempt)."""
        self.alloc.free(req.req_id)
        del self.running[req.req_id]
        self.free_slots.append(req.slot)

    def detach(self, req: Request) -> None:
        """Remove a running request KEEPING its blocks (prefill→decode
        handoff): the slot returns to the free list but the allocator table
        stays live — the caller owns the blocks and must ``alloc.free`` the
        request id once the transfer is done."""
        del self.running[req.req_id]
        self.free_slots.append(req.slot)
        req.slot = -1

    def _preempt(self, req: Request) -> None:
        self.preemption.on_preempt(req, self.alloc)   # table still live here
        self.release(req)
        req.preempt()
        self.waiting.appendleft(req)
        self.num_preemptions += 1

    # ------------------------------------------------------------------- plan
    def schedule(self, spec_drafts: Optional[Dict[int, "np.ndarray"]] = None
                 ) -> StepPlan:
        """Compact, admit, budget prefill chunks, preempt until the plan
        fits.

        ``spec_drafts`` (speculative decoding) maps req_id -> drafted tokens
        for DECODING requests; each draft widens its request's lane count to
        ``1 + K``.  Draft lanes are charged against the step token budget
        ahead of prefill chunks and TRIMMED to it — total lanes stay within
        ``#decode + token_budget``, the same bound the non-spec scheduler
        gives — with half the budget held back for prefill whenever a
        PREFILLING request is waiting on chunks, so speculation can slow
        prefill but never starve it.  Drafts are also charged exact block
        demand like any other appended token; a draft whose request gets
        preempted in the fit loop is simply dropped.
        """
        self._compact_slots()
        self._admit()
        # Same-wave prefix dedup: a mid-prefill request whose next blocks
        # were published since last step (by a same-prompt donor, possibly
        # itself still prefilling — the KV-written watermark is the proof of
        # completeness) fast-forwards over them instead of recomputing.
        for req in self.running.values():
            if req.state is RequestState.PREFILLING:
                adopted = self.alloc.extend_prefix(req.req_id,
                                                   req.active_prompt)
                if adopted:
                    req.prefill_pos += adopted
        spec_drafts = spec_drafts or {}
        while True:
            plan = StepPlan()
            budget = self.token_budget
            prefill_pending = any(r.state is RequestState.PREFILLING
                                  for r in self.running.values())
            spec_budget = budget // 2 if prefill_pending else budget
            for req in self.running.values():
                if req.state is RequestState.DECODING:
                    if len(req.output) >= req.max_new_tokens:
                        # Provisionally complete: the async engine already
                        # committed this request's final token (value still
                        # in flight) — no further lanes; it finishes when
                        # its device future resolves.
                        continue
                    plan.decode.append(req)
                    draft = spec_drafts.get(req.req_id)
                    if draft is not None and spec_budget > 0:
                        take = min(len(draft), spec_budget)
                        if take > 0:
                            plan.spec[req.req_id] = draft[:take]
                            spec_budget -= take
            # speculative lanes consume token budget before prefill chunks
            budget = max(budget - sum(len(d) for d in plan.spec.values()), 0)
            for req in self.running.values():
                if req.state is RequestState.PREFILLING and budget > 0:
                    n = min(req.prefill_remaining, budget)
                    if n > 0:
                        plan.prefill.append((req, n))
                        budget -= n
            if self._blocks_needed(plan) <= self.alloc.num_free:
                return plan
            if plan.spec:
                # Shed optional work first: dropping drafts costs one step's
                # speedup; preempting a request throws away computed KV.
                spec_drafts = {}
                self.num_spec_sheds += 1
                continue
            victim = self._pick_victim(now=time.time())
            if victim is None:
                raise OutOfBlocksError(
                    "a single request exceeds the KV pool; cannot preempt "
                    "further")
            self._preempt(victim)
