"""Scheduler-driven serving engine over the paged KV cache.

The runtime realization of the paper's §4.2 vLLM case study, split into the
three layers of a production serving stack:

  * ``repro.serving.request``   — per-request state machine (WAITING ->
    PREFILLING -> DECODING -> PREEMPTED -> FINISHED) + sampling params;
  * ``repro.serving.scheduler`` — admission (prefix-cache aware), chunked-
    prefill token budgeting, preemption under block pressure — with the
    actual decisions (admission order, victim choice, cached-block eviction)
    delegated to registered strategies from ``repro.serving.policy``;
  * this module                 — the jit'd step driver: it renders each
    :class:`StepPlan` into ONE fused device program
    (``model.decode_tokens_paged`` + batched per-request sampling).

Step anatomy (the paper's BlockList optimization, end-to-end):

  * every step runs a single fused program over flat token lanes: one lane
    per decoding request plus up to ``token_budget`` prompt-chunk lanes from
    prefilling requests — chunked prefill never stalls the decode batch and
    there is no separate prefill program;
  * lane counts are bucketed to powers of two, so the engine compiles
    O(log max_tokens) programs total (slot-stable shapes everywhere else:
    block lists are padded to the pool size, sampling inputs to max_batch);
  * prompt prefixes shared across requests reuse pool blocks via the
    allocator's prefix cache (refcounted, copy-on-write on append) — a
    shared-prefix workload allocates strictly fewer blocks than independent
    prompts and skips recomputing the shared KV;
  * under block pressure the scheduler preempts the policy-ranked victim
    (recompute-style: its blocks are freed, generation state survives);
  * finished requests free their blocks immediately; hashed blocks are
    parked cached-free for future prefix hits, evicted by the registered
    eviction policy when the pool runs dry;
  * full blocks produced during DECODE are hash-registered too (not just
    prompt prefill), so preemption-resume recompute and repeated
    prompt+generation prefixes hit the cache;
  * with a registered speculative proposer (``repro.serving.spec``), each
    decoding request's step carries its last token plus K drafted tokens
    through the SAME fused program — the chunked attention grid already
    handles multi-token queries — followed by a batched rejection-accept
    (``verify_batched``) that emits the longest accepted prefix + one
    corrected/bonus token and rewinds speculatively reserved KV blocks;
  * TTFT / TPOT percentiles, throughput, preemption / prefix-hit /
    speculation counters and per-step-phase timing buckets via
    ``repro.serving.metrics`` (paper Fig 17e metrics);
  * with a ``mesh`` (built via ``repro.launch.mesh``), the SAME engine runs
    mesh-native: params are TP-sharded by ``distributed.sharding``'s rules,
    the KV pool is sequence-sharded on its block dimension, each layer's
    append + attention runs under shard_map with per-shard local BlockLists
    and a log-sum-exp combine (``paged_attention_chunked_sharded``, pinned
    through the registry as the ``sharded`` backend), and greedy output
    streams stay bit-identical to the single-device engine — the scheduler
    and StepPlan are device-count-agnostic (docs/sharded_serving.md).
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize as sanitize_lib
from repro.config import ModelConfig, ServeConfig
from repro.core import dispatch
from repro.core.paged_kv import (
    BlockAllocator, HostPool, copy_pool_blocks, make_fused_pool)
from repro.perf import autotune as autotune_lib
from repro.serving import policy as policy_lib
from repro.serving import sampling as sampling_lib
from repro.serving import spec as spec_lib
from repro.serving import request as request_lib
from repro.serving.metrics import EngineMetrics
from repro.serving.request import Request, RequestState, SamplingParams
from repro.serving.scheduler import Scheduler, StepPlan
from repro.serving.spans import Spans

__all__ = ["Request", "RequestState", "SamplingParams", "ServingEngine"]


_bucket = request_lib.bucket_pow2      # lane/slot counts -> power-of-two


def _lanes(plan: StepPlan) -> Dict[str, int]:
    """A plan's lanes by kind: prompt-chunk tokens and decoding requests
    (a request's drafted lanes count in neither)."""
    return {"prefill_tokens": sum(n for _, n in plan.prefill),
            "decode_tokens": len(plan.decode)}


class _PendingStep:
    """One dispatched-but-unresolved fused step (docs/async_engine.md).

    Built by ``ServingEngine._build``: the plan was rendered, every lane's
    KV slots were reserved AND provisionally committed, each decode-ish
    action appended a placeholder output token, and the fused program was
    dispatched — ``nxt_dev`` is its device-side future.  ``_resolve`` later
    blocks on the future and reconciles: placeholders become real tokens,
    EOS / max_new_tokens finishes fire, and finishes cancel the request's
    in-flight action in the NEXT pending step (if one was already built
    against the provisional state).
    """

    __slots__ = ("actions", "slots", "chain", "nxt_dev", "cancelled",
                 "phases", "num_tokens", "lanes", "t_dispatch")

    def __init__(self, *, actions, slots, chain, nxt_dev, phases,
                 num_tokens, lanes, t_dispatch):
        # actions: (kind, req, n, pos0, out_idx) — kind "decode"/"prefill";
        # out_idx indexes the placeholder in req.output (None: chunk-only
        # prefill, nothing to resolve).  slots: req_id -> slot snapshot at
        # build time (slot compaction may move requests before resolve).
        self.actions = actions
        self.slots = slots
        self.chain = chain
        self.nxt_dev = nxt_dev
        self.cancelled: set = set()
        self.phases = phases
        self.num_tokens = num_tokens
        self.lanes = lanes
        self.t_dispatch = t_dispatch

    def cancel(self, req) -> None:
        """A resolve finished ``req`` while its next step is in flight:
        drop the in-flight action (allocator state is already freed) and
        pop the provisional placeholder so the output stream ends at the
        real final token."""
        rid = req.req_id
        for kind, r, _n, _pos0, out_idx in self.actions:
            if r.req_id == rid:
                self.cancelled.add(rid)
                if out_idx is not None:
                    assert out_idx == len(req.output) - 1, (rid, out_idx)
                    req.output.pop()
                return


class ServingEngine:
    def __init__(self, model, params, cfg: ModelConfig, serve: ServeConfig,
                 *, num_blocks: Optional[int] = None, eos_id: int = -1,
                 token_budget: Optional[int] = None, seed: int = 0,
                 admission=None, preemption=None, eviction=None,
                 proposer=None, mesh=None, role: str = "full"):
        self.model = model
        self.cfg = cfg
        self.serve = serve
        self.eos_id = eos_id
        # Disaggregated serving (docs/disaggregated.md): a "prefill"-role
        # engine runs prompt prefill only — a request whose last chunk
        # commits is PARKED on ``self.prefilled`` (state stays PREFILLING,
        # blocks stay live) instead of transitioning to DECODING, for the
        # frontend to hand off to a decode-role engine via take_prefilled().
        if role not in ("full", "prefill"):
            raise ValueError(f"unknown engine role {role!r}")
        self.role = role
        self.prefill_only = role == "prefill"
        self.prefilled: List[Request] = []
        # Mesh-native serving: a jax Mesh (repro.launch.mesh) turns every
        # step into the sharded fused program — params TP-sharded via the
        # repo-wide ShardingRules, KV pool sequence-sharded over the model
        # axis, per-layer attention combined across it.  ``None`` falls
        # back to ``ServeConfig.devices`` (the config-level knob; a count
        # the host can't supply raises in make_serving_mesh rather than
        # silently serving single-device), else the single-device engine,
        # byte-for-byte the old behaviour; the scheduler below never sees
        # the difference.
        if mesh is None and serve.devices > 1:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(model=serve.devices)
        self.mesh = mesh
        self.mesh_axis = serve.parallel.model_axis
        S = int(mesh.shape[self.mesh_axis]) if mesh is not None else 1
        self.shards = S
        bs = serve.kv_block_size
        nb = num_blocks or serve.max_blocks or serve.max_batch * 64
        nb = -(-nb // S) * S            # pool splits into equal shard slices
        a = cfg.attention
        # Resolve the serving-policy triple ONCE through the policy registry
        # (explicit ctor args > force_policies scope > ServeConfig > default)
        # and pin it for the run — like the attention backend below, metrics
        # are attributable to exactly one admission/preemption/eviction
        # combination.
        adm, pre, evi = policy_lib.resolve_triple(
            admission=admission, preemption=preemption, eviction=eviction,
            config=serve)
        self.policies = {axis: p.name for axis, p in
                         ((policy_lib.ADMISSION, adm),
                          (policy_lib.PREEMPTION, pre),
                          (policy_lib.EVICTION, evi))}
        self._policy_objs = (adm, pre, evi)
        self.alloc = BlockAllocator(num_blocks=nb, block_size=bs,
                                    num_shards=S, eviction_policy=evi)
        # Host-memory KV tier (docs/disaggregated.md): evicted cached-free
        # blocks demote into a host LRU (policy-gated) instead of dropping
        # their content; prefix hits promote them back.  The device↔host
        # copies run in sync_pools()' ordered tier drain.
        self.host_pool: Optional[HostPool] = None
        if serve.host_blocks > 0:
            if S > 1:
                raise ValueError(
                    "host KV tier requires an unsharded pool (the demote/"
                    "promote block copies assume single-device block slices)")
            self.host_pool = HostPool(serve.host_blocks)
            self.alloc.host_pool = self.host_pool
        # ONE fused buffer (K and V side by side on the minor axis): the
        # allocator, CoW drain, tier demote/promote and the disagg handoff
        # each move a single pool; the chunked path reads it through split
        # views (repro.core.paged_kv.fused_kv_views).
        self.pools = {"kv": make_fused_pool(
            cfg.num_layers, nb, bs, a.num_kv_heads, a.head_dim,
            jnp.dtype(cfg.dtype))}
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from repro.distributed.sharding import ShardingRules
            rules = ShardingRules(mesh, head_dim=a.head_dim)
            params = jax.device_put(params,
                                    rules.named(rules.params_tree(params)))
            pool_sh = NamedSharding(mesh, P(None, self.mesh_axis))
            self.pools = {k: jax.device_put(v, pool_sh)
                          for k, v in self.pools.items()}
        self.params = params
        self.B = serve.max_batch
        self.max_total = nb
        self.scheduler = Scheduler(
            self.alloc, max_batch=self.B,
            token_budget=token_budget or serve.prefill_chunk,
            admission=adm, preemption=pre)
        self._free_slots = self.scheduler.free_slots    # shared list object
        self.finished: List[Request] = []
        # Resolve the hot-path attention backend ONCE through the unified
        # registry (ServeConfig.backend is the config-precedence level; env /
        # force_backend scopes still win, explicit args would win over both).
        # The resolved name is pinned for every step so perf numbers are
        # attributable to one implementation, and exposed via metrics().
        # A mesh pins the ``sharded`` backend explicitly (strict resolve —
        # the CallSpec carries the mesh as the capability evidence): the
        # per-layer combine is not a preference a config hint can override,
        # it is what makes the sequence-sharded pool computable at all.
        self.attn_impl = str(serve.attn_impl)
        if self.attn_impl not in ("ragged", "chunked"):
            raise ValueError(
                f"attn_impl {serve.attn_impl!r}: expected 'ragged' or "
                "'chunked'")
        fam = ("paged_attention_ragged" if self.attn_impl == "ragged"
               else "paged_attention_chunked")
        if mesh is not None:
            self.attn_backend = dispatch.resolve(
                fam, dispatch.SHARDED,
                spec=dispatch.CallSpec(platform=jax.default_backend(),
                                       kwargs={"mesh": mesh})).backend
        else:
            self.attn_backend = dispatch.resolve(
                fam, config=serve.backend).backend
        self._metrics = EngineMetrics(backend=self.attn_backend)
        self._spans = Spans()
        self._key = jax.random.PRNGKey(seed)
        self._step_count = 0
        # Async overlapped loop (docs/async_engine.md): with overlap on,
        # step N+1's propose/schedule/render runs on host while step N's
        # fused program is still on device; ``_pending`` holds step N's
        # un-resolved record, ``_chain`` maps req_id -> step-N slot for
        # requests whose last output token is still a device-side future
        # (the fused program substitutes it via ``tok_src``/``nxt_prev``).
        self.overlap = bool(serve.overlap)
        self.prefetch_depth = int(serve.prefetch_depth)
        self.q_chunk = int(serve.q_chunk)
        # Ragged-kernel tunables: explicit config value (> 0) wins; fields
        # left at 0 consult the committed autotune table for this
        # (page_size, head_dim, backend) cell (repro.perf.autotune,
        # BENCH_010.json — counted tuned_resolved / tuned_fallback, the
        # kernel-layer mirror of the `auto` policy triple), falling back to
        # the registry defaults on any miss.
        defaults = dict(dispatch.get_op("paged_attention_ragged").tunables)
        self._tune_counters = {"tuned_resolved": 0, "tuned_fallback": 0}
        explicit = {k: int(getattr(serve, k)) for k in
                    autotune_lib.TUNABLE_KEYS}
        if self.attn_impl == "ragged" and any(
                v == 0 for v in explicit.values()):
            tuned = autotune_lib.resolve_tunables(bs, a.head_dim,
                                                  self.attn_backend)
            if tuned is not None:
                defaults.update(tuned)
                self._tune_counters["tuned_resolved"] = 1
            else:
                self._tune_counters["tuned_fallback"] = 1
        self.attn_tunables = {k: (explicit[k] if explicit[k] > 0
                                  else int(defaults[k]))
                              for k in autotune_lib.TUNABLE_KEYS}
        # Runtime sanitizers (repro.analysis.sanitize): retrace guard on
        # the step dispatch, host-sync guard around the build half, and
        # allocator invariant checks after every commit reconciliation.
        self.sanitize = bool(serve.sanitize)
        self.sanitizer = (sanitize_lib.Sanitizer() if self.sanitize
                          else None)
        self._pending: Optional[_PendingStep] = None
        self._chain: Dict[int, int] = {}
        self._copy_fn = jax.jit(copy_pool_blocks)
        self._dummy_prev = jnp.zeros((1,), jnp.int32)
        # Inside the sharded program the combine is called directly under
        # shard_map (the registry pinned the name above for attribution);
        # the single-device program threads the resolved name through the
        # chunked op family as before.
        attn_backend = None if mesh is not None else self.attn_backend
        mesh_axis = self.mesh_axis if mesh is not None else None
        prefetch_depth = self.prefetch_depth
        q_chunk = self.q_chunk
        attn_impl = self.attn_impl
        attn_tunables = dict(self.attn_tunables)

        def fused(params, pools, lists, tokens, tok_src, nxt_prev, key,
                  temps, top_ks, top_ps):
            # Device-token chaining: lanes with tok_src >= 0 take their
            # input token from the PREVIOUS step's sampled outputs (still
            # device-resident under overlap) instead of the host-rendered
            # placeholder — the decode input never round-trips to host.
            live = jnp.clip(tok_src, 0, nxt_prev.shape[0] - 1)
            tokens = jnp.where(tok_src >= 0, nxt_prev[live], tokens)
            logits, pools = model.decode_tokens_paged(
                params, pools, lists, tokens, attn_backend=attn_backend,
                q_chunk=q_chunk, prefetch_depth=prefetch_depth, mesh=mesh,
                axis=mesh_axis, attn_impl=attn_impl, **attn_tunables)
            with jax.named_scope("sample"):
                nxt = sampling_lib.sample_batched(key, logits, temps,
                                                  top_ks, top_ps)
            return nxt, pools

        self._step_fn = jax.jit(fused)

        # Speculative decoding (repro.serving.spec): resolve the proposer
        # like the policy triple — explicit ctor arg > force_proposer scope >
        # ServeConfig.spec > "off" — and pin it for the run. With a proposer
        # the engine runs the spec step: same fused forward (logit rows at
        # every draft lane via ``logit_lanes``) + batched rejection-accept.
        self.proposer = spec_lib.resolve(proposer, config=serve.spec)
        if (self.proposer is not None
                and not getattr(self.proposer, "deterministic", True)):
            # verify_batched's delta-q acceptance rule treats the draft
            # distribution as a point mass — exact ONLY for deterministic
            # proposers.  A stochastic proposer reaching it would silently
            # skew the sampling distribution, so fail at adoption, not at
            # verify (docs/spec_decoding.md, "Be deterministic").
            raise ValueError(
                f"proposer {self.proposer.name!r} declares "
                "deterministic=False: verify_batched's delta-q rejection "
                "rule assumes the draft distribution is a point mass, so a "
                "stochastic proposer would bias the emitted distribution. "
                "Thread its q distribution through verify_batched or use a "
                "deterministic proposer (see docs/spec_decoding.md).")
        self.spec_k = max(1, serve.spec_k) if self.proposer else 0
        self._spec_counters = {"steps": 0, "drafted_steps": 0,
                               "decode_lanes": 0, "proposed_tokens": 0,
                               "accepted_tokens": 0, "emitted_tokens": 0,
                               "rollback_blocks": 0}
        if self.proposer is not None:
            self.proposer.bind(self)

            def fused_spec(params, pools, lists, tokens, key, temps, top_ks,
                           top_ps, drafts, draft_lens):
                logits, pools = model.decode_tokens_paged(
                    params, pools, lists, tokens, attn_backend=attn_backend,
                    q_chunk=q_chunk, prefetch_depth=prefetch_depth,
                    mesh=mesh, axis=mesh_axis, attn_impl=attn_impl,
                    **attn_tunables)
                with jax.named_scope("verify"):
                    out, acc = spec_lib.verify_batched(
                        key, logits, drafts, draft_lens, temps, top_ks,
                        top_ps)
                return out, acc, pools

            self._spec_step_fn = jax.jit(fused_spec)

    # -------------------------------------------------------------- lifecycle
    def submit(self, req: Request) -> None:
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.req_id}: empty prompt")
        # KV is written for the prompt and all generated tokens except the
        # last (sampling it finishes the request before its KV lands); the
        # scheduler additionally wants one slack block at admission.
        bs = self.alloc.block_size
        positions = len(req.prompt) + max(req.max_new_tokens - 1, 0)
        worst = max(-(-positions // bs), -(-len(req.prompt) // bs) + 1)
        if worst > self.alloc.num_blocks:
            raise ValueError(
                f"request {req.req_id} can never fit: needs up to {worst} "
                f"blocks, pool has {self.alloc.num_blocks}")
        self.scheduler.submit(req)

    @property
    def waiting(self) -> List[Request]:
        return list(self.scheduler.waiting)

    @property
    def active(self) -> Dict[int, Request]:
        return self.scheduler.running

    # ------------------------------------------------------------- step build
    def _render(self, plan: StepPlan):
        """Render a StepPlan into the fused program's input arrays."""
        alloc, B = self.alloc, self.B
        T = _bucket(plan.num_tokens)
        # Slot-keyed arrays (sampling knobs, kv lens, logit lanes) are sized
        # to a power-of-two bucket of the ACTIVE slots, not max_batch — the
        # same bucketing as token lanes, so a lightly loaded engine samples
        # over 8 lanes instead of max_batch. Slots are allocated low-first,
        # so max(slot)+1 tracks the live batch closely.
        reqs = list(plan.decode) + [req for req, _ in plan.prefill]
        Bs = min(_bucket(1 + max(req.slot for req in reqs)), B)
        # Verify rows only when this step actually carries drafts: a
        # draftless step (proposer came up empty, drafts shed, prefill-only)
        # runs the plain (B, V) program instead of paying R unembed rows.
        spec_step = bool(plan.spec)
        R = self.spec_k + 1 if spec_step else 1         # logit rows per slot
        tokens = np.zeros((T,), np.int32)
        # tok_src[lane] >= 0: the lane's input token is the PREVIOUS step's
        # sampled output at that slot, still in flight on device — the fused
        # program substitutes it (overlap chaining); -1 = host-known token.
        tok_src = np.full((T,), -1, np.int32)
        token_req = np.full((T,), Bs, np.int32)         # Bs == padding lane
        token_pos = np.zeros((T,), np.int32)
        slots = np.full((T, 2), (self.max_total, 0), np.int32)  # dropped write
        last_lane = np.zeros((Bs,), np.int32)
        kv_lens = np.zeros((Bs,), np.int32)
        temps = np.zeros((Bs,), np.float32)
        top_ks = np.zeros((Bs,), np.int32)
        top_ps = np.ones((Bs,), np.float32)
        logit_lanes = np.zeros((Bs, R), np.int32)
        draft_tokens = np.zeros((Bs, max(R - 1, 1)), np.int32)
        draft_lens = np.zeros((Bs,), np.int32)
        lane = 0
        committed: List[tuple] = []             # (req, n_tokens, start_pos)
        for req in plan.decode:
            rid = req.req_id
            pos = alloc.seq_len(rid)
            draft = plan.spec.get(rid)
            n = 1 if draft is None else 1 + len(draft)
            ss = alloc.reserve_tokens(rid, n)
            src = self._chain.get(rid, -1)
            if src >= 0:
                # output[-1] is an unresolved placeholder — chain it from
                # the pending step's device outputs. Drafted steps resolve
                # the pipeline first, so spec lanes never chain.
                assert draft is None, rid
                tok_src[lane] = src
            else:
                tokens[lane] = req.output[-1]
            if n > 1:                           # drafted lanes ride behind
                tokens[lane + 1:lane + n] = draft
                draft_tokens[req.slot, :n - 1] = draft
                draft_lens[req.slot] = n - 1
            token_req[lane:lane + n] = req.slot
            token_pos[lane:lane + n] = pos + np.arange(n)
            slots[lane:lane + n] = ss
            last_lane[req.slot] = lane + n - 1
            # a row per lane; unused rows repeat the last lane (masked by
            # draft_lens in verify_batched)
            logit_lanes[req.slot] = np.minimum(lane + np.arange(R),
                                               lane + n - 1)
            kv_lens[req.slot] = pos + n
            lane += n
            committed.append((req, n, pos))
        for req, n in plan.prefill:
            rid = req.req_id
            pos0 = alloc.seq_len(rid)
            ss = alloc.reserve_tokens(rid, n)
            chunk = req.active_prompt[pos0:pos0 + n]
            tokens[lane:lane + n] = chunk
            token_req[lane:lane + n] = req.slot
            token_pos[lane:lane + n] = pos0 + np.arange(n)
            slots[lane:lane + n] = ss
            last_lane[req.slot] = lane + n - 1
            logit_lanes[req.slot] = lane + n - 1        # only row 0 is read
            kv_lens[req.slot] = pos0 + n
            lane += n
            committed.append((req, n, pos0))
        for req, _, _ in committed:
            temps[req.slot] = req.sampling.temperature
            top_ks[req.slot] = req.sampling.top_k
            top_ps[req.slot] = req.sampling.top_p
        # Block lists AFTER reservations (tables may have grown / CoW'd).
        # A prefix-shared block is effectual for EVERY holder, so the entry
        # count can exceed the pool size — bucket the capacity like T.
        # With a mesh the allocator renders per-shard LOCAL lists instead
        # (same slot keys, same bucketing per shard slice): the fused
        # program shards them over the model axis and every rank attends
        # against exactly the BlockList slice its pool shard serves.
        tables = {req.req_id: alloc.table(req.req_id)
                  for req, _, _ in committed}
        if self.mesh is not None:
            bl, br, bp = alloc.build_sharded_block_lists(
                [(req.req_id, req.slot) for req, _, _ in committed],
                pad_req=Bs)
        else:
            needed = sum(len(t) for t in tables.values())
            cap = (self.max_total if needed <= self.max_total
                   else _bucket(needed, lo=self.max_total))
            bl = np.zeros((cap,), np.int32)
            br = np.full((cap,), Bs, np.int32)
            bp = np.zeros((cap,), np.int32)
            cursor = 0
            for req, _, _ in committed:
                table = tables[req.req_id]
                n = len(table)
                bl[cursor:cursor + n] = table
                br[cursor:cursor + n] = req.slot
                bp[cursor:cursor + n] = np.arange(n)
                cursor += n
        # Ragged metadata: each committed entry is one contiguous lane run
        # (decode entries first, then prefill chunks — exactly the order the
        # lanes were rendered above), so the prefix sums + slot map describe
        # the same (token_req, token_pos, kv_lens) lanes the chunked path
        # reads directly.  Bs-bucketed like every slot-keyed array, so the
        # ragged program compiles per (T, Bs) bucket — no extra retraces.
        q_lens = np.zeros((Bs,), np.int64)
        kv_l = np.zeros((Bs,), np.int64)
        seq_slot = np.full((Bs,), Bs, np.int32)         # Bs == dropped slot
        for j, (req, n, pos0) in enumerate(committed):
            seq_slot[j] = req.slot
            q_lens[j] = n
            kv_l[j] = pos0 + n
        cu_q = np.zeros((Bs + 1,), np.int32)
        cu_kv = np.zeros((Bs + 1,), np.int32)
        cu_q[1:] = np.cumsum(q_lens)
        cu_kv[1:] = np.cumsum(kv_l)
        # The ragged kernel's walk: each query tile of ``tq`` lanes walks the
        # BlockList entries of the sequences its lanes belong to, from its
        # first sequence's first entry to its last sequence's last.
        cu_b = np.zeros((len(committed) + 1,), np.int64)
        cu_b[1:] = np.cumsum([len(tables[req.req_id])
                              for req, _, _ in committed])
        tq = max(min(self.attn_tunables["num_queries_per_block"], T), 1)
        starts = np.arange(0, cu_q[-1], tq)
        ends = np.minimum(starts + tq, cu_q[-1]) - 1
        first = np.searchsorted(cu_q, starts, "right") - 1
        last = np.searchsorted(cu_q, ends, "right") - 1
        attn_pages = int((cu_b[last + 1] - cu_b[first]).sum())
        lists = {
            "block_list": jnp.asarray(bl), "block_req": jnp.asarray(br),
            "block_pos": jnp.asarray(bp), "kv_lens": jnp.asarray(kv_lens),
            "token_req": jnp.asarray(token_req),
            "token_pos": jnp.asarray(token_pos),
            "cu_q_lens": jnp.asarray(cu_q),
            "cu_kv_lens": jnp.asarray(cu_kv),
            "seq_slot": jnp.asarray(seq_slot),
            "slots": jnp.asarray(slots),
            "last_lane": jnp.asarray(last_lane),
        }
        if spec_step:
            lists["logit_lanes"] = jnp.asarray(logit_lanes)
        sample_args = (jnp.asarray(temps), jnp.asarray(top_ks),
                       jnp.asarray(top_ps))
        spec_args = ((jnp.asarray(draft_tokens), jnp.asarray(draft_lens))
                     if spec_step else None)
        return (lists, jnp.asarray(tokens), jnp.asarray(tok_src),
                sample_args, spec_args, committed, attn_pages)

    # -------------------------------------------------------------- main loop
    def _propose(self) -> Dict[int, np.ndarray]:
        """Ask the proposer for drafts for every DECODING request.

        Runs BEFORE scheduling so the scheduler can budget the extra lanes
        (blocks and tokens); a request preempted in the fit loop simply
        drops its draft.  The draft length is clamped so the step can never
        emit past ``max_new_tokens`` — the worst-case block bound checked at
        submit() is unchanged by speculation.  All requests go through ONE
        ``propose_batch`` call so proposers with a device-side rollout
        (draft-model) amortize it across the batch instead of running
        per-request host loops.
        """
        pend = [(req, min(self.spec_k,
                          req.max_new_tokens - len(req.output) - 1))
                for req in self.scheduler.running.values()
                if req.state is RequestState.DECODING
                and len(req.output) < req.max_new_tokens]
        if not pend:
            return {}
        raw = self.proposer.propose_batch(pend)
        drafts: Dict[int, np.ndarray] = {}
        for req, _ in pend:
            d = raw.get(req.req_id)
            d = (np.zeros((0,), np.int32) if d is None
                 else np.asarray(d, np.int32))
            self.proposer.on_propose(req, len(d))
            if len(d):
                drafts[req.req_id] = d
        return drafts

    def step(self) -> int:
        """One engine iteration: [propose] + schedule + ONE fused
        chunked-prefill/decode[/verify] program + host-side lifecycle
        updates. Returns #tokens processed.

        With ``ServeConfig.overlap`` the build half (propose / schedule /
        render / dispatch) runs against the PREVIOUS step's provisional
        state while that step is still executing on device; its resolve
        (commit reconciliation) happens after this step has been dispatched.
        Overlap off dispatches and resolves in the same call — identical
        behaviour to the serial loop. Greedy output streams are
        bit-identical either way (docs/async_engine.md).

        Each part runs inside a host span (``repro.serving.spans``):
        ``engine.step`` holds ``propose``, ``schedule``, ``render`` (inputs
        and the step key), ``drain`` (pool traffic), ``dispatch`` (the
        jitted call), ``wait`` (blocking on the tokens) and ``commit``;
        ``phase_s`` is rolled up from their readings.
        """
        with self._spans.span("step"):
            return self._step()

    def _step(self) -> int:
        span = self._spans.span
        if self.proposer is not None and self._pending is not None:
            # Proposers read the tail of req.output; under overlap its last
            # entry may still be an unresolved placeholder, which would
            # silently starve draft matching. Resolve first — drafted steps
            # are synchronization barriers anyway, so a proposer-active
            # engine sees exactly the serial engine's state at propose time.
            pend, self._pending = self._pending, None
            self._resolve(pend, None)
            if not self.scheduler.has_work():
                return 0        # the resolve finished the last requests —
                                # this iteration was a drain, not an idle tick
        drafts: Dict[int, np.ndarray] = {}
        propose_s = 0.0
        if self.proposer is not None:
            with span("propose") as sp:
                drafts = self._propose()
            propose_s = sp.s
        with span("schedule") as ss:
            plan = self.scheduler.schedule(spec_drafts=drafts)
        phases = {"propose": propose_s, "schedule_render": ss.s}
        if plan.num_tokens == 0:
            if self._pending is not None:      # drain the in-flight step
                pend, self._pending = self._pending, None
                for k, v in phases.items():
                    pend.phases[k] += v
                self._resolve(pend, None)
                return 0
            # Idle iteration: nothing scheduled, nothing in flight — record
            # the wall time instead of letting it vanish from phase_s.
            self._metrics.record_step(
                num_tokens=0, emitted_tokens=0, idle=True,
                phases={"propose": propose_s, "idle": ss.s})
            return 0
        if plan.spec:
            # Drafted steps are synchronization barriers: accepted drafts
            # commit KV at positions later lanes depend on and rejection
            # rolls reserved blocks back — never left in flight. Resolve
            # the pipeline first so every token the verify compares against
            # is concrete, then drop plan entries for requests that
            # finished at that resolve.
            if self._pending is not None:
                pend, self._pending = self._pending, None
                self._resolve(pend, None)
                self._filter_finished(plan)
                if plan.num_tokens == 0:
                    return 0
            return self._step_sync(plan, phases)
        pend_new = self._build(plan, phases)
        prev, self._pending = self._pending, None
        if prev is not None:
            self._resolve(prev, pend_new)
        if self.overlap:
            self._pending = pend_new
        else:
            self._resolve(pend_new, None)
        return plan.num_tokens

    # ------------------------------------------------------------- sanitizers
    def _sanitize_scope(self, scope: str):
        """Host-sync guard for the build half (no-op unless sanitizing)."""
        if self.sanitizer is None:
            return contextlib.nullcontext()
        return self.sanitizer.no_host_sync(scope)

    def _expect_cached(self, tag: str, *trees):
        """Retrace guard around one jit dispatch (no-op unless sanitizing)."""
        if self.sanitizer is None:
            return contextlib.nullcontext()
        return self.sanitizer.expect_cached(
            sanitize_lib.jit_signature(tag, *trees))

    def _check_allocator(self) -> None:
        if self.sanitizer is not None:
            self.sanitizer.check_allocator(self.alloc)

    # ---------------------------------------------------- overlapped pipeline
    def _drain_cow(self) -> None:
        """Apply pending copy-on-write block copies to the device pools.

        Copy counts are bucketed to powers of two with out-of-bounds padding
        (src = dst = pool size — the clipped gather reads a throwaway block,
        the ``mode="drop"`` scatter discards it), so a varying number of CoW
        copies per step reuses O(log pool) compiled programs instead of
        retracing ``copy_pool_blocks`` on every new count.
        """
        copies = self.alloc.drain_copies()
        if not copies:
            return
        n = _bucket(len(copies), lo=8)
        srcs = np.full((n,), self.max_total, np.int32)
        dsts = np.full((n,), self.max_total, np.int32)
        srcs[:len(copies)] = [s for s, _ in copies]
        dsts[:len(copies)] = [d for _, d in copies]
        srcs, dsts = jnp.asarray(srcs), jnp.asarray(dsts)
        # one executable per pow2 bucket: a second compile for a seen bucket
        # size would be exactly the per-call retrace class this drain's
        # bucketing exists to prevent
        with self._expect_cached("cow", n):
            self.pools = {k: self._copy_fn(p, srcs, dsts)
                          for k, p in self.pools.items()}

    def _drain_tier(self) -> None:
        """Apply queued host-tier traffic to the device pools, IN ORDER.

        A demote reads its block's per-channel pool slices (ONE fused kv
        slice) to host BEFORE any same-step reuse overwrites them (the slice
        is a data dependency on the in-flight program, so in-flight writes
        land first and the read content is the committed content); a promote
        scatters a previously saved host copy into its fresh block.  Runs
        before the CoW drain: CoW destinations are fresh pops that may be
        demoted blocks being reused.
        """
        channels = sorted(self.pools)
        ops = self.alloc.drain_tier_ops()
        for kind, entry, blk in ops:
            if kind == "demote":
                # documented host roundtrip: a demotion IS a device->host
                # copy — declared to the host-sync guard by reason
                entry.data = tuple(
                    sanitize_lib.host_read(self.pools[c][:, blk],
                                           reason="tier-drain")
                    for c in channels)
            else:
                assert entry.data is not None, "promote before demote copy"
                for c, val in zip(channels, entry.data):
                    self.pools[c] = self.pools[c].at[:, blk].set(
                        jnp.asarray(val, self.pools[c].dtype))

    def sync_pools(self) -> None:
        """Flush allocator-queued device-pool traffic (tier ops, then CoW).

        Public because the disaggregation frontend must flush the decode
        pool before writing handed-off KV into freshly reserved slots —
        a stale CoW whole-block copy or tier op applied later would clobber
        or misread them.
        """
        self._drain_tier()
        self._drain_cow()

    def _build(self, plan: StepPlan,
               phases: Dict[str, float]) -> "_PendingStep":
        """Render + dispatch a draftless plan and commit it provisionally.

        Every lane's KV slots are reserved AND committed here (one token per
        decode lane, the whole chunk per prefill lane) so the next schedule
        sees post-step sequence lengths; each decode-ish action appends a
        placeholder output token (the sampled value is still a device
        future) recorded in ``_chain`` for device-token chaining.  All
        host bookkeeping whose content is already known happens now —
        prefill chunk accounting, prompt prefix registration, the
        PREFILLING -> DECODING transition; everything value-dependent
        (EOS, TTFT stamps, generated-block hashing) waits for ``_resolve``.
        """
        # The build half must never block on the in-flight device step: a
        # device->host read here (outside the tier-drain allowlist) would
        # serialize the overlap the async loop exists for.  The retrace
        # guard scopes only the fused dispatch — eager housekeeping
        # (fold_in, render uploads) compiles once harmlessly.
        span = self._spans.span
        with self._sanitize_scope("overlap-build"):
            with span("render") as sr:
                (lists, tokens, tok_src, sample_args, spec_args, committed,
                 attn_pages) = self._render(plan)
                self._step_count += 1
                key = jax.random.fold_in(self._key, self._step_count)
            assert spec_args is None, "drafted plans go through _step_sync"
            with span("drain") as sd:
                self.sync_pools()
            nxt_prev = (self._pending.nxt_dev if self._pending is not None
                        else self._dummy_prev)
            with self._expect_cached("step", lists, tokens, tok_src,
                                     nxt_prev, sample_args):
                with span("dispatch") as sx:
                    nxt_dev, self.pools = self._step_fn(
                        self.params, self.pools, lists, tokens, tok_src,
                        nxt_prev, key, *sample_args)
        phases["schedule_render"] += sr.s + sd.s
        actions = []
        chain: Dict[int, int] = {}
        for req, n, pos0 in committed:
            rid = req.req_id
            self.alloc.commit_tokens(rid, n)
            if req.state is RequestState.DECODING:
                req.output.append(0)            # placeholder: value in flight
                chain[rid] = req.slot
                actions.append(("decode", req, n, pos0, len(req.output) - 1))
            else:                               # prefill chunk
                start = req.prefill_pos
                req.prefill_pos += n
                self.alloc.register_prefix(rid, req.active_prompt,
                                           req.prefill_pos, start=start)
                out_idx = None
                if req.prefill_remaining == 0:  # final chunk samples a token
                    if self.prefill_only:
                        # prefill role: park for handoff — no transition, no
                        # sampled token; the decode engine recomputes the
                        # final position's logits at admission (the same
                        # last-token rule the prefix cache already applies)
                        self.prefilled.append(req)
                    else:
                        req.to_state(RequestState.DECODING)
                        req.output.append(0)
                        chain[rid] = req.slot
                        out_idx = len(req.output) - 1
                actions.append(("prefill", req, n, pos0, out_idx))
        self._chain = chain
        if self.proposer is not None:
            self._spec_counters["steps"] += 1
        return _PendingStep(
            actions=actions,
            slots={req.req_id: req.slot for req, _, _ in committed},
            chain=chain, nxt_dev=nxt_dev, phases=phases,
            num_tokens=plan.num_tokens,
            lanes={**_lanes(plan), "attn_pages": attn_pages},
            t_dispatch=sx.t0)

    def _resolve(self, pend: "_PendingStep",
                 next_pending: Optional["_PendingStep"]) -> None:
        """Block on a pending step's device future and reconcile.

        Placeholders become real tokens, EOS / max-token finishes fire
        (cancelling the request's in-flight action in ``next_pending`` —
        the allocator's free is the reconciliation point), preempted-
        mid-flight requests keep their resolved token for recompute-resume,
        and the step's metrics are recorded with the device phase spanning
        dispatch -> future resolved.
        """
        with self._spans.span("wait") as sw:
            nxt = np.asarray(pend.nxt_dev)      # blocks until step N is done
        with self._spans.span("commit") as sc:
            if self._chain is pend.chain:       # overlap off: nothing newer
                self._chain = {}
            now = time.time()
            emitted = 0
            for kind, req, n, pos0, out_idx in pend.actions:
                rid = req.req_id
                if rid in pend.cancelled or out_idx is None:
                    continue    # finished at an earlier resolve / chunk-only
                tok = int(nxt[pend.slots[rid]])
                req.output[out_idx] = tok
                emitted += 1
                preempted = req.state is RequestState.PREEMPTED
                if kind == "decode" and not preempted:
                    self._register_generated(req, pos0, new_len=pos0 + n)
                if kind == "prefill" and req.first_token_at is None:
                    req.first_token_at = now
                # out_idx + 1 = this request's output length through THIS
                # action (req.output may already hold the NEXT step's
                # placeholder).
                if out_idx + 1 >= req.max_new_tokens or tok == self.eos_id:
                    self._finish(req, now, next_pending=next_pending)
        self._metrics.record_step(
            num_tokens=pend.num_tokens, emitted_tokens=emitted,
            **pend.lanes,
            phases={**pend.phases, "device": sw.t1 - pend.t_dispatch,
                    "commit": sc.s})
        # Post-reconciliation is the quiescent point: provisional commits,
        # finishes and preemption frees have all landed in the allocator.
        self._check_allocator()

    def _filter_finished(self, plan: StepPlan) -> None:
        """Drop plan entries whose request finished while the plan was being
        scheduled against provisional state (resolve ran after schedule)."""
        plan.decode = [r for r in plan.decode
                       if r.state is RequestState.DECODING]
        live = {r.req_id for r in plan.decode}
        plan.spec = {rid: d for rid, d in plan.spec.items() if rid in live}
        plan.prefill = [(r, n) for r, n in plan.prefill
                        if r.state is RequestState.PREFILLING]

    # ------------------------------------------------------ synchronous step
    def _step_sync(self, plan: StepPlan, phases: Dict[str, float]) -> int:
        """The drafted (speculative) step, fully synchronous."""
        span = self._spans.span
        with span("render") as sr:
            (lists, tokens, tok_src, sample_args, spec_args, committed,
             attn_pages) = self._render(plan)
            self._step_count += 1
            key = jax.random.fold_in(self._key, self._step_count)
        assert spec_args is not None
        del tok_src                 # pipeline resolved: every token concrete
        with span("drain") as sd:
            self.sync_pools()
        with self._expect_cached("spec", lists, tokens, sample_args,
                                 spec_args):
            with span("dispatch") as sx:
                out, acc, self.pools = self._spec_step_fn(
                    self.params, self.pools, lists, tokens, key,
                    *sample_args, *spec_args)
        with span("wait") as sw:
            out, acc = np.asarray(out), np.asarray(acc)
        with span("commit") as sc:
            emitted = self._commit_spec(plan, committed, out, acc)
        phases["schedule_render"] += sr.s + sd.s
        self._metrics.record_step(
            num_tokens=plan.num_tokens, emitted_tokens=emitted,
            **_lanes(plan), attn_pages=attn_pages,
            phases={**phases, "device": sw.t1 - sx.t0, "commit": sc.s})
        self._check_allocator()
        return plan.num_tokens

    def _commit_spec(self, plan: StepPlan, committed, out: np.ndarray,
                     acc: np.ndarray) -> int:
        """Commit a drafted step's accepted tokens; returns how many were
        emitted."""
        nxt = out[:, 0]
        now = time.time()
        emitted = 0
        for req, n, _ in committed:
            if req.state is RequestState.DECODING:
                # speculative lane: commit the accepted prefix, roll back
                # the rejected tail's reserved blocks (rewind semantics)
                a = min(int(acc[req.slot]), n - 1)
                self.alloc.commit_tokens(req.req_id, 1 + a)
                if a < n - 1:
                    table_before = len(self.alloc.table(req.req_id))
                    self.alloc.truncate(req.req_id,
                                        self.alloc.seq_len(req.req_id))
                    self._spec_counters["rollback_blocks"] += (
                        table_before - len(self.alloc.table(req.req_id)))
            else:
                self.alloc.commit_tokens(req.req_id, n)
        for req, n, pos0 in committed:
            if req.state is RequestState.DECODING:
                a = min(int(acc[req.slot]), n - 1)
                row = out[req.slot]
                self._register_generated(req, pos0, accepted=row[:a])
                appended = 0
                for j in range(a + 1):
                    self._append_token(req, int(row[j]), now)
                    appended += 1
                    if req.state is RequestState.FINISHED:
                        break               # EOS inside the accepted run
                emitted += appended
                if n > 1:
                    # count only DRAFTED lanes, and only tokens that
                    # actually reached the output stream (an EOS mid-
                    # prefix drops the tokens behind it) — an undrafted
                    # lane riding a spec step is a plain decode
                    self._spec_counters["decode_lanes"] += 1
                    self._spec_counters["accepted_tokens"] += min(
                        a, appended)
                    self._spec_counters["emitted_tokens"] += appended
            else:                                       # prefill chunk
                start = req.prefill_pos
                req.prefill_pos += n
                self.alloc.register_prefix(req.req_id, req.active_prompt,
                                           req.prefill_pos, start=start)
                if req.prefill_remaining == 0:
                    if self.prefill_only:
                        self.prefilled.append(req)
                        continue
                    req.to_state(RequestState.DECODING)
                    if req.first_token_at is None:
                        req.first_token_at = now
                    self._append_token(req, int(nxt[req.slot]), now)
                    emitted += 1
        self._spec_counters["steps"] += 1
        self._spec_counters["drafted_steps"] += 1
        self._spec_counters["proposed_tokens"] += sum(
            len(d) for d in plan.spec.values())
        return emitted

    def _register_generated(self, req: Request, pos0: int,
                            accepted: Optional[np.ndarray] = None,
                            new_len: Optional[int] = None) -> None:
        """Hash-register full KV blocks produced during decode.

        Prompt prefill publishes block hashes as chunks commit; this is the
        decode-side analogue (ROADMAP: generated-token prefix caching): any
        block FILLED by this step's committed tokens becomes prefix-cache
        content, so preemption-resume recompute and repeated
        prompt+generation prefixes get cache hits.  ``accepted`` carries
        this step's committed-but-not-yet-appended draft tokens (spec path).
        ``new_len`` is the post-step sequence length; the overlapped resolve
        passes it explicitly because by resolve time the allocator may
        already hold the NEXT step's provisional commits.
        """
        if new_len is None:
            new_len = self.alloc.seq_len(req.req_id)
        bs = self.alloc.block_size
        if pos0 // bs == new_len // bs:         # no block filled this step
            return
        seq = req.resume_tokens()
        if accepted is not None and len(accepted):
            seq = np.concatenate([seq, np.asarray(accepted, np.int32)])
        self.alloc.register_prefix(req.req_id, seq, new_len, start=pos0)

    def _append_token(self, req: Request, tok: int, now: float) -> None:
        req.output.append(tok)
        if len(req.output) >= req.max_new_tokens or tok == self.eos_id:
            self._finish(req, now)

    def _finish(self, req: Request, now: float,
                next_pending: Optional["_PendingStep"] = None) -> None:
        if req.state is RequestState.PREEMPTED:
            # Finished at resolve AFTER being preempted mid-flight: blocks
            # are already freed; pull it out of the recompute queue.
            try:
                self.scheduler.waiting.remove(req)
            except ValueError:
                pass
        else:
            self.scheduler.release(req)
        req.finish(now)
        self.finished.append(req)
        self._metrics.record_finished(
            ttft=req.ttft, tpot=req.tpot, num_output_tokens=len(req.output),
            arrival=req.arrival, done_at=now)
        if next_pending is not None:
            next_pending.cancel(req)
        self._chain.pop(req.req_id, None)

    @property
    def busy(self) -> bool:
        """Work queued, running, or still in flight in the pipeline."""
        return self.scheduler.has_work() or self._pending is not None

    def take_prefilled(self) -> List[Request]:
        """Prefill role: pop requests whose prompt KV is fully committed.

        Each is detached from the scheduler (slot returned, blocks KEPT and
        still owned by its req_id) — the caller performs the handoff and must
        ``alloc.free(req_id)`` afterwards to release the prefill-side copy
        (its published blocks then park cached-free, keeping the prefill
        prefix cache warm for repeat prompts)."""
        out, self.prefilled = self.prefilled, []
        for req in out:
            self.scheduler.detach(req)
        return out

    def run_until_done(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if not self.busy:
                return
            self.step()
        raise RuntimeError("serving did not converge")

    # --------------------------------------------------------------- metrics
    def metrics(self) -> Dict[str, float]:
        m = self._metrics.summary()
        m["spans"] = self._spans.summary()
        hits, misses = self.alloc.prefix_hits, self.alloc.prefix_misses
        # Mesh attribution: the shape the fused program ran on (axis name ->
        # size; None for the single-device engine) and the device count, so
        # a --devices sweep row is attributable to one mesh like rows are to
        # one backend/policy/proposer.
        mesh_shape = (dict(self.mesh.shape) if self.mesh is not None
                      else None)
        m.update({
            "mesh_shape": mesh_shape,
            "devices": (int(np.prod(list(mesh_shape.values())))
                        if mesh_shape else 1),
            # Pipeline attribution (like backend/mesh_shape): whether the
            # overlapped loop ran and the kernel's KV-page DMA ring depth.
            "overlap": self.overlap,
            "prefetch_depth": self.prefetch_depth,
            "q_chunk": self.q_chunk,
            # Ragged-kernel attribution: which attention family the fused
            # step dispatched and the resolved tunables (explicit config,
            # autotune-table hit, or registry defaults — the
            # tuned_resolved/tuned_fallback counters below say which).
            "attn_impl": self.attn_impl,
            **self.attn_tunables,
            "blocks_free": self.alloc.num_free,
            "preemptions": self.scheduler.num_preemptions,
            "slot_compactions": self.scheduler.num_slot_compactions,
            "prefix_hits": hits,
            "prefix_misses": misses,
            "prefix_hit_rate": hits / (hits + misses) if hits + misses else 0.0,
            "cow_copies": self.alloc.cow_copies,
        })
        # Speculative-decoding attribution: the resolved proposer plus the
        # acceptance evidence (rate, mean accepted length, rollbacks, shed
        # draft sets) — a --spec sweep row is attributable to one proposer.
        c = self._spec_counters
        m["spec"] = {
            "proposer": self.proposer.name if self.proposer else spec_lib.OFF,
            "k": self.spec_k,
            "acceptance_rate": (c["accepted_tokens"] / c["proposed_tokens"]
                                if c["proposed_tokens"] else 0.0),
            "mean_accepted_len": (c["accepted_tokens"] / c["drafted_steps"]
                                  if c["drafted_steps"] else 0.0),
            # output tokens emitted per DRAFTED (request, step) decode lane:
            # > 1 iff accepted drafts actually land (batch-size free;
            # undrafted lanes — whole draftless steps run the plain (B, V)
            # program — don't count)
            "tokens_per_decode_lane": (c["emitted_tokens"] / c["decode_lanes"]
                                       if c["decode_lanes"] else 0.0),
            "spec_sheds": self.scheduler.num_spec_sheds,
            **c,
        }
        if self.proposer is not None:
            m["spec"].update({f"proposer.{k}": v for k, v in
                              sorted(self.proposer.counters.items())})
        # The resolved policy triple the run executed with, plus each
        # policy's own counters (admitted / victims / evictions / ...) keyed
        # "<axis>.<counter>" — rows from a --policy sweep are attributable to
        # one admission/preemption/eviction combination.
        for axis, name in self.policies.items():
            m[f"{axis}_policy"] = name
        m["policy_counters"] = {
            f"{p.axis}.{k}": v
            for p in self._policy_objs for k, v in sorted(p.counters.items())}
        # Engine role (disaggregated serving) + host-tier attribution: pool
        # sizes per tier and the demote/promote/hit/drop traffic, with the
        # counters ALSO flattened next to the policy counters so benchmark
        # rows carry them the same way (docs/disaggregated.md).
        m["role"] = self.role
        hp = self.host_pool
        tier_counters = (dict(hp.counters) if hp is not None else
                         {"demotes": 0, "promotes": 0, "hits": 0, "drops": 0})
        m["tier"] = {
            "hbm_blocks": self.alloc.num_blocks,
            "host_blocks": hp.capacity if hp is not None else 0,
            "host_blocks_used": len(hp) if hp is not None else 0,
            **tier_counters,
        }
        m["policy_counters"].update(
            {f"tier.{k}": v for k, v in sorted(tier_counters.items())})
        m["policy_counters"].update(
            {f"tune.{k}": v for k, v in sorted(self._tune_counters.items())})
        # Sanitizer attribution (docs/static_analysis.md): whether the run
        # was guarded plus the guard counters, ALSO flattened next to the
        # policy counters so benchmark rows carry them the same way.  A
        # clean sanitized run shows retraces == transfer_guard_trips == 0
        # with invariant_checks > 0.
        san = (self.sanitizer.counters() if self.sanitizer is not None else
               {"retraces": 0, "transfer_guard_trips": 0,
                "invariant_checks": 0, "allowed_host_syncs": 0,
                "compiles": 0})
        m["sanitize"] = {"enabled": self.sanitize, **san}
        m["policy_counters"].update(
            {f"sanitize.{k}": v for k, v in sorted(san.items())})
        return m
