"""Role-aware frontend for disaggregated prefill/decode serving.

Production engines split the compute-bound prefill phase from the
bandwidth/latency-bound decode phase (the paper's §4.2 asymmetry): a burst
of long prompts then saturates the *prefill* engine while the *decode*
engine keeps emitting tokens at its own cadence instead of carrying prompt
chunks inside every fused step.  :class:`DisaggEngine` wires two ordinary
:class:`~repro.serving.engine.ServingEngine` instances into that shape:

  * the **prefill role** engine (``role="prefill"``) admits and chunk-
    prefills prompts exactly like the monolithic engine, but PARKS a
    request whose last chunk commits instead of decoding it;
  * the frontend pops parked requests (``take_prefilled``), performs the
    **handoff**, and submits a decode-side clone to the **decode role**
    engine, which runs the unmodified full engine (speculation, overlap,
    policies, host tier all apply).

Handoff contract (public allocator API only):

  1. the prefill side guarantees every committed token is KV-written
    (``BlockAllocator.transferable`` — the per-block watermark is the
    proof);
  2. the frontend stages the prompt's FULL blocks into the decode pool
    under a reserved negative request id: ``allocate_prefix`` adopts
    whatever the decode cache already holds (HBM hits and host-tier
    promotions both count), ``reserve_tokens``/``commit_tokens`` transfer
    the rest (:func:`copy_block_tokens` moves the raw KV, routed through
    host so cross-device role placement works), ``register_prefix``
    publishes the hashes;
  3. the prefill side frees its copy — the blocks park cached-free, so the
    prefill engine's prefix cache stays warm for repeated prompts;
  4. the decode-side clone is submitted as a fresh WAITING request: normal
    admission adopts every staged block and recomputes only the sub-block
    tail + final logits — exactly the prefix-cache last-token rule — so
    greedy streams are bit-identical to the monolithic engine.  The
    staging id is released only once the clone leaves WAITING, so staged
    blocks cannot be evicted while the clone queues.

Prompts shorter than one KV block carry no transferable KV and route
straight to the decode engine.

Determinism: the frontend loop is strictly serial (one prefill step, the
handoffs it unlocked, then up to ``decode_steps_per_step`` decode steps), so
runs are reproducible — and because greedy token values depend only on KV
*content*, never on step interleaving, outputs are bit-identical to the
monolithic engine for any interleave ratio.  Overlap (``ServeConfig.overlap``)
still hides device time inside each engine's own pipeline; the
``decode_steps_per_step`` knob is what decouples decode cadence from prefill
program latency (the TPOT protection measured by ``benchmarks/disagg.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize as sanitize_lib
from repro.config import ModelConfig, ServeConfig
from repro.serving.engine import Request, ServingEngine
from repro.serving.metrics import LatencyTracker
from repro.serving.request import RequestState

__all__ = ["DisaggEngine", "copy_block_tokens"]


def parse_roles(roles: str) -> Tuple[str, ...]:
    """Validate a ``ServeConfig.roles`` string -> role tuple ("" = mono)."""
    if not roles:
        return ()
    parts = tuple(p.strip() for p in roles.replace("+", ",").split(",") if p.strip())
    if parts in (("split",), ("prefill", "decode"), ("decode", "prefill")):
        return ("prefill", "decode")
    raise ValueError(
        f"unsupported roles spec {roles!r}; use 'prefill,decode' (or 'split')")


def copy_block_tokens(dst_pools, src_pools, src_slots: np.ndarray,
                      dst_slots: np.ndarray):
    """Copy per-token KV entries between two layer-stacked pools.

    ``src_slots`` / ``dst_slots`` are (n, 2) ``[block, offset]`` arrays (the
    shape ``reserve_tokens`` returns).  The gather round-trips through host
    (``np.asarray`` forces the source device copy) so the two pools may live
    on different devices; the in-flight-program data dependency on the
    source pool guarantees the content read is the committed content.
    Returns the updated ``dst_pools`` dict.
    """
    sb, so = np.asarray(src_slots[:, 0]), np.asarray(src_slots[:, 1])
    db, do = jnp.asarray(dst_slots[:, 0]), jnp.asarray(dst_slots[:, 1])
    out = dict(dst_pools)
    for c in dst_pools:          # ONE fused (L, NB, KV, BS, 2*HD) channel
        # documented host roundtrip — declared to the host-sync sanitizer
        vals = sanitize_lib.host_read(src_pools[c][:, sb, :, so],
                                      reason="disagg-handoff")  # (n, L, ...)
        out[c] = dst_pools[c].at[:, db, :, do].set(
            jnp.asarray(vals, dst_pools[c].dtype))
    return out


class DisaggEngine:
    """Two-role disaggregated serving frontend (see module docstring).

    Mirrors the monolithic :class:`ServingEngine` surface the launcher and
    benchmarks use: ``submit`` / ``step`` / ``run_until_done`` /
    ``finished`` / ``metrics``.
    """

    def __init__(self, model, params, cfg: ModelConfig, serve: ServeConfig,
                 *, num_blocks: Optional[int] = None,
                 prefill_blocks: Optional[int] = None,
                 decode_blocks: Optional[int] = None,
                 eos_id: int = -1, token_budget: Optional[int] = None,
                 seed: int = 0, devices: Optional[Sequence] = None,
                 decode_steps_per_step: int = 4):
        if parse_roles(serve.roles or "prefill,decode") != ("prefill",
                                                           "decode"):
            raise ValueError(f"unsupported roles {serve.roles!r}")
        if serve.devices > 1:
            raise ValueError(
                "disaggregated roles run one engine per role; pass per-role "
                "devices via the `devices` pair, not ServeConfig.devices")
        if devices is not None and len(devices) != 2:
            raise ValueError("devices must be a (prefill, decode) pair")
        self._devices = tuple(devices) if devices is not None else (None,
                                                                    None)
        # The prefill role never decodes: speculation is decode-only work,
        # so it is forced off there; everything else (chunk budget, overlap,
        # policies, host tier) applies to both roles.
        pre_serve = dataclasses.replace(serve, roles="", spec="off")
        dec_serve = dataclasses.replace(serve, roles="")

        def build(role: str, sv: ServeConfig, nb: Optional[int], dev):
            ctx = (jax.default_device(dev) if dev is not None
                   else contextlib.nullcontext())
            with ctx:
                p = (jax.device_put(params, dev) if dev is not None
                     else params)
                return ServingEngine(model, p, cfg, sv, num_blocks=nb,
                                     eos_id=eos_id, token_budget=token_budget,
                                     seed=seed, role=role)

        self.pre = build("prefill", pre_serve,
                         prefill_blocks or num_blocks, self._devices[0])
        self.dec = build("full", dec_serve,
                         decode_blocks or num_blocks, self._devices[1])
        self.block_size = serve.kv_block_size
        self.eos_id = eos_id
        self.decode_steps_per_step = max(1, decode_steps_per_step)
        self.finished: List[Request] = self.dec.finished   # shared list
        self.handoff = LatencyTracker()                    # seconds parked
        self.num_handoffs = 0
        self.num_direct = 0        # sub-block prompts routed straight to dec
        self._pending_handoffs: Deque[Tuple[Request, float]] = deque()
        self._originals: Dict[int, Request] = {}
        self._dreqs: Dict[int, Request] = {}
        self._staged: Dict[int, int] = {}                  # rid -> staging id

    # -------------------------------------------------------------- lifecycle
    @staticmethod
    def _clone(req: Request, max_new: int) -> Request:
        """A fresh WAITING copy for one role (identity + policy fields)."""
        return Request(req_id=req.req_id, prompt=req.prompt,
                       max_new_tokens=max_new, sampling=req.sampling,
                       arrival=req.arrival, priority=req.priority,
                       deadline=req.deadline)

    def submit(self, req: Request) -> None:
        if req.req_id < 0:
            raise ValueError(
                f"request {req.req_id}: negative ids are reserved for "
                "handoff staging")
        if req.req_id in self._originals:
            raise ValueError(f"request {req.req_id}: duplicate id")
        full = len(req.prompt) // self.block_size
        if full > 0 and full + 2 > self.dec.alloc.num_blocks:
            raise ValueError(
                f"request {req.req_id}: handoff stages {full} full blocks "
                f"and admission needs 2 more, decode pool has only "
                f"{self.dec.alloc.num_blocks}")
        self._originals[req.req_id] = req
        if full == 0:
            # No transferable KV in a sub-block prompt: the prefill leg
            # would be pure overhead — decode engine prefills it itself.
            dreq = self._clone(req, req.max_new_tokens)
            self._dreqs[req.req_id] = dreq
            self.dec.submit(dreq)
            self.num_direct += 1
            return
        self.pre.submit(self._clone(req, 1))     # max_new sizes decode slack

    def step(self) -> int:
        """One frontend iteration: prefill step -> unlocked handoffs ->
        up to ``decode_steps_per_step`` decode steps.  Returns lane tokens
        processed across both engines."""
        n = 0
        if self.pre.busy:
            n += self.pre.step()
            t = time.perf_counter()
            for req in self.pre.take_prefilled():
                self._pending_handoffs.append((req, t))
        self._try_handoffs()
        for _ in range(self.decode_steps_per_step):
            if not self.dec.busy:
                break
            n += self.dec.step()
            self._release_staged()
            self._try_handoffs()
        return n

    @property
    def busy(self) -> bool:
        return (self.pre.busy or self.dec.busy
                or bool(self._pending_handoffs))

    def run_until_done(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if not self.busy:
                return
            self.step()
        raise RuntimeError("disaggregated serving did not converge")

    # ---------------------------------------------------------------- handoff
    def _try_handoffs(self) -> None:
        """Move parked prefills into the decode pool, FIFO, while it fits.

        Worst-case pops of one staging import: every full block fresh plus
        one copy-on-write for an already-fully-cached tail — back-pressure
        keeps the request parked (prefill-side blocks intact) until the
        decode pool can absorb it, so a decode-side burst can never strand
        KV mid-transfer.
        """
        while self._pending_handoffs:
            req, t0 = self._pending_handoffs[0]
            full = len(req.prompt) // self.block_size
            if self.dec.alloc.num_free < full + 1:
                break
            self._pending_handoffs.popleft()
            self._handoff(req)
            self.handoff.record(time.perf_counter() - t0)
            self.num_handoffs += 1

    def _handoff(self, preq: Request) -> None:
        rid = preq.req_id
        prompt = np.asarray(preq.prompt, np.int32)
        bs = self.block_size
        n_import = (len(prompt) // bs) * bs
        pre_alloc, dec_alloc = self.pre.alloc, self.dec.alloc
        assert pre_alloc.seq_len(rid) >= n_import, (
            rid, pre_alloc.seq_len(rid), n_import)
        assert pre_alloc.transferable(rid), (
            f"request {rid}: parked blocks not fully KV-written")
        pre_table = pre_alloc.table(rid)
        hand = -rid - 1                         # staging id (disjoint space)
        cached = dec_alloc.allocate_prefix(hand, prompt)
        if cached < n_import:
            dst = dec_alloc.reserve_tokens(hand, n_import - cached)
            src = np.array([(pre_table[p // bs], p % bs)
                            for p in range(cached, n_import)], np.int32)
            # flush staged CoW/tier traffic first: a whole-block copy or
            # promote applied after our slot writes would clobber them
            self.dec.sync_pools()
            self.dec.pools = copy_block_tokens(self.dec.pools, self.pre.pools,
                                               src, dst)
            dec_alloc.commit_tokens(hand, n_import - cached)
        dec_alloc.register_prefix(hand, prompt, n_import, start=0)
        pre_alloc.free(rid)         # prefill copy parks cached-free (warm)
        dreq = self._clone(self._originals[rid],
                           self._originals[rid].max_new_tokens)
        self._dreqs[rid] = dreq
        self._staged[rid] = hand
        self.dec.submit(dreq)

    def _release_staged(self) -> None:
        """Drop staging holds whose decode clone has been admitted.

        Admission adopted the staged blocks (refcount bump), so releasing
        the staging id cannot drop content a queued clone still needs — the
        hold exists exactly to pin blocks while the clone is WAITING.
        """
        for rid in [r for r, d in self._dreqs.items()
                    if r in self._staged
                    and d.state is not RequestState.WAITING]:
            self.dec.alloc.free(self._staged.pop(rid))

    # ---------------------------------------------------------------- metrics
    def metrics(self) -> Dict[str, object]:
        """Decode-engine metrics (arrival-to-done spans cover the whole
        pipeline since clones keep the original arrival), plus per-role and
        handoff attribution."""
        m = dict(self.dec.metrics())
        pre_m = self.pre.metrics()
        role_keys = ("steps", "num_idle_steps", "lane_tokens_per_step",
                     "output_tokens", "finished", "preemptions",
                     "prefix_hits", "prefix_misses", "backend", "overlap",
                     "phase_s", "tier")
        m["roles"] = {
            "prefill": {**{k: pre_m[k] for k in role_keys},
                        "prefills_completed": self.num_handoffs},
            "decode": {**{k: m[k] for k in role_keys},
                       "direct_submits": self.num_direct},
        }
        m["handoffs"] = self.num_handoffs
        m["handoff_ms"] = {k: (v * 1e3 if k != "n" else v)
                           for k, v in self.handoff.summary().items()}
        # flatten prefill-side tier counters beside the decode ones
        m["policy_counters"] = dict(m["policy_counters"])
        m["policy_counters"].update(
            {f"tier.prefill.{k}": v
             for k, v in sorted(pre_m["tier"].items())
             if k in ("demotes", "promotes", "hits", "drops")})
        m["role"] = "prefill,decode"
        return m
