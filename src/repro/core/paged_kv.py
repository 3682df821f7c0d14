"""Paged KV-cache pool and block allocator.

Host side (`BlockAllocator`): a refcounted free-list allocator over a fixed
pool of KV blocks — vLLM's memory manager, including its two serving-side
tricks:

  * **Prefix caching** — every *full* block of a sequence is content-hashed
    (chained over the prefix, so a block's key commits to everything before
    it): prompt blocks as prefill chunks commit, and blocks filled during
    DECODE under their true prompt+generation content (the engine's
    generated-token registration — preemption-resume recompute and repeated
    prompt+generation prefixes hit the cache). Freed blocks whose content is
    hashed are parked in a cached-free LRU instead of being scrubbed; a
    later prompt with the same prefix re-adopts them with a refcount bump
    and skips recomputing their KV.
  * **Copy-on-write** — a block shared by several requests (refcount > 1) is
    never written in place; :meth:`reserve_tokens` transparently allocates a
    private copy and records a (src, dst) pair for the engine to apply on the
    device pool via :func:`copy_pool_blocks`.

Which cached-free block is sacrificed when the pool needs a fresh one is NOT
decided here: it flows through a registered eviction policy
(``repro.serving.policy``, axis ``eviction``).  The allocator keeps per-block
:class:`BlockStats` (lifetime prefix-cache hits, peak refcount) so scorers
like ``hit-rate`` and ``refcount-aware`` have evidence to rank on; the
default resolves to the registered ``lru`` policy, byte-for-byte the old
oldest-freed-first behaviour.

Two extensions ride on that machinery (docs/disaggregated.md):

  * **KV-written watermark** — ``_written[block]`` counts how many leading
    token slots of a block hold committed KV.  It gates
    :meth:`extend_prefix` (same-wave prefix dedup: a borrower admitted
    while the donor is still prefilling fast-forwards over blocks the
    moment they are published, full and written) and backs
    :meth:`transferable`, the prefill→decode handoff's contract that a
    request's blocks can be copied out of this pool.
  * **Host-memory tier** — with a :class:`HostPool` attached, evicting a
    cached-free block *demotes* its content to host memory (policy-gated:
    the eviction policy's ``demote`` hook scores keep/drop on the same
    ``BlockStats``) instead of dropping it, and a prefix hit on a demoted
    key *promotes* it back into a fresh HBM block before admission.  The
    allocator only does bookkeeping; the actual device↔host copies are
    queued on :attr:`pending_tier_ops` for the engine to apply in order
    (demotes read old content before any reuse overwrites it).

Sequence state is mutated ONLY through the public API — ``allocate`` /
``allocate_prefix``, ``reserve_tokens`` + ``commit_tokens``, ``rewind`` /
``truncate``, ``free`` — so engines never poke ``_lens`` directly.  The
reserve/commit/truncate triple is also the speculative-decoding rollback
primitive: reserve K+1 write slots, commit only the accepted prefix, and
truncate to the committed length — refcounts and the free list are restored
exactly for a fully-rejected step (``tests/test_spec.py``).

Per scheduling step the allocator also renders the device layouts:
  * a padded 2D **BlockTable** (B, max_blocks)  — the baseline layout whose
    zero-padding induces redundant gathers (paper Fig 16a), or
  * a flat 1D **BlockList** of only *effectual* blocks plus per-block request
    ids / positions — the paper's optimized layout (Fig 16b), or
  * per-shard **local BlockLists** (``build_sharded_block_lists``) for the
    sequence-sharded chunked path: each mesh rank gets the slice of the
    BlockList its pool shard can serve, with LOCAL pool indices
    (docs/sharded_serving.md).

Device side: the serving pool is one dense fused array (num_blocks, KV,
block_size, 2*HD) per layer (stacked over layers for scan);
``append_to_fused_pool`` writes one new token per lane into its current
block/offset.  Split (num_blocks, block_size, KV, HD) K and V pools remain
for the decode-only path (``append_to_pool``).
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np


class OutOfBlocksError(RuntimeError):
    pass


@dataclass
class BlockStats:
    """Per-physical-block evidence for eviction scorers.

    ``hits``      lifetime prefix-cache adoptions of this block's content;
    ``peak_ref``  highest simultaneous refcount the block ever reached.
    Reset whenever the block is handed out for fresh content.
    """

    hits: int = 0
    peak_ref: int = 1


def _prefix_key(tokens: np.ndarray, n_tokens: int) -> bytes:
    """Content hash of ``tokens[:n_tokens]`` (chained prefix hash)."""
    buf = np.ascontiguousarray(tokens[:n_tokens], dtype=np.int32).tobytes()
    return hashlib.blake2b(buf, digest_size=16).digest()


@dataclass
class HostBlock:
    """One demoted KV block staged in host memory.

    ``data`` is filled lazily by the engine's tier drain (a device→host copy
    of the block's slice per pool channel — ONE fused ``kv`` slice, or
    (k, v) slices with split pools); ``stats``
    carries the block's eviction evidence across the tier round-trip so a
    promoted block keeps its history.
    """

    key: bytes
    stats: BlockStats
    data: Optional[Tuple[np.ndarray, ...]] = None   # host copy per channel


class HostPool:
    """Host-memory KV tier: an LRU of demoted cached-free blocks.

    Capacity is counted in blocks.  ``put`` registers a demotion (oldest
    entry dropped on overflow), ``take`` consumes an entry for promotion.
    The pool never touches device memory — entries carry host ``np`` copies
    written by the engine's ordered tier drain.
    """

    def __init__(self, capacity: int):
        assert capacity > 0, capacity
        self.capacity = int(capacity)
        self._entries: "OrderedDict[bytes, HostBlock]" = OrderedDict()
        self.counters: Dict[str, int] = {
            "demotes": 0, "promotes": 0, "hits": 0, "drops": 0}

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: bytes) -> bool:
        return key in self._entries

    def put(self, key: bytes, stats: BlockStats) -> HostBlock:
        """Demote ``key``: stage a new entry (content copied in later by the
        engine's tier drain) and LRU-drop past capacity."""
        self._entries.pop(key, None)        # re-demotion replaces stale data
        entry = HostBlock(key=key, stats=stats)
        self._entries[key] = entry
        self.counters["demotes"] += 1
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self.counters["drops"] += 1
        return entry

    def take(self, key: bytes) -> Optional[HostBlock]:
        """Consume an entry for promotion back into the HBM pool."""
        entry = self._entries.pop(key, None)
        if entry is not None:
            self.counters["promotes"] += 1
            self.counters["hits"] += 1
        return entry

    def untake(self, key: bytes, entry: HostBlock) -> None:
        """Roll back a ``take`` whose promotion could not get an HBM block."""
        self._entries[key] = entry
        self.counters["promotes"] -= 1
        self.counters["hits"] -= 1


@dataclass
class BlockAllocator:
    """Refcounted free-list allocator over ``num_blocks`` KV blocks."""

    num_blocks: int
    block_size: int
    # Sequence-sharding over a mesh axis: the device pool is split
    # CONTIGUOUSLY into ``num_shards`` equal slices, so physical block ``b``
    # lives on shard ``b // (num_blocks // num_shards)`` at local index
    # ``b % (num_blocks // num_shards)`` — exactly the slice shard_map hands
    # each rank when the pool array is sharded on its block dimension.  The
    # free list is interleaved across shards so allocation stays balanced.
    num_shards: int = 1
    # Cached-free eviction scorer: an ``EvictionPolicy`` from
    # ``repro.serving.policy`` (duck-typed here — core stays importable
    # without the serving layer; the registered default is resolved lazily on
    # first eviction).
    eviction_policy: Optional[Any] = None
    # Optional host-memory tier: evicted cached-free blocks are demoted into
    # it (policy-gated) instead of dropped, and promoted back on prefix hit.
    host_pool: Optional[HostPool] = None
    _free: List[int] = field(default_factory=list)
    _tables: Dict[int, List[int]] = field(default_factory=dict)
    _lens: Dict[int, int] = field(default_factory=dict)
    # block -> refcount, for every live (allocated or cached-free) block
    _ref: Dict[int, int] = field(default_factory=dict)
    # prefix cache: content hash <-> block (only FULL prompt blocks)
    _hash_of: Dict[int, bytes] = field(default_factory=dict)
    _block_of: Dict[bytes, int] = field(default_factory=dict)
    # refcount-0 blocks whose content is retained for prefix reuse, in
    # freed order (oldest first — the candidate order eviction scorers see)
    _cached_free: "OrderedDict[int, None]" = field(default_factory=OrderedDict)
    # block -> BlockStats, evidence for eviction scorers
    _stats: Dict[int, BlockStats] = field(default_factory=dict)
    # block -> KV-written watermark: #leading token slots holding committed
    # KV (the same-wave-dedup / handoff-transferability evidence)
    _written: Dict[int, int] = field(default_factory=dict)
    # (src, dst) copy-on-write pairs awaiting a device-pool copy
    pending_copies: List[Tuple[int, int]] = field(default_factory=list)
    # ordered host-tier traffic awaiting device copies: ("demote"|"promote",
    # HostBlock, block).  Order matters — a demote must read its block's
    # content before any same-step reuse overwrites it, and before a promote
    # consumes its data.
    pending_tier_ops: List[Tuple[str, HostBlock, int]] = field(
        default_factory=list)
    # counters (surfaced by ServingEngine.metrics)
    prefix_hits: int = 0
    prefix_misses: int = 0
    cow_copies: int = 0
    cache_evictions: int = 0
    blocks_allocated: int = 0    # total fresh-block grabs (prefix hits skip it)

    def __post_init__(self):
        if self.num_shards > 1:
            assert self.num_blocks % self.num_shards == 0, (
                self.num_blocks, self.num_shards)
            # Pop order cycles shards (0, per, 2*per, ..., 1, per+1, ...):
            # consecutive allocations land on different ranks, so per-shard
            # BlockList fills — and therefore per-rank attention work — stay
            # balanced instead of filling shard 0 first.
            per = self.blocks_per_shard
            order = [s * per + i for i in range(per)
                     for s in range(self.num_shards)]
            self._free = list(reversed(order))
        else:
            self._free = list(range(self.num_blocks - 1, -1, -1))

    @property
    def blocks_per_shard(self) -> int:
        return self.num_blocks // self.num_shards

    def shard_of(self, block: int) -> int:
        """Owning mesh rank of a physical block (contiguous pool slices)."""
        return block // self.blocks_per_shard

    # -- block bookkeeping --------------------------------------------------
    def _eviction(self) -> Any:
        """The eviction scorer, lazily resolved to the registered default.

        The import is deferred so ``repro.core`` never depends on the serving
        layer at module load (``repro.serving.policy`` imports this module).
        """
        if self.eviction_policy is None:
            from repro.serving.policy import EVICTION, resolve
            self.eviction_policy = resolve(EVICTION)
        return self.eviction_policy

    def _pop_block(self) -> int:
        """Take a block: plain free list first, then evict a cached-free
        block chosen by the registered eviction policy."""
        if self._free:
            blk = self._free.pop()
        elif self._cached_free:
            pol = self._eviction()
            blk = int(pol.select(tuple(self._cached_free), self._stats))
            if blk not in self._cached_free:
                raise RuntimeError(
                    f"eviction policy {getattr(pol, 'name', pol)!r} selected "
                    f"block {blk}, not a cached-free candidate")
            del self._cached_free[blk]
            key = self._hash_of.get(blk)        # capture before unregister
            self._unregister(blk)
            if self.host_pool is not None and key is not None:
                demote = getattr(pol, "demote", None)
                if demote is None or demote(blk, self._stats):
                    entry = self.host_pool.put(
                        key, self._stats.get(blk, BlockStats()))
                    self.pending_tier_ops.append(("demote", entry, blk))
            pol.on_evict(blk, self._stats)
            self.cache_evictions += 1
        else:
            raise OutOfBlocksError("pool exhausted")
        self.blocks_allocated += 1
        self._stats[blk] = BlockStats()          # fresh content, fresh record
        self._written[blk] = 0
        return blk

    def _unregister(self, blk: int) -> None:
        key = self._hash_of.pop(blk, None)
        if key is not None and self._block_of.get(key) == blk:
            del self._block_of[key]

    def _decref(self, blk: int) -> None:
        if blk not in self._ref:
            raise RuntimeError(f"double free of block {blk}")
        self._ref[blk] -= 1
        if self._ref[blk] == 0:
            del self._ref[blk]
            if blk in self._hash_of:      # keep content for prefix reuse
                self._cached_free[blk] = None
            else:
                self._free.append(blk)

    # -- lifecycle ----------------------------------------------------------
    def allocate(self, req_id: int, num_tokens: int) -> List[int]:
        assert req_id not in self._tables, req_id
        n = max(1, -(-num_tokens // self.block_size))
        if self.num_free < n:
            raise OutOfBlocksError(f"need {n} blocks, have {self.num_free}")
        blocks = [self._pop_block() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
        self._tables[req_id] = blocks
        self._lens[req_id] = num_tokens
        return blocks

    def allocate_prefix(self, req_id: int, tokens: np.ndarray) -> int:
        """Admit ``req_id`` reusing cached prefix blocks; return #cached tokens.

        Every leading *full* block of ``tokens`` whose chained content hash is
        in the prefix cache is adopted (refcount bump) instead of allocated.
        With a host tier attached, a miss in the HBM cache falls back to
        promoting the demoted entry into a fresh block (content restored by
        the engine's tier drain before the step runs).  The sequence length
        starts at the cached token count, so prefill can skip straight to the
        first uncached token. At least one token is always left to recompute
        (a fully-cached prompt still needs its final logits), which makes the
        last shared block copy-on-write on first append.
        """
        assert req_id not in self._tables, req_id
        bs = self.block_size
        blocks: List[int] = []
        cached = 0
        full = len(tokens) // bs
        for i in range(full):
            key = _prefix_key(tokens, (i + 1) * bs)
            blk = self._block_of.get(key)
            if blk is None and self.host_pool is not None:
                blk = self._promote(key)
            if blk is None:
                break
            self._adopt(blk)
            blocks.append(blk)
            cached += bs
            self.prefix_hits += 1
        self.prefix_misses += full - len(blocks)
        if not blocks:                      # cold start: behave like allocate
            blk = self._pop_block()
            self._ref[blk] = 1
            blocks.append(blk)
        self._tables[req_id] = blocks
        cached = min(cached, max(len(tokens) - 1, 0))
        self._lens[req_id] = cached
        return cached

    def _adopt(self, blk: int) -> None:
        """Take one more reference on a cache-hit block (cached-free revival,
        live share, or a just-promoted tier block) and bump its evidence."""
        if blk in self._cached_free:
            del self._cached_free[blk]
            self._ref[blk] = 1
        else:
            self._ref[blk] = self._ref.get(blk, 0) + 1
        st = self._stats.setdefault(blk, BlockStats())
        st.hits += 1
        st.peak_ref = max(st.peak_ref, self._ref[blk])

    def _promote(self, key: bytes) -> Optional[int]:
        """Stage a host-tier entry back into a fresh HBM block.

        The block is hash-registered immediately (so chained lookups for the
        following blocks resolve) with its pre-demotion stats restored and a
        full watermark; the actual host→device content copy is queued on
        :attr:`pending_tier_ops`.  Returns ``None`` on a tier miss or when
        the HBM pool cannot yield a block (the entry is put back).
        """
        assert self.host_pool is not None
        entry = self.host_pool.take(key)
        if entry is None:
            return None
        try:
            blk = self._pop_block()
        except OutOfBlocksError:
            self.host_pool.untake(key, entry)
            return None
        self._hash_of[blk] = key
        self._block_of[key] = blk
        self._stats[blk] = entry.stats
        self._written[blk] = self.block_size
        self.pending_tier_ops.append(("promote", entry, blk))
        return blk

    def peek_prefix(self, tokens: np.ndarray) -> int:
        """#tokens a prompt would get from the HBM cache, without mutating it.

        Host-tier entries are deliberately NOT counted: a promotion consumes
        a fresh HBM block, so for admission sizing a demoted prefix block
        costs what a fresh block costs.
        """
        bs = self.block_size
        cached = 0
        for i in range(len(tokens) // bs):
            if _prefix_key(tokens, (i + 1) * bs) not in self._block_of:
                break
            cached += bs
        return min(cached, max(len(tokens) - 1, 0))

    def extend_prefix(self, req_id: int, tokens: np.ndarray) -> int:
        """Same-wave prefix dedup: fast-forward a mid-prefill request over
        blocks another request published since it was admitted.

        While ``req_id``'s committed length sits on a block boundary, adopt
        the published block for its next ``block_size`` tokens — but only if
        that block's KV-written watermark covers the whole block (the donor
        may still be prefilling later chunks; a published block is complete,
        the watermark is the proof).  An untouched placeholder block at the
        frontier (the cold-start pop: private, unpublished, watermark 0) is
        swapped back to the free list.  As in :meth:`allocate_prefix`, at
        least one token is always left to recompute.  Returns the number of
        tokens fast-forwarded; callers advance their prefill cursor by it.
        """
        bs = self.block_size
        pos = self._lens[req_id]
        table = self._tables[req_id]
        adopted = 0
        while pos % bs == 0 and pos + bs <= len(tokens) - 1:
            blk = self._block_of.get(_prefix_key(tokens, pos + bs))
            if blk is None or self._written.get(blk, 0) < bs:
                break
            bi = pos // bs
            if bi < len(table):
                own = table[bi]
                if (own == blk or self._ref.get(own) != 1
                        or own in self._hash_of
                        or self._written.get(own, 0) > 0):
                    break               # frontier block already has content
                table[bi] = blk
                self._decref(own)       # untouched placeholder -> free list
            else:
                assert bi == len(table), (bi, len(table))
                table.append(blk)
            self._adopt(blk)
            self.prefix_hits += 1
            pos += bs
            adopted += bs
        if adopted:
            self._lens[req_id] = pos
        return adopted

    def register_prefix(self, req_id: int, tokens: np.ndarray,
                        num_valid: int, start: int = 0) -> None:
        """Publish content hashes for full blocks covered by committed KV.

        ``tokens[:num_valid]`` must have their KV written to the request's
        blocks; ``start`` (a token count) skips blocks published by earlier
        calls so incremental prefill commits hash each block once.
        Shared-safe: an existing hash entry is never overwritten.
        """
        bs = self.block_size
        table = self._tables[req_id]
        for i in range(start // bs, num_valid // bs):
            blk = table[i]
            if blk in self._hash_of:
                continue
            key = _prefix_key(tokens, (i + 1) * bs)
            if key in self._block_of:       # identical content already cached
                continue
            self._hash_of[blk] = key
            self._block_of[key] = blk

    def reserve_tokens(self, req_id: int, n: int) -> np.ndarray:
        """Reserve write slots for the next ``n`` tokens; returns (n, 2).

        Grows the block table on demand and performs copy-on-write for any
        target block shared with another request (the (src, dst) pair lands
        in :attr:`pending_copies` — apply with :func:`copy_pool_blocks`
        before the step). Does not advance the sequence: call
        :meth:`commit_tokens` once the KV entries are written.
        """
        pos0 = self._lens[req_id]
        table = self._tables[req_id]
        out = np.zeros((n, 2), np.int32)
        for j in range(n):
            pos = pos0 + j
            bi = pos // self.block_size
            if bi == len(table):
                blk = self._pop_block()
                self._ref[blk] = 1
                table.append(blk)
            blk = table[bi]
            if self._ref[blk] > 1:          # shared: copy-on-write
                new = self._pop_block()
                self._ref[new] = 1
                self._ref[blk] -= 1
                table[bi] = new
                self.pending_copies.append((blk, new))
                self.cow_copies += 1
                # the device copy clones the whole block: watermark carries
                self._written[new] = self._written.get(blk, 0)
                blk = new
            elif blk in self._hash_of:      # private but published: invalidate
                self._unregister(blk)
            out[j] = (blk, pos % self.block_size)
        return out

    def commit_tokens(self, req_id: int, n: int) -> None:
        pos0 = self._lens[req_id]
        if n > 0:                           # advance KV-written watermarks
            bs = self.block_size
            table = self._tables[req_id]
            for bi in range(pos0 // bs, (pos0 + n - 1) // bs + 1):
                filled = min(pos0 + n - bi * bs, bs)
                blk = table[bi]
                if filled > self._written.get(blk, 0):
                    self._written[blk] = filled
        self._lens[req_id] = pos0 + n

    def drain_copies(self) -> List[Tuple[int, int]]:
        copies, self.pending_copies = self.pending_copies, []
        return copies

    def drain_tier_ops(self) -> List[Tuple[str, HostBlock, int]]:
        """Hand the queued host-tier traffic to the engine, IN ORDER."""
        ops, self.pending_tier_ops = self.pending_tier_ops, []
        return ops

    # Single-token conveniences (legacy API, used by tests/benchmarks).
    def reserve_slot(self, req_id: int) -> Tuple[int, int]:
        blk, off = self.reserve_tokens(req_id, 1)[0]
        return int(blk), int(off)

    def commit_token(self, req_id: int) -> None:
        self.commit_tokens(req_id, 1)

    def append_token(self, req_id: int) -> Tuple[int, int]:
        """reserve + commit in one call (single-step convenience)."""
        slot = self.reserve_slot(req_id)
        self.commit_token(req_id)
        return slot

    def rewind(self, req_id: int, n: int = 1) -> None:
        """Public rollback: drop the last ``n`` committed tokens.

        Trailing blocks no longer covered are released (decref — shared
        blocks survive for their other holders). The next
        :meth:`reserve_tokens` re-reserves the rewound positions, with
        copy-on-write if the block is still shared.
        """
        self.truncate(req_id, max(self._lens[req_id] - n, 0))

    def truncate(self, req_id: int, new_len: int) -> None:
        """Public truncation: keep only the first ``new_len`` tokens."""
        assert 0 <= new_len <= self._lens[req_id], (new_len, self._lens[req_id])
        table = self._tables[req_id]
        keep = max(1, -(-new_len // self.block_size))
        while len(table) > keep:
            self._decref(table.pop())
        self._lens[req_id] = new_len
        # Rolled-back KV in the last kept block is stale: lower its watermark
        # when the block is private and unpublished (the spec-rollback case —
        # shared/published blocks keep valid content for their other holders).
        last = table[-1]
        off = max(new_len - (len(table) - 1) * self.block_size, 0)
        if (self._ref.get(last) == 1 and last not in self._hash_of
                and off < self._written.get(last, 0)):
            self._written[last] = off

    def free(self, req_id: int) -> None:
        if req_id not in self._tables:
            raise KeyError(f"free of unknown request {req_id} (double free?)")
        for blk in self._tables.pop(req_id):
            self._decref(blk)
        del self._lens[req_id]

    @property
    def num_free(self) -> int:
        """Allocatable blocks: truly free + evictable cached-free."""
        return len(self._free) + len(self._cached_free)

    def check_invariants(self, *, drained: bool = False) -> None:
        """Validate the allocator's full internal state; raise ``ValueError``
        naming the first violated invariant.

        Called after every commit when ``ServeConfig.sanitize`` is on (via
        ``repro.analysis.sanitize``).  With ``drained=True`` additionally
        requires the fully-idle state: every block free, no tables, no
        pending device traffic.
        """
        def fail(msg: str) -> None:
            raise ValueError(msg)

        bs, blocks = self.block_size, set(range(self.num_blocks))
        free, cached = set(self._free), set(self._cached_free)
        live = set(self._ref)     # _decref drops the entry at refcount 0
        # 1. free / cached-free / refcounted partition the block space
        if len(free) != len(self._free):
            fail(f"duplicate ids on free list: {sorted(self._free)}")
        for a, b, what in ((free, cached, "free and cached-free"),
                           (free, live, "free and refcounted"),
                           (cached, live, "cached-free and refcounted")):
            if a & b:
                fail(f"blocks both {what}: {sorted(a & b)}")
        if (free | cached | live) != blocks:
            fail(f"blocks neither free nor tracked: "
                 f"{sorted(blocks - free - cached - live)}")
        # 2. refcounts equal table occurrences exactly
        occurrences: Dict[int, int] = {}
        for table in self._tables.values():
            for blk in table:
                occurrences[blk] = occurrences.get(blk, 0) + 1
        if occurrences != self._ref:
            off = {blk: (occurrences.get(blk, 0), self._ref.get(blk, 0))
                   for blk in set(occurrences) ^ set(self._ref)
                   or {b for b in occurrences
                       if occurrences[b] != self._ref.get(b)}}
            fail(f"refcounts disagree with table occurrences "
                 f"(block: (occurrences, refcount)): {off}")
        # 3. per-request table shape: lens keyed like tables, nonempty
        #    tables, enough blocks to cover the committed length (>= — a
        #    reserve may over-grow the table ahead of its commit)
        if set(self._lens) != set(self._tables):
            fail(f"_lens keys {sorted(self._lens)} != _tables keys "
                 f"{sorted(self._tables)}")
        for rid, table in self._tables.items():
            if not table:
                fail(f"request {rid} has an empty block table")
            need = -(-self._lens[rid] // bs)
            if len(table) < need:
                fail(f"request {rid}: {len(table)} blocks cover only "
                     f"{len(table) * bs} tokens < committed {self._lens[rid]}")
        # 4. prefix cache is a bijection and covers every cached-free block
        if {k: b for b, k in self._hash_of.items()} != dict(self._block_of):
            fail("prefix cache maps are not inverse bijections")
        if not cached <= set(self._hash_of):
            fail(f"cached-free blocks without a content hash: "
                 f"{sorted(cached - set(self._hash_of))}")
        # 5. watermarks in range (NOT <= committed fill: CoW carries the
        #    donor's watermark, which may exceed the new holder's fill)
        for blk, w in self._written.items():
            if not 0 <= w <= bs:
                fail(f"block {blk} watermark {w} outside [0, {bs}]")
        # 6. tier-op ordering: a promote's data must exist by the time it
        #    is applied — set at demotion or host-pool insertion
        for kind, entry, blk in self.pending_tier_ops:
            if kind == "promote" and entry.data is None:
                fail(f"pending promote of block {blk} has no host data")
        # 7. CoW queue: endpoints in range, destination refcounted
        for src, dst in self.pending_copies:
            if not (0 <= src < self.num_blocks
                    and 0 <= dst < self.num_blocks):
                fail(f"pending copy ({src}, {dst}) out of range")
            if dst not in self._ref:
                fail(f"pending copy destination {dst} is not a live block")
        # 8. fully drained state
        if drained:
            if self.num_free != self.num_blocks:
                fail(f"not drained: {self.num_free}/{self.num_blocks} free")
            if self._tables or self.pending_copies or self.pending_tier_ops:
                fail(f"not drained: tables={sorted(self._tables)} "
                     f"copies={self.pending_copies} "
                     f"tier_ops={len(self.pending_tier_ops)}")

    def ref_count(self, block: int) -> int:
        return self._ref.get(block, 0)

    def written(self, block: int) -> int:
        """KV-written watermark of a physical block (0 if never written)."""
        return self._written.get(block, 0)

    def transferable(self, req_id: int) -> bool:
        """True iff every committed token's KV is watermark-covered — the
        prefill→decode handoff contract: the request's blocks can be copied
        out of this pool without reading unwritten slots."""
        pos = self._lens[req_id]
        for i, blk in enumerate(self._tables[req_id]):
            need = min(max(pos - i * self.block_size, 0), self.block_size)
            if self._written.get(blk, 0) < need:
                return False
        return True

    def block_stats(self, block: int) -> BlockStats:
        """Eviction evidence for ``block`` (empty record if never touched)."""
        return self._stats.setdefault(block, BlockStats())

    def seq_len(self, req_id: int) -> int:
        return self._lens[req_id]

    def table(self, req_id: int) -> List[int]:
        return list(self._tables[req_id])

    # -- device-layout builders ----------------------------------------------
    def build_block_table(self, req_ids: List[int], max_blocks: int,
                          pad_block: int = 0) -> Tuple[np.ndarray, np.ndarray]:
        """Baseline padded layout (vLLM_base): (B, max_blocks) + seq_lens (B,).

        Padding entries point at ``pad_block`` — they are *gathered anyway* by
        the baseline kernel, reproducing the paper's redundant-gather cost.
        """
        B = len(req_ids)
        tab = np.full((B, max_blocks), pad_block, np.int32)
        lens = np.zeros((B,), np.int32)
        for i, r in enumerate(req_ids):
            t = self._tables[r]
            assert len(t) <= max_blocks, (len(t), max_blocks)
            tab[i, :len(t)] = t
            lens[i] = self._lens[r]
        return tab, lens

    def build_block_list(self, req_ids: List[int], max_total: Optional[int] = None
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Optimized flat layout (vLLM_opt / this framework).

        Returns (block_list, block_req, block_pos, seq_lens):
          block_list (T,) pool indices of ONLY effectual blocks
          block_req  (T,) owning request index in [0,B)
          block_pos  (T,) block's ordinal position within its request
          seq_lens   (B,)
        Padded (if max_total given) with req = B (out-of-range ⇒ dropped by
        segment ops) so the array shape is static for jit.
        """
        lists, reqs, poss = [], [], []
        lens = np.zeros((len(req_ids),), np.int32)
        for i, r in enumerate(req_ids):
            t = self._tables[r]
            lists.extend(t)
            reqs.extend([i] * len(t))
            poss.extend(range(len(t)))
            lens[i] = self._lens[r]
        T = len(lists)
        if max_total is not None:
            assert T <= max_total, (T, max_total)
            pad = max_total - T
            lists.extend([0] * pad)
            reqs.extend([len(req_ids)] * pad)   # out-of-range segment id
            poss.extend([0] * pad)
        return (np.asarray(lists, np.int32), np.asarray(reqs, np.int32),
                np.asarray(poss, np.int32), lens)

    def build_sharded_block_lists(self, req_slots: List[Tuple[int, int]],
                                  pad_req: int,
                                  min_per_shard: Optional[int] = None,
                                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Per-shard LOCAL BlockLists — the chunked sharded path's render.

        The sharded sibling of the flat list the engine renders per step:
        each entry of a request's table lands on its PHYSICAL owner shard
        (``shard_of``) as a LOCAL pool index (``block % blocks_per_shard``),
        keyed by the caller-supplied slot id (``req_slots`` is
        ``[(req_id, slot), ...]``) with its ordinal block position.  Sharding
        the resulting (S, M) arrays on dim 0 hands every shard_map rank
        exactly the slice of the BlockList its pool shard can serve —
        ``paged_attention_chunked_sharded`` combines the partials.

        ``M`` is ``min_per_shard`` (default ``blocks_per_shard``, mirroring
        the single-device render's pool-size capacity) grown by
        power-of-two doubling when prefix-shared tables overflow it, so the
        engine's jit cache stays O(log) programs.  Padding entries carry
        ``pad_req`` (an out-of-range slot id ⇒ masked by the kernel).
        Returns ``(block_list, block_req, block_pos)``, each (S, M) int32.
        """
        S = self.num_shards
        per_shard = self.blocks_per_shard
        entries: List[List[Tuple[int, int, int]]] = [[] for _ in range(S)]
        for r, slot in req_slots:
            for k, b in enumerate(self._tables[r]):
                entries[b // per_shard].append((b % per_shard, slot, k))
        cap = min_per_shard if min_per_shard is not None else per_shard
        need = max((len(e) for e in entries), default=0)
        while cap < need:
            cap *= 2
        bl = np.zeros((S, cap), np.int32)
        br = np.full((S, cap), pad_req, np.int32)
        bp = np.zeros((S, cap), np.int32)
        for s in range(S):
            for j, (b, slot, k) in enumerate(entries[s]):
                bl[s, j], br[s, j], bp[s, j] = b, slot, k
        return bl, br, bp

    def write_slots(self, req_ids: List[int]) -> np.ndarray:
        """(B, 2) [block, offset] where the NEXT token of each request lands.

        Reserves blocks on demand (call :meth:`commit_token` after the step).
        """
        out = np.zeros((len(req_ids), 2), np.int32)
        for i, r in enumerate(req_ids):
            out[i] = self.reserve_tokens(r, 1)[0]
        return out


# ---------------------------------------------------------------------------
# Device-side pool ops (pure jnp; shapes are jit-static)
# ---------------------------------------------------------------------------
def make_pool(num_layers: int, num_blocks: int, block_size: int,
              num_kv: int, head_dim: int, dtype=jnp.bfloat16):
    shape = (num_layers, num_blocks, block_size, num_kv, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def make_fused_pool(num_layers: int, num_blocks: int, block_size: int,
                    num_kv: int, head_dim: int, dtype=jnp.bfloat16):
    """ONE KV buffer, head-major per page, K and V side by side on the
    minor axis (docs/ragged_kernel.md).

    Shape (L, NB, KV, BS, 2*HD): lanes ``[:HD]`` of a page row hold K and
    ``[HD:]`` hold V.  Every whole-block move (CoW block copy, tier
    demote/promote, disagg handoff, the kernel's HBM->VMEM page DMA) is ONE
    transfer instead of two, and a page's two minor dims are (BS, 2*HD):
    a head_dim-64 page is made of whole 128-lane rows with the page size on
    the sublanes, the tiling the TPU's DMA engine moves and the HBM layout
    pads nothing of.  ``fused_kv_views`` recovers split (k, v) pools for
    math written against them; ``append_to_fused_pool`` writes fresh
    per-token K/V.
    """
    shape = (num_layers, num_blocks, num_kv, block_size, 2 * head_dim)
    return jnp.zeros(shape, dtype)


def fused_kv_views(pool):
    """Split pools from a fused one: ``(..., KV, BS, 2*HD) -> k, v`` of
    shape ``(..., BS, KV, HD)``, the split-pool layout.

    Valid for any leading dims — a whole layer stack, one layer, or a
    single page.  The views hold exactly the values a split pool would, so
    math running on them is bit-identical to the split layout.
    """
    hd = pool.shape[-1] // 2
    split = jnp.swapaxes(pool, -3, -2)
    return split[..., :hd], split[..., hd:]


def fuse_kv_heads(pool_k, pool_v):
    """Inverse of :func:`fused_kv_views`: split pools ``(..., BS, KV, HD)
    x2 -> (..., KV, BS, 2*HD)``, K in the low lanes and V in the high ones.
    """
    return jnp.swapaxes(jnp.concatenate([pool_k, pool_v], axis=-1), -3, -2)


def append_to_pool(pool_layer, kv_new, slots):
    """Write one token per request into a single layer's split pool.

    pool_layer (NB, BS, KV, HD); kv_new (B, KV, HD); slots (B, 2) [block, off].
    Out-of-range slots (e.g. (NB, 0) on non-owning model ranks of a sharded
    pool) are dropped — this is how sharded writes stay shard-local.
    """
    return pool_layer.at[slots[:, 0], slots[:, 1]].set(
        kv_new.astype(pool_layer.dtype), mode="drop")


def append_to_fused_pool(pool_layer, k_new, v_new, slots):
    """Write one token per lane into a single layer's fused pool.

    pool_layer (NB, KV, BS, 2*HD); k_new/v_new (T, KV, HD); slots (T, 2)
    [block, off].  Out-of-range slots are dropped, as in
    :func:`append_to_pool`.
    """
    kv_new = jnp.concatenate([k_new, v_new], axis=-1)        # (T, KV, 2*HD)
    return pool_layer.at[slots[:, 0], :, slots[:, 1]].set(
        kv_new.astype(pool_layer.dtype), mode="drop")


def copy_pool_blocks(pool, srcs, dsts):
    """Copy whole blocks across the layer-stacked pool (copy-on-write).

    pool (L, NB, BS, KV, HD); srcs/dsts (n,) block indices.  Out-of-bounds
    entries (src = dst = NB) are inert padding: the gather clips to the last
    block and the ``mode="drop"`` scatter discards the write — callers pad
    the copy count to a power-of-two bucket so a varying number of CoW
    copies per step reuses a handful of compiled programs.
    """
    NB = pool.shape[1]
    vals = jnp.take(pool, jnp.minimum(srcs, NB - 1), axis=1)
    return pool.at[:, dsts].set(vals, mode="drop")


def gather_prefill_into_pool(pool_layer, k_seq, block_table, seq_len: int,
                             block_size: int):
    """Scatter a prefilled (B, S, KV, HD) K (or V) into pool blocks.

    block_table (B, nb) lists each request's blocks in order.
    """
    B, S = k_seq.shape[:2]
    nb = block_table.shape[1]
    assert nb * block_size >= S
    k_blocks = k_seq.reshape(B, S // block_size, block_size, *k_seq.shape[2:])
    flat_idx = block_table[:, :S // block_size].reshape(-1)
    return pool_layer.at[flat_idx].set(
        k_blocks.reshape((-1,) + k_blocks.shape[2:]).astype(pool_layer.dtype))
