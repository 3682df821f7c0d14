"""PagedAttention: baseline padded BlockTable vs optimized flat BlockList.

Reproduces the paper's §4.2 vLLM case study as a TPU-native op pair:

* :func:`paged_attention_base` — vLLM_base analogue. Gathers **every** entry
  of the padded (B, max_blocks) BlockTable, including zero-pad blocks, then
  masks. The redundant gathers are real HLO bytes (visible in cost analysis),
  exactly the waste the paper measures (Fig 17b).
* :func:`paged_attention_opt` — vLLM_opt analogue. A flat BlockList of only
  effectual blocks drives a *batched GEMM* over (total_blocks, block_size)
  tiles with a segment-softmax across each request's blocks. This is the
  MXU-friendly restructuring the paper performs at the PyTorch level; here it
  is also the exact math of the Pallas kernel in
  ``repro.kernels.paged_attention`` (scalar-prefetched index_map).
* :func:`paged_attention_sharded` — beyond-paper: flash-decoding combine of
  the opt path across a mesh axis (sequence-sharded KV pool), used by the
  multi-pod ``serve_step``.
* :func:`paged_attention_chunked` — chunked-prefill generalization: a flat
  batch of query *tokens* (decode tokens and prompt-chunk tokens mixed) each
  attends causally to its request's pool blocks. With one token per request
  it reduces to the opt path; with a chunk it is prefill-in-the-decode-step,
  which is what lets the serving engine run ONE fused program per step.
* :func:`paged_attention_chunked_sharded` — the two combined: the chunked
  math over a sequence-sharded KV pool inside ``shard_map``. Each rank holds
  a shard of the pool plus ITS OWN local BlockList slice
  (``BlockAllocator.build_sharded_block_lists``), computes flash-style
  partials (running max / sumexp / weighted-V) for every query lane against
  only local blocks, and the partials are log-sum-exp-combined across the
  mesh axis with (T, H)-sized collectives — the KV never moves.  This is
  the sharded serving engine's per-layer attention (docs/sharded_serving.md)
  and the ``sharded`` backend of the ``paged_attention_chunked`` op family.

All math: q (B, H, HD) single decode token (or (T, H, HD) flat token lanes
for the chunked op); pool (NB, BS, KV, HD). GQA handled by grouping H into
KV groups. f32 softmax accumulation.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import dispatch

NEG_INF = -1e30


def _q_grouped(q, num_kv: int):
    B, H, HD = q.shape
    return q.reshape(B, num_kv, H // num_kv, HD)


def paged_attention_base(q, pool_k, pool_v, block_table, seq_lens,
                         *, sm_scale: Optional[float] = None):
    """Baseline: padded BlockTable (B, MAXB). Gathers pad blocks too."""
    B, H, HD = q.shape
    NB, BS, KV, _ = pool_k.shape
    MAXB = block_table.shape[1]
    scale = sm_scale if sm_scale is not None else HD ** -0.5

    # Redundant gather: (B, MAXB, BS, KV, HD) — pads included, as in vLLM_base.
    k = jnp.take(pool_k, block_table.reshape(-1), axis=0).reshape(
        B, MAXB, BS, KV, HD)
    v = jnp.take(pool_v, block_table.reshape(-1), axis=0).reshape(
        B, MAXB, BS, KV, HD)
    qg = _q_grouped(q, KV)
    scores = jnp.einsum("bkgd,bmskd->bkgms", qg, k).astype(jnp.float32) * scale
    pos = (jnp.arange(MAXB)[:, None] * BS + jnp.arange(BS)[None, :])  # (MAXB,BS)
    mask = pos[None] < seq_lens[:, None, None]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores.reshape(B, KV, qg.shape[2], -1), axis=-1)
    w = w.reshape(scores.shape).astype(v.dtype)
    out = jnp.einsum("bkgms,bmskd->bkgd", w, v)
    return out.reshape(B, H, HD)


def _opt_partials(q, pool_k, pool_v, block_list, block_req, block_pos,
                  seq_lens, num_reqs: int, scale: float):
    """Per-request (max, sumexp, weighted-V) from a flat BlockList segment."""
    B, H, HD = q.shape
    NB, BS, KV, _ = pool_k.shape
    T = block_list.shape[0]
    G = H // KV

    k = jnp.take(pool_k, block_list, axis=0)              # (T, BS, KV, HD)
    v = jnp.take(pool_v, block_list, axis=0)
    req = jnp.clip(block_req, 0, B - 1)
    qg = _q_grouped(q, KV)[req]                           # (T, KV, G, HD)
    scores = jnp.einsum("tkgd,tskd->tkgs", qg, k).astype(jnp.float32) * scale
    pos = block_pos[:, None] * BS + jnp.arange(BS)[None]  # (T, BS)
    valid = (pos < seq_lens[jnp.clip(block_req, 0, B - 1)][:, None]) & (
        block_req[:, None] < num_reqs)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)

    seg = jnp.where(block_req < num_reqs, block_req, B)   # pad -> dropped
    m_t = scores.max(axis=-1)                             # (T, KV, G)
    m = jax.ops.segment_max(m_t, seg, num_segments=B + 1)[:B]
    m = jnp.maximum(m, -1e30)
    p = jnp.exp(scores - m[jnp.clip(seg, 0, B - 1)][:, :, :, None])
    p = jnp.where(valid[:, None, None], p, 0.0)
    l_t = p.sum(axis=-1)                                  # (T, KV, G)
    l = jax.ops.segment_sum(l_t, seg, num_segments=B + 1)[:B]
    o_t = jnp.einsum("tkgs,tskd->tkgd", p.astype(v.dtype), v).astype(jnp.float32)
    o = jax.ops.segment_sum(o_t, seg, num_segments=B + 1)[:B]
    return m, l, o                                        # (B,KV,G),(B,KV,G),(B,KV,G,HD)


def paged_attention_opt(q, pool_k, pool_v, block_list, block_req, block_pos,
                        seq_lens, *, sm_scale: Optional[float] = None):
    """Optimized: flat BlockList — only effectual blocks are touched."""
    B, H, HD = q.shape
    KV = pool_k.shape[2]
    scale = sm_scale if sm_scale is not None else HD ** -0.5
    m, l, o = _opt_partials(q, pool_k, pool_v, block_list, block_req,
                            block_pos, seq_lens, B, scale)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, HD).astype(q.dtype)


def paged_attention_sharded(q, pool_k, pool_v, block_list, block_req,
                            block_pos, seq_lens, *, axis: str,
                            sm_scale: Optional[float] = None):
    """Flash-decoding combine across mesh axis ``axis`` (inside shard_map).

    Each rank holds a shard of the pool and ITS OWN BlockList slice (built by
    ``BlockAllocator.build_sharded_block_lists``). Partials are combined with
    small (B,H)-sized collectives — the sequence dimension never moves.
    """
    B, H, HD = q.shape
    scale = sm_scale if sm_scale is not None else HD ** -0.5
    m_r, l_r, o_r = _opt_partials(q, pool_k, pool_v, block_list, block_req,
                                  block_pos, seq_lens, B, scale)
    m = jax.lax.pmax(m_r, axis)
    corr = jnp.exp(m_r - m)
    l = jax.lax.psum(l_r * corr, axis)
    o = jax.lax.psum(o_r * corr[..., None], axis)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, HD).astype(q.dtype)


def paged_attention_chunked(q, pool_k, pool_v, block_list, block_req,
                            block_pos, kv_lens, token_req, token_pos,
                            *, sm_scale: Optional[float] = None):
    """Chunked-prefill paged attention over flat token lanes.

    q         (T, H, HD)  queries — a mix of decode tokens (one per request)
                          and prompt-chunk tokens (several per request)
    block_*   (Tb,)       flat BlockList as in :func:`paged_attention_opt`,
                          with ``block_req`` holding request/slot ids
    kv_lens   (B,)        total valid KV per request AFTER this step's tokens
                          were appended to the pool
    token_req (T,)        owning request/slot of each query lane (>= B ⇒ pad)
    token_pos (T,)        absolute sequence position of each query token

    Each query attends to keys of its own request with ``key_pos <=
    token_pos`` (causal within the chunk — the chunk's own KV is already in
    the pool). Padding lanes produce zeros. With T == B and one token per
    request this computes exactly :func:`paged_attention_opt`.
    """
    T, H, HD = q.shape
    scale = sm_scale if sm_scale is not None else HD ** -0.5
    m, l, o = _chunked_partials(q, pool_k, pool_v, block_list, block_req,
                                block_pos, kv_lens, token_req, token_pos,
                                scale)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(T, H, HD).astype(q.dtype)


def _chunked_partials(q, pool_k, pool_v, block_list, block_req, block_pos,
                      kv_lens, token_req, token_pos, scale: float):
    """Per-lane flash partials of the chunked math over a BlockList slice.

    Returns ``(m, l, o)`` with shapes (T, KV, G), (T, KV, G), (T, KV, G, HD):
    the running max, sum of exponentials and weighted-V accumulator of every
    query lane against ONLY the blocks in ``block_list``.  With the full
    BlockList this normalizes to :func:`paged_attention_chunked`; with a
    per-shard slice the partials are what the sharded combine reduces.  A
    lane that owns no block here has ``m == -1e30`` and ``l == 0`` — the
    combine's exp-correction weighs it out exactly.
    """
    T, H, HD = q.shape
    NB, BS, KV, _ = pool_k.shape
    B = kv_lens.shape[0]
    G = H // KV

    k = jnp.take(pool_k, block_list, axis=0)              # (Tb, BS, KV, HD)
    v = jnp.take(pool_v, block_list, axis=0)
    qg = q.reshape(T, KV, G, HD)
    scores = jnp.einsum("tkgd,uskd->tkgus", qg, k).astype(jnp.float32) * scale
    key_pos = block_pos[:, None] * BS + jnp.arange(BS)[None]    # (Tb, BS)
    breq = jnp.clip(block_req, 0, B - 1)
    valid = ((block_req[None, :] == token_req[:, None])         # (T, Tb)
             & (block_req[None, :] < B)
             & (token_req[:, None] < B))
    valid = (valid[:, :, None]
             & (key_pos[None] <= token_pos[:, None, None])      # causal
             & (key_pos[None] < kv_lens[breq][None, :, None]))  # (T, Tb, BS)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=(-2, -1))                    # (T, KV, G)
    m = jnp.maximum(m, -1e30)
    p = jnp.exp(scores - m[:, :, :, None, None])
    p = jnp.where(valid[:, None, None], p, 0.0)
    l = p.sum(axis=(-2, -1))                              # (T, KV, G)
    o = jnp.einsum("tkgus,uskd->tkgd", p.astype(v.dtype), v).astype(jnp.float32)
    return m, l, o


def paged_attention_chunked_sharded(q, pool_k, pool_v, block_list, block_req,
                                    block_pos, kv_lens, token_req, token_pos,
                                    *, axis: str,
                                    sm_scale: Optional[float] = None):
    """Chunked paged attention over a sequence-sharded pool (inside shard_map).

    The chunked generalization of :func:`paged_attention_sharded`: every
    query *lane* (decode tokens, prompt-chunk tokens, speculative draft
    lanes — anything :func:`paged_attention_chunked` accepts) computes
    flash partials against its rank's pool shard and LOCAL BlockList slice
    (built by ``BlockAllocator.build_sharded_block_lists``), then the
    per-rank (max, sumexp, weighted-V) triples are log-sum-exp-combined
    across mesh axis ``axis`` with (T, H)-sized collectives.  The sequence
    dimension never moves; lanes whose blocks all live on other ranks are
    weighed out by the exp correction.  Padding lanes produce zeros, like
    the single-device op.
    """
    T, H, HD = q.shape
    scale = sm_scale if sm_scale is not None else HD ** -0.5
    m_r, l_r, o_r = _chunked_partials(q, pool_k, pool_v, block_list,
                                      block_req, block_pos, kv_lens,
                                      token_req, token_pos, scale)
    m = jax.lax.pmax(m_r, axis)
    corr = jnp.exp(m_r - m)
    l = jax.lax.psum(l_r * corr, axis)
    o = jax.lax.psum(o_r * corr[..., None], axis)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(T, H, HD).astype(q.dtype)


def ragged_lane_metadata(cu_q_lens, cu_kv_lens, seq_slot, num_lanes: int,
                         num_slots: int):
    """Derive per-lane ``(token_req, token_pos, kv_lens)`` from ragged
    cu_q_lens/cu_kv_lens metadata (docs/ragged_kernel.md).

    The ragged contract indexes SEQUENCES in lane order: sequence ``j`` owns
    query lanes ``[cu_q_lens[j], cu_q_lens[j+1])``, holds ``cu_kv_lens[j+1] -
    cu_kv_lens[j]`` valid KV positions after this step's append, and lives in
    engine slot ``seq_slot[j]`` (an out-of-range slot marks an empty padding
    entry).  A sequence's query lanes are always its LAST ``nq`` positions —
    true for decode lanes, prefill chunks and speculative draft lanes alike,
    because the engine reserves this step's KV before rendering.

    Returns arrays bit-identical to the engine's rendered lane metadata:
    ``token_req``/``token_pos`` (num_lanes,) and slot-keyed ``kv_lens``
    (num_slots,) — lanes past ``cu_q_lens[-1]`` become padding lanes
    (owner == num_slots, every key masked).
    """
    nseq = seq_slot.shape[0]
    lanes = jnp.arange(num_lanes, dtype=jnp.int32)
    # rightmost j with cu_q_lens[j] <= lane (the prefix sums are sorted, so
    # a count of them; it skips empty entries)
    cu = cu_q_lens.astype(jnp.int32)
    j = (cu[None, :] <= lanes[:, None]).sum(axis=1, dtype=jnp.int32) - 1
    j = jnp.clip(j, 0, nseq - 1)
    nq = cu_q_lens[1:] - cu_q_lens[:-1]                  # (nseq,)
    kvl = cu_kv_lens[1:] - cu_kv_lens[:-1]               # (nseq,)
    in_range = lanes < cu_q_lens[-1]
    token_req = jnp.where(in_range, seq_slot[j], num_slots).astype(jnp.int32)
    token_pos = jnp.where(
        in_range, kvl[j] - nq[j] + (lanes - cu_q_lens[j]), 0).astype(jnp.int32)
    kv_lens = jnp.zeros((num_slots,), jnp.int32).at[seq_slot].set(
        kvl.astype(jnp.int32), mode="drop")              # pads dropped
    return token_req, token_pos, kv_lens


def paged_attention_ragged(q, kv_pool, block_list, block_req, block_pos,
                           cu_q_lens, cu_kv_lens, seq_slot,
                           *, sm_scale: Optional[float] = None):
    """One ragged launch for mixed prefill-chunk + decode lanes over the
    FUSED KV pool (the ``ref`` oracle of the ``paged_attention_ragged``
    family).

    q          (T, H, HD)   flat token lanes, sequences contiguous in lane
                            order (decode lanes and prompt-chunk lanes mixed)
    kv_pool    (NB, KV, BS, 2*HD)  fused pool layer, K and V side by side
                            on the minor axis
                            (:func:`repro.core.paged_kv.make_fused_pool`)
    block_*    (Tb,)        flat BlockList keyed by slot id, as in
                            :func:`paged_attention_chunked`
    cu_q_lens  (S+1,)       prefix sums of per-sequence query-lane counts
    cu_kv_lens (S+1,)       prefix sums of per-sequence valid-KV counts
                            (AFTER this step's tokens were appended)
    seq_slot   (S,)         sequence -> engine slot id (>= S ⇒ empty entry)

    The lane metadata is DERIVED from the ragged prefix sums
    (:func:`ragged_lane_metadata`) and the attention math is exactly
    :func:`_chunked_partials` over split views of the fused pool — integer
    derivation cannot perturb float ops, so results are bit-identical to the
    chunked path on the same workload.
    """
    from repro.core import paged_kv

    T, H, HD = q.shape
    S = seq_slot.shape[0]
    scale = sm_scale if sm_scale is not None else HD ** -0.5
    pool_k, pool_v = paged_kv.fused_kv_views(kv_pool)
    token_req, token_pos, kv_lens = ragged_lane_metadata(
        cu_q_lens, cu_kv_lens, seq_slot, T, S)
    m, l, o = _chunked_partials(q, pool_k, pool_v, block_list, block_req,
                                block_pos, kv_lens, token_req, token_pos,
                                scale)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(T, H, HD).astype(q.dtype)


def paged_attention_ragged_sharded(q, kv_pool, block_list, block_req,
                                   block_pos, cu_q_lens, cu_kv_lens, seq_slot,
                                   *, axis: str,
                                   sm_scale: Optional[float] = None):
    """Ragged attention over a sequence-sharded FUSED pool (inside shard_map).

    The ragged metadata is replicated (every rank derives the same lane
    arrays); each rank computes chunked flash partials against its pool
    shard's LOCAL BlockList slice and the triples are log-sum-exp-combined
    across ``axis`` — exactly :func:`paged_attention_chunked_sharded` on
    split views of the fused shard, so the sharded ragged engine stays
    bit-identical to the sharded chunked engine.
    """
    from repro.core import paged_kv

    T = q.shape[0]
    S = seq_slot.shape[0]
    pool_k, pool_v = paged_kv.fused_kv_views(kv_pool)
    token_req, token_pos, kv_lens = ragged_lane_metadata(
        cu_q_lens, cu_kv_lens, seq_slot, T, S)
    return paged_attention_chunked_sharded(
        q, pool_k, pool_v, block_list, block_req, block_pos, kv_lens,
        token_req, token_pos, axis=axis, sm_scale=sm_scale)


def paged_attention(q, pool_k, pool_v, block_list, block_req, block_pos,
                    seq_lens, backend=None):
    """Decode-shape PagedAttention through the unified registry.

    ONE resolver call (:mod:`repro.core.dispatch`): explicit ``backend`` is
    strict and round-trips to the named implementation; ``None`` follows
    scope/env/config/auto precedence.  Implementations are registered in
    ``repro.kernels.paged_attention.ops``.
    """
    return dispatch.get_op("paged_attention")(
        q, pool_k, pool_v, block_list, block_req, block_pos, seq_lens,
        backend=backend)


def paged_attention_chunked_op(q, pool_k, pool_v, block_list, block_req,
                               block_pos, kv_lens, token_req, token_pos,
                               *, backend=None, q_chunk: int = 16,
                               prefetch_depth: int = 0):
    """Chunked-prefill PagedAttention through the unified registry.

    Same contract as :func:`paged_attention_chunked` (which is the ``ref``
    implementation); ``pallas``/``pallas_interpret`` select the query-chunk
    grid kernel in ``repro.kernels.paged_attention.kernel``.
    ``prefetch_depth`` >= 2 additionally selects the multi-buffered KV-page
    DMA ring in the Pallas kernel (jnp backends ignore it); both knobs are
    declared as family tunables in the registry.
    """
    return dispatch.get_op("paged_attention_chunked")(
        q, pool_k, pool_v, block_list, block_req, block_pos, kv_lens,
        token_req, token_pos, q_chunk=q_chunk, prefetch_depth=prefetch_depth,
        backend=backend)


def paged_attention_ragged_op(q, kv_pool, block_list, block_req, block_pos,
                              cu_q_lens, cu_kv_lens, seq_slot, *,
                              backend=None, num_queries_per_block: int = 16,
                              num_kv_pages_per_block: int = 1,
                              vmem_limit_bytes: int = 0):
    """Ragged fused-pool PagedAttention through the unified registry.

    Same contract as :func:`paged_attention_ragged` (the ``ref``
    implementation); ``pallas``/``pallas_interpret`` select the ragged grid
    kernel in ``repro.kernels.paged_attention.kernel``.  The three kwargs are
    the family's registered tunables (docs/ragged_kernel.md):
    ``num_queries_per_block`` is the query-tile row count,
    ``num_kv_pages_per_block`` how many KV pages one step of a query tile's
    walk consumes from the double-buffered fused-page DMA ring, and
    ``vmem_limit_bytes`` caps the ring's VMEM footprint (0 = uncapped).
    jnp backends ignore all three; measured best configs per (page_size,
    head_dim, backend) live in the committed autotune table
    (``repro.perf.autotune``).
    """
    return dispatch.get_op("paged_attention_ragged")(
        q, kv_pool, block_list, block_req, block_pos, cu_q_lens, cu_kv_lens,
        seq_slot, num_queries_per_block=num_queries_per_block,
        num_kv_pages_per_block=num_kv_pages_per_block,
        vmem_limit_bytes=vmem_limit_bytes, backend=backend)
