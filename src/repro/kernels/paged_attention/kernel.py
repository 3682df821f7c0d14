"""PagedAttention kernels — the paper's BlockList technique, TPU-native.

The flat BlockList of *effectual* KV-block indices drives the KV fetches:
scalar prefetch (``pltpu.PrefetchScalarGridSpec``) puts the block ids in
SMEM, and each fetch DMAs exactly one useful page from the HBM pool into
VMEM. In the decode and chunked kernels the BlockList IS the Pallas grid
(the BlockSpec ``index_map`` reads the block id); the ragged kernel's grid
is the query tiles, and each tile walks only the BlockList range that
holds its own sequences' entries, ringing its own page DMAs. Zero-pad
blocks never leave HBM — this is the TPU realization of vLLM_opt's "gather
only effectual blocks" (paper Fig 16b), with the online-softmax
accumulation replacing the separate Softmax launch.

The BlockList is sorted by request (the allocator guarantees it), so per-
request accumulators live in VMEM scratch across the blocks of one request;
output rows are rewritten as the running normalized value and the final
page for a request leaves the correct result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    # scalar-prefetched
    block_list, block_req, block_pos, seq_lens,
    # blocked inputs
    q_ref, k_ref, v_ref,
    # output
    o_ref,
    # scratch
    acc_ref, m_ref, l_ref,
    *, bs: int, num_kv: int, num_reqs: int, sm_scale: float,
):
    t = pl.program_id(0)
    req = block_req[t]
    is_pad = req >= num_reqs
    prev_req = block_req[jnp.maximum(t - 1, 0)]
    first = jnp.logical_or(t == 0, req != prev_req)

    @pl.when(jnp.logical_and(first, jnp.logical_not(is_pad)))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(jnp.logical_not(is_pad))
    def _step():
        pos = block_pos[t] * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, bs), 1)                     # (1, bs)
        valid = pos < seq_lens[jnp.minimum(req, num_reqs - 1)]
        # Rewrites the running normalized output; the last block of this
        # request leaves the final value.
        _flash_update(q_ref, lambda kv: (k_ref[0, :, kv, :],
                                         v_ref[0, :, kv, :]),
                      o_ref, acc_ref, m_ref, l_ref, valid, num_kv=num_kv,
                      sm_scale=sm_scale)


def paged_attention_pallas(q, pool_k, pool_v, block_list, block_req,
                           block_pos, seq_lens, *, sm_scale=None,
                           interpret: bool = True):
    """q (B,H,hd); pools (NB,BS,KV,hd); flat BlockList arrays (T,).

    Each request's query heads are laid out per KV head outside the kernel
    ((B, KV, G, hd)), so every matmul inside is a 2-D (G, hd) x (hd, bs).
    """
    B, H, hd = q.shape
    NB, BS, KV, _ = pool_k.shape
    G = H // KV
    T = block_list.shape[0]
    scale = float(sm_scale if sm_scale is not None else hd ** -0.5)

    kernel = functools.partial(_paged_kernel, bs=BS, num_kv=KV, num_reqs=B,
                               sm_scale=scale)

    # index maps take (grid ids, *prefetched scalars)
    def q_map(t, bl, br, bp, sl):
        return (jnp.minimum(br[t], B - 1), 0, 0, 0)

    def kv_map(t, bl, br, bp, sl):
        return (bl[t], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), q_map),
            pl.BlockSpec((1, BS, KV, hd), kv_map),
            pl.BlockSpec((1, BS, KV, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, G, hd), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_list, block_req, block_pos, seq_lens, q.reshape(B, KV, G, hd),
      pool_k, pool_v)
    return out.reshape(B, H, hd)


def _flash_update(q_ref, page, o_ref, acc_ref, m_ref, l_ref, valid, *,
                  num_kv: int, sm_scale: float):
    """One online-softmax update of a query tile against one KV page.

    ``q_ref``/``o_ref`` blocks are (1, KV, R, hd): the R query rows of each
    KV head's group, laid out outside the kernel so no head group is
    reshaped in VMEM (Mosaic refuses a (TQ, G, hd) -> (TQ*G, hd) cast when
    G is not tile-aligned).  ``page(kv)`` returns that KV head's (bs, hd)
    K and V values; ``valid`` is an (R, bs) or (1, bs) mask.  Scratch is
    (KV, R, hd) for the accumulator and (KV, R, 1) for the running max and
    sum.  Shared by all three kernels so their math cannot drift.
    """
    for kv in range(num_kv):                       # static small loop
        q = q_ref[0, kv]                           # (R, hd)
        k, v = page(kv)                            # (bs, hd) each
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                           # (R, bs)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[kv]                         # (R, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        l_new = l_ref[kv] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc = acc_ref[kv] * corr + pv
        acc_ref[kv] = acc
        m_ref[kv] = m_new
        l_ref[kv] = l_new
        # Rewrite the running normalized output; the last page of the tile
        # leaves the final value.
        o_ref[0, kv] = (acc / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


def _group_lanes(q, token_req, token_pos, num_kv: int, tq: int):
    """Lay flat lanes out per KV head for the token-lane kernels.

    q (Tp, H, hd) -> (Tp/tq, KV, G*tq, hd), rows ordered (group head, lane)
    within each query tile; the lane metadata (Tp,) is repeated to match as
    (Tp/tq, G*tq, 1), so the kernel's mask is built at the matmul's shape.
    """
    Tp, H, hd = q.shape
    G = H // num_kv
    n = Tp // tq
    qg = q.reshape(n, tq, num_kv, G, hd).transpose(0, 2, 3, 1, 4)

    def rep(x):
        x = jnp.broadcast_to(x.reshape(n, 1, tq), (n, G, tq))
        return x.reshape(n, G * tq, 1).astype(jnp.int32)

    return (qg.reshape(n, num_kv, G * tq, hd), rep(token_req),
            rep(token_pos))


def _ungroup_lanes(o, T: int, tq: int):
    """Inverse of :func:`_group_lanes` on the output: -> (T, H, hd)."""
    n, KV, R, hd = o.shape
    G = R // tq
    o = o.reshape(n, KV, G, tq, hd).transpose(0, 3, 1, 2, 4)
    return o.reshape(n * tq, KV * G, hd)[:T]


def _chunked_valid_mask(block_req, block_pos, kv_lens, treq_ref, tpos_ref,
                        t, *, bs: int, num_reqs: int):
    """(R, bs) ownership+causality+length mask for BlockList entry ``t``.

    ``treq_ref``/``tpos_ref`` are (1, R, 1) blocks of the grouped lane
    metadata (:func:`_group_lanes`)."""
    req = block_req[t]
    treq = treq_ref[0]                             # (R, 1)
    tpos = tpos_ref[0]
    key_pos = block_pos[t] * bs + jax.lax.broadcasted_iota(
        jnp.int32, (1, bs), 1)                     # (1, bs)
    kvl = kv_lens[jnp.minimum(req, num_reqs - 1)]
    lane_ok = (treq == req) & (treq < num_reqs)    # (R, 1)
    return lane_ok & (key_pos <= tpos) & (key_pos < kvl)   # causal, length


def _init_tile(acc_ref, m_ref, l_ref, o_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    # Lanes with no valid keys (padding, empty requests) must read 0.
    o_ref[...] = jnp.zeros_like(o_ref)


def _chunked_kernel(
    # scalar-prefetched
    block_list, block_req, block_pos, kv_lens,
    # blocked inputs
    q_ref, k_ref, v_ref, treq_ref, tpos_ref,
    # output
    o_ref,
    # scratch
    acc_ref, m_ref, l_ref,
    *, bs: int, num_kv: int, num_reqs: int, sm_scale: float,
):
    """Chunked-prefill grid step: one (query-chunk, BlockList entry) pair.

    Grid is (num_q_chunks, T_blocks) with the block dimension innermost, so
    the per-chunk online-softmax accumulators persist in VMEM scratch across
    every BlockList entry of one query chunk.  Lanes of a chunk may belong to
    different requests — ownership, causality and KV length are all enforced
    by the mask, exactly as in ``paged_attention_chunked`` (the jnp ref).
    """
    t = pl.program_id(1)
    is_pad = block_req[t] >= num_reqs

    @pl.when(t == 0)
    def _init():
        _init_tile(acc_ref, m_ref, l_ref, o_ref)

    @pl.when(jnp.logical_not(is_pad))
    def _step():
        valid = _chunked_valid_mask(block_req, block_pos, kv_lens, treq_ref,
                                    tpos_ref, t, bs=bs, num_reqs=num_reqs)
        _flash_update(q_ref, lambda kv: (k_ref[0, :, kv, :],
                                         v_ref[0, :, kv, :]),
                      o_ref, acc_ref, m_ref, l_ref, valid, num_kv=num_kv,
                      sm_scale=sm_scale)


def _chunked_kernel_prefetch(
    # scalar-prefetched
    block_list, block_req, block_pos, kv_lens,
    # blocked inputs (pools stay in HBM/ANY — DMA'd manually below)
    q_ref, k_hbm, v_hbm, treq_ref, tpos_ref,
    # output
    o_ref,
    # scratch
    acc_ref, m_ref, l_ref, k_buf, v_buf, k_sem, v_sem,
    *, bs: int, num_kv: int, num_reqs: int, sm_scale: float, depth: int,
    num_blocks: int,
):
    """Multi-buffered variant: the KV-page HBM→VMEM DMA runs ``depth`` deep.

    Instead of letting the BlockSpec pipeline fetch one (bs, KV, hd) page
    per grid step, the pools stay in HBM (``memory_space=ANY``) and the
    kernel drives its own DMA ring: VMEM scratch holds ``depth`` page slots
    per pool, and at BlockList entry ``t`` the page for entry
    ``t + depth - 1`` is *started* before the page for ``t`` is *waited* —
    so up to ``depth - 1`` page fetches are in flight behind the flash
    inner loop.  Entry 0 of every query chunk warm-starts the first
    ``depth - 1`` pages.  Every started copy is waited exactly once
    (pad entries included — they fetch a real page and skip only the
    compute), keeping the per-slot DMA semaphores balanced across the grid.
    """
    t = pl.program_id(1)
    Tb = pl.num_programs(1)
    is_pad = block_req[t] >= num_reqs

    def start(e):
        slot = jax.lax.rem(e, depth)
        blk = jnp.minimum(block_list[e], num_blocks - 1)
        pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[slot],
                              k_sem.at[slot]).start()
        pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[slot],
                              v_sem.at[slot]).start()

    @pl.when(t == 0)
    def _init():
        _init_tile(acc_ref, m_ref, l_ref, o_ref)
        for d in range(min(depth - 1, Tb)):       # warm-up: fill the ring
            start(jnp.int32(d))

    @pl.when(t + depth - 1 < Tb)                  # steady state: run ahead
    def _ahead():
        start(t + depth - 1)

    slot = jax.lax.rem(t, depth)
    blk = jnp.minimum(block_list[t], num_blocks - 1)
    pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[slot], k_sem.at[slot]).wait()
    pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[slot], v_sem.at[slot]).wait()

    @pl.when(jnp.logical_not(is_pad))
    def _step():
        valid = _chunked_valid_mask(block_req, block_pos, kv_lens, treq_ref,
                                    tpos_ref, t, bs=bs, num_reqs=num_reqs)
        _flash_update(q_ref, lambda kv: (k_buf[slot, :, kv, :],
                                         v_buf[slot, :, kv, :]),
                      o_ref, acc_ref, m_ref, l_ref, valid, num_kv=num_kv,
                      sm_scale=sm_scale)


def _pad_lanes(q, token_req, token_pos, tq: int, num_reqs: int):
    """Pad flat lanes to a multiple of ``tq``; padding lanes get an
    out-of-range owner so every key is masked."""
    pad = (-q.shape[0]) % tq
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        token_req = jnp.pad(token_req, (0, pad), constant_values=num_reqs)
        token_pos = jnp.pad(token_pos, (0, pad))
    return q, token_req, token_pos


def _lane_scratch(KV: int, R: int, hd: int):
    """Flash accumulator, running max and running sum for one query tile."""
    return [pltpu.VMEM((KV, R, hd), jnp.float32),
            pltpu.VMEM((KV, R, 1), jnp.float32),
            pltpu.VMEM((KV, R, 1), jnp.float32)]


def paged_attention_chunked_pallas(q, pool_k, pool_v, block_list, block_req,
                                   block_pos, kv_lens, token_req, token_pos,
                                   *, sm_scale=None, q_chunk: int = 16,
                                   prefetch_depth: int = 0,
                                   interpret: bool = True):
    """Chunked-prefill PagedAttention with a query-chunk grid dimension.

    Same contract as ``repro.core.attention_api.paged_attention_chunked``:
    q (T, H, hd) flat token lanes (decode tokens and prompt-chunk tokens
    mixed), flat BlockList arrays (Tb,), kv_lens (B,), token_req/token_pos
    (T,).  The decode kernel above is the one-lane-per-request special case;
    here the grid grows a leading query-chunk dimension and the scalar-
    prefetched BlockList still drives exact-tile DMA — zero-pad pool blocks
    never leave HBM.

    ``prefetch_depth`` selects the KV-page DMA strategy.  0 (and 1) keep the
    BlockSpec pipeline: Pallas fetches one page per grid step, overlapping at
    most one fetch with compute.  depth >= 2 switches to the manual
    multi-buffered ring in ``_chunked_kernel_prefetch``: the pools stay in
    HBM and up to ``depth - 1`` page DMAs run ahead of the flash loop, at the
    cost of ``2 * depth`` (bs, KV, hd) page slots of VMEM scratch.  Both
    strategies share the flash update, so results are identical.
    """
    T, H, hd = q.shape
    NB, BS, KV, _ = pool_k.shape
    B = kv_lens.shape[0]
    Tb = block_list.shape[0]
    scale = float(sm_scale if sm_scale is not None else hd ** -0.5)
    depth = int(prefetch_depth)
    if depth < 0:
        raise ValueError(f"prefetch_depth must be >= 0, got {depth}")

    tq = max(min(q_chunk, T), 1)
    q, token_req, token_pos = _pad_lanes(q, token_req, token_pos, tq, B)
    qg, treq, tpos = _group_lanes(q, token_req, token_pos, KV, tq)
    n, _, R, _ = qg.shape

    # index maps take (grid ids, *prefetched scalars)
    def q_map(i, t, bl, br, bp, kvl):
        return (i, 0, 0, 0)

    def kv_map(i, t, bl, br, bp, kvl):
        return (bl[t], 0, 0, 0)

    def lane_map(i, t, bl, br, bp, kvl):
        return (i, 0, 0)

    if depth >= 2:
        kernel = functools.partial(
            _chunked_kernel_prefetch, bs=BS, num_kv=KV, num_reqs=B,
            sm_scale=scale, depth=depth, num_blocks=NB)
        # Pools stay in HBM; the kernel rings its own page DMAs.
        kv_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = _lane_scratch(KV, R, hd) + [
            pltpu.VMEM((depth, BS, KV, hd), pool_k.dtype),
            pltpu.VMEM((depth, BS, KV, hd), pool_v.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ]
        # The DMA ring state spans grid steps of the q-chunk dim too (warm-up
        # reruns per chunk), so neither dimension may be parallelized.
        semantics = ("arbitrary", "arbitrary")
    else:
        kernel = functools.partial(_chunked_kernel, bs=BS, num_kv=KV,
                                   num_reqs=B, sm_scale=scale)
        kv_spec = pl.BlockSpec((1, BS, KV, hd), kv_map)
        scratch = _lane_scratch(KV, R, hd)
        semantics = ("parallel", "arbitrary")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n, Tb),
        in_specs=[
            pl.BlockSpec((1, KV, R, hd), q_map),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, R, 1), lane_map),
            pl.BlockSpec((1, R, 1), lane_map),
        ],
        out_specs=pl.BlockSpec((1, KV, R, hd), q_map),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=interpret,
    )(block_list, block_req, block_pos, kv_lens, qg, pool_k, pool_v,
      treq, tpos)
    return _ungroup_lanes(out, T, tq)


def ragged_tile_ranges(block_req, cu_q_lens, seq_slot, num_tiles: int,
                       tq: int):
    """``(lo, hi)``, each (num_tiles,): the BlockList range query tile ``i``
    walks.

    Tile ``i`` holds lanes ``[i*tq, (i+1)*tq)``; its sequences are those
    whose lanes ``[cu_q_lens[j], cu_q_lens[j+1])`` meet the tile, and its
    range runs from the first to one past the last entry any of them owns
    (``block_req`` == its slot).  Entries are laid out in lane order and
    each sequence's are contiguous, so the range holds nothing else; it
    covers every owned entry whatever the order, and anything caught inside
    it is masked as before.  A tile with no sequence (padding lanes) gets
    an empty range.
    """
    i32 = jnp.int32
    tb = block_req.shape[0]
    e = jnp.arange(tb, dtype=i32)[:, None]
    owns = block_req[:, None] == seq_slot[None, :]              # (tb, nseq)
    first = jnp.where(owns, e, tb).min(axis=0)                  # (nseq,)
    last = jnp.where(owns, e + 1, 0).max(axis=0)
    start = jnp.arange(num_tiles, dtype=i32)[:, None] * tq      # (n, 1)
    cu = cu_q_lens.astype(i32)
    meets = (cu[:-1] < start + tq) & (cu[1:] > start) & (cu[1:] > cu[:-1])
    lo = jnp.where(meets, first, tb).min(axis=1)
    hi = jnp.where(meets, last, 0).max(axis=1)
    return jnp.minimum(lo, hi), hi


def _ragged_kernel(
    # scalar-prefetched
    block_list, block_req, block_pos, kv_lens, tile_lo, tile_hi,
    # blocked inputs (the fused pool stays in HBM/ANY — DMA'd manually)
    q_ref, kv_hbm, treq_ref, tpos_ref,
    # output
    o_ref,
    # scratch
    acc_ref, m_ref, l_ref, kv_buf, kv_sem,
    *, bs: int, num_kv: int, head_dim: int, num_reqs: int, sm_scale: float,
    pages: int, num_blocks: int,
):
    """One query tile's walk of its own BlockList range, over the FUSED pool.

    The grid is the query tiles; tile ``i`` walks entries
    ``[tile_lo[i], tile_hi[i])`` (:func:`ragged_tile_ranges`) in a loop of
    page groups, ``pages`` entries each, and fetches no page outside that
    range.  The fused pool means ONE ``(KV, bs, 2*hd)`` page per DMA instead
    of a (k, v) pair; its minor axis holds K and V side by side, so a
    head_dim-64 page moves whole 128-lane rows with the page size on the
    sublanes — the tiling the DMA engine needs.
    The ring is double-buffered over page groups: group ``g+1`` starts
    before group ``g`` is waited, so a whole group's pages stream behind
    the flash inner loop.  Pad entries inside the range fetch a real page
    and skip only the compute, keeping every started copy waited exactly
    once.

    The per-page math is ``_flash_update`` + ``_chunked_valid_mask`` on the
    K and V lane halves of the fused page — the ragged and chunked paths
    cannot drift.
    """
    i = pl.program_id(0)
    lo, hi = tile_lo[i], tile_hi[i]
    num_groups = (hi - lo + pages - 1) // pages
    hd = head_dim

    def copy(e, slot, j):
        blk = jnp.minimum(block_list[e], num_blocks - 1)
        return pltpu.make_async_copy(kv_hbm.at[blk], kv_buf.at[slot, j],
                                     kv_sem.at[slot, j])

    def start_group(g):
        slot = jax.lax.rem(g, 2)
        for j in range(pages):                    # static small loop
            e = lo + g * pages + j

            @pl.when(e < hi)
            def _start(e=e, j=j):
                copy(e, slot, j).start()

    _init_tile(acc_ref, m_ref, l_ref, o_ref)

    @pl.when(num_groups > 0)                      # warm-up: fill slot 0
    def _first():
        start_group(jnp.int32(0))

    def group(g, carry):
        @pl.when(g + 1 < num_groups)              # run ahead
        def _ahead():
            start_group(g + 1)

        slot = jax.lax.rem(g, 2)
        for j in range(pages):                    # static small loop
            e = lo + g * pages + j

            @pl.when(e < hi)
            def _page(e=e, j=j):
                copy(e, slot, j).wait()

                @pl.when(block_req[e] < num_reqs)
                def _step():
                    valid = _chunked_valid_mask(
                        block_req, block_pos, kv_lens, treq_ref, tpos_ref, e,
                        bs=bs, num_reqs=num_reqs)
                    _flash_update(
                        q_ref, lambda kv: (kv_buf[slot, j, kv, :, :hd],
                                           kv_buf[slot, j, kv, :, hd:]),
                        o_ref, acc_ref, m_ref, l_ref, valid, num_kv=num_kv,
                        sm_scale=sm_scale)
        return carry

    jax.lax.fori_loop(0, num_groups, group, 0)


def paged_attention_ragged_pallas(q, kv_pool, block_list, block_req,
                                  block_pos, cu_q_lens, cu_kv_lens, seq_slot,
                                  *, sm_scale=None,
                                  num_queries_per_block: int = 16,
                                  num_kv_pages_per_block: int = 1,
                                  vmem_limit_bytes: int = 0,
                                  interpret: bool = True):
    """Ragged fused-pool PagedAttention: one launch for prefill + decode.

    Same contract as ``repro.core.attention_api.paged_attention_ragged``:
    q (T, H, hd) flat token lanes with sequences contiguous in lane order,
    kv_pool (NB, KV, BS, 2*hd) fused layer (K and V side by side on the
    minor axis), flat BlockList arrays (Tb,), and cu_q_lens/cu_kv_lens/
    seq_slot ragged metadata.  The lane arrays the grid masks against are
    DERIVED from the prefix sums at the XLA level (``ragged_lane_metadata``
    — the same integer math as the jnp ref), and so is each query tile's
    BlockList range (:func:`ragged_tile_ranges`); both are scalar-prefetched.
    The grid is the query tiles, and each walks only its own range.

    Tunables (registered on the ``paged_attention_ragged`` family, measured
    by the autotune sweep in ``benchmarks/paged_attention_bench.py``):

    * ``num_queries_per_block`` — query-tile rows per grid step (the ragged
      analogue of ``q_chunk``).
    * ``num_kv_pages_per_block`` — fused KV pages one step of a tile's walk
      consumes; the double-buffered DMA ring holds ``2 *`` this many pages
      in VMEM.
    * ``vmem_limit_bytes`` — cap on the ring's VMEM footprint: the page
      group is clamped so the ring fits, and the limit is passed to the
      Mosaic compiler.
    """
    from repro.core.attention_api import ragged_lane_metadata

    T, H, hd = q.shape
    NB, num_kv, BS, hd2 = kv_pool.shape
    assert hd2 == 2 * hd, (kv_pool.shape, q.shape)
    B = seq_slot.shape[0]
    scale = float(sm_scale if sm_scale is not None else hd ** -0.5)

    token_req, token_pos, kv_lens = ragged_lane_metadata(
        cu_q_lens, cu_kv_lens, seq_slot, T, B)

    pages = max(int(num_kv_pages_per_block), 1)
    if vmem_limit_bytes:
        page_bytes = BS * num_kv * hd2 * jnp.dtype(kv_pool.dtype).itemsize
        pages = max(min(pages, int(vmem_limit_bytes) // (2 * page_bytes)), 1)
    tq = max(min(int(num_queries_per_block), T), 1)
    q, token_req, token_pos = _pad_lanes(q, token_req, token_pos, tq, B)
    qg, treq, tpos = _group_lanes(q, token_req, token_pos, num_kv, tq)
    n, _, R, _ = qg.shape

    # Each tile's BlockList range, once per launch; scalar-prefetched.
    tile_lo, tile_hi = ragged_tile_ranges(block_req, cu_q_lens, seq_slot, n,
                                          tq)

    kernel = functools.partial(
        _ragged_kernel, bs=BS, num_kv=num_kv, head_dim=hd, num_reqs=B,
        sm_scale=scale, pages=pages, num_blocks=NB)

    # index maps take (grid ids, *prefetched scalars)
    def q_map(i, *_):
        return (i, 0, 0, 0)

    def lane_map(i, *_):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, num_kv, R, hd), q_map),
            # ONE buffer in HBM; the kernel rings its own fused-page DMAs.
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, R, 1), lane_map),
            pl.BlockSpec((1, R, 1), lane_map),
        ],
        out_specs=pl.BlockSpec((1, num_kv, R, hd), q_map),
        scratch_shapes=_lane_scratch(num_kv, R, hd) + [
            pltpu.VMEM((2, pages, num_kv, BS, hd2), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((2, pages)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem_limit_bytes) or None),
        interpret=interpret,
    )(block_list, block_req, block_pos, kv_lens, tile_lo, tile_hi, qg,
      kv_pool, treq, tpos)
    return _ungroup_lanes(out, T, tq)
