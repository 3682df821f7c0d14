"""PagedAttention op families through the unified registry.

Single registration site for three op families:

* ``paged_attention`` — decode shape: one query token per request,
  q (B, H, hd) against the flat BlockList.
* ``paged_attention_chunked`` — the serving engine's fused chunked-prefill +
  decode shape: q (T, H, hd) flat token lanes, each attending causally to its
  request's pool blocks.
* ``paged_attention_ragged`` — the same mixed lanes described by
  cu_q_lens/cu_kv_lens prefix sums over a FUSED head-interleaved KV pool
  (``[K0, V0, K1, V1, ...]`` on the head axis — one buffer, one DMA ring),
  with ``num_queries_per_block`` / ``num_kv_pages_per_block`` /
  ``vmem_limit_bytes`` as measured tunables (see docs/ragged_kernel.md).

The jnp BlockList form (``repro.core.attention_api``) is registered as both
``ref`` (it is the oracle) and ``xla`` (it is also the tuned XLA production
path — segment-softmax, only effectual blocks gathered), so auto resolution
on CPU picks it while perf attribution still distinguishes the two roles.
The Pallas kernels register as ``pallas`` (TPU) and ``pallas_interpret``.
The chunked family additionally registers ``sharded``: the shard_map
log-sum-exp combine (``paged_attention_chunked_sharded``), capability-gated
on mesh presence (``dispatch.mesh_present``) — the standalone form splits
the flat BlockList across a 1-D mesh over every local device, which is both
the parity harness for the collective math and the single-resolver home of
the sharded serving engine's per-layer attention (the engine runs the same
kernel under its own mesh with a sequence-sharded pool; see
docs/sharded_serving.md).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch, paged_kv
from repro.core.attention_api import (
    paged_attention_chunked as _chunked_jnp,
    paged_attention_chunked_sharded, paged_attention_opt,
    paged_attention_ragged as _ragged_jnp, paged_attention_ragged_sharded)
from repro.distributed.sharding import auto_mesh
from repro.kernels.paged_attention.kernel import (
    paged_attention_chunked_pallas, paged_attention_pallas,
    paged_attention_ragged_pallas)


def _pools(key, NB=8, BS=4, KV=2, hd=16):
    ks = jax.random.split(key, 2)
    pk = jax.random.normal(ks[0], (NB, BS, KV, hd), jnp.float32)
    pv = jax.random.normal(ks[1], (NB, BS, KV, hd), jnp.float32)
    return pk, pv


def _example_decode():
    """2 requests (lens 6 and 3), blocks 0,1 / 2, one pad entry."""
    key = jax.random.PRNGKey(0)
    pk, pv = _pools(key)
    B, H, hd = 2, 4, 16
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, H, hd), jnp.float32)
    bl = jnp.asarray([0, 1, 2, 0], jnp.int32)
    br = jnp.asarray([0, 0, 1, B], jnp.int32)
    bp = jnp.asarray([0, 1, 0, 0], jnp.int32)
    lens = jnp.asarray([6, 3], jnp.int32)
    return (q, pk, pv, bl, br, bp, lens), {}


def _example_chunked():
    """Mixed lanes: req 0 decode token (pos 5) + req 1 prefill chunk (pos
    1..2) + one padding lane, over the same pool as the decode example."""
    key = jax.random.PRNGKey(0)
    pk, pv = _pools(key)
    B, H, hd = 2, 4, 16
    q = jax.random.normal(jax.random.fold_in(key, 2), (4, H, hd), jnp.float32)
    bl = jnp.asarray([0, 1, 2, 0], jnp.int32)
    br = jnp.asarray([0, 0, 1, B], jnp.int32)
    bp = jnp.asarray([0, 1, 0, 0], jnp.int32)
    kv_lens = jnp.asarray([6, 3], jnp.int32)
    token_req = jnp.asarray([0, 1, 1, B], jnp.int32)
    token_pos = jnp.asarray([5, 1, 2, 0], jnp.int32)
    return (q, pk, pv, bl, br, bp, kv_lens, token_req, token_pos), \
        {"q_chunk": 2, "prefetch_depth": 2}


def _example_ragged():
    """The chunked example re-expressed as ragged metadata over the FUSED
    pool: cu_q_lens/cu_kv_lens/seq_slot derive the exact token_req [0,1,1,B]
    / token_pos [5,1,2,0] / kv_lens [6,3] lanes of ``_example_chunked``, so
    cross-family parity asserts are bitwise, not approximate."""
    key = jax.random.PRNGKey(0)
    pk, pv = _pools(key)
    kv_pool = paged_kv.fuse_kv_heads(pk, pv)
    H, hd = 4, 16
    q = jax.random.normal(jax.random.fold_in(key, 2), (4, H, hd), jnp.float32)
    bl = jnp.asarray([0, 1, 2, 0], jnp.int32)
    br = jnp.asarray([0, 0, 1, 2], jnp.int32)
    bp = jnp.asarray([0, 1, 0, 0], jnp.int32)
    cu_q = jnp.asarray([0, 1, 3], jnp.int32)
    cu_kv = jnp.asarray([0, 6, 9], jnp.int32)
    seq_slot = jnp.asarray([0, 1], jnp.int32)
    return (q, kv_pool, bl, br, bp, cu_q, cu_kv, seq_slot), \
        {"num_queries_per_block": 2, "num_kv_pages_per_block": 2,
         "vmem_limit_bytes": 0}


_DECODE = dispatch.op(
    "paged_attention", example=_example_decode,
    doc="BlockList PagedAttention, decode shape: one query token per request")
_CHUNKED = dispatch.op(
    "paged_attention_chunked", example=_example_chunked,
    doc="Fused chunked-prefill + decode PagedAttention over flat token lanes",
    # Cross-backend knobs: query-chunk grid tile and the KV-page DMA ring
    # depth (0/1 = BlockSpec pipeline, >=2 = multi-buffered manual DMA in the
    # Pallas kernel; jnp backends ignore it). Swept by benchmarks/saturation.
    tunables={"q_chunk": 16, "prefetch_depth": 0})
_RAGGED = dispatch.op(
    "paged_attention_ragged", example=_example_ragged,
    doc="Ragged prefill+decode PagedAttention over the fused KV pool",
    # Measured by the autotune sweep in benchmarks/paged_attention_bench.py;
    # the committed best-per-(page_size, head_dim, backend) table
    # (BENCH_010.json via repro.perf.autotune) overrides these defaults at
    # engine resolve time.
    tunables={"num_queries_per_block": 16, "num_kv_pages_per_block": 1,
              "vmem_limit_bytes": 0})


@jax.jit
def _decode_jnp(q, pool_k, pool_v, block_list, block_req, block_pos,
                seq_lens):
    return paged_attention_opt(q, pool_k, pool_v, block_list, block_req,
                               block_pos, seq_lens)


# ONE jitted function under both names (shared compile cache); the ranks
# keep the oracle/production roles distinguishable in attribution.
_DECODE.register("ref")(_decode_jnp)
_DECODE.register("xla")(_decode_jnp)


@_DECODE.register("pallas")
@jax.jit
def _decode_pallas(q, pool_k, pool_v, block_list, block_req, block_pos,
                   seq_lens):
    return paged_attention_pallas(q, pool_k, pool_v, block_list, block_req,
                                  block_pos, seq_lens, interpret=False)


@_DECODE.register("pallas_interpret")
@jax.jit
def _decode_interpret(q, pool_k, pool_v, block_list, block_req, block_pos,
                      seq_lens):
    return paged_attention_pallas(q, pool_k, pool_v, block_list, block_req,
                                  block_pos, seq_lens, interpret=True)


@partial(jax.jit, static_argnames=("q_chunk", "prefetch_depth"))
def _chunked_ref(q, pool_k, pool_v, block_list, block_req, block_pos,
                 kv_lens, token_req, token_pos, *, q_chunk: int = 16,
                 prefetch_depth: int = 0):
    del q_chunk, prefetch_depth      # DMA strategy is a kernel-backend concern
    return _chunked_jnp(q, pool_k, pool_v, block_list, block_req, block_pos,
                        kv_lens, token_req, token_pos)


_CHUNKED.register("ref")(_chunked_ref)
_CHUNKED.register("xla")(_chunked_ref)


@_CHUNKED.register("pallas")
@partial(jax.jit, static_argnames=("q_chunk", "prefetch_depth"))
def _chunked_pallas(q, pool_k, pool_v, block_list, block_req, block_pos,
                    kv_lens, token_req, token_pos, *, q_chunk: int = 16,
                    prefetch_depth: int = 0):
    return paged_attention_chunked_pallas(
        q, pool_k, pool_v, block_list, block_req, block_pos, kv_lens,
        token_req, token_pos, q_chunk=q_chunk,
        prefetch_depth=prefetch_depth, interpret=False)


@_CHUNKED.register("pallas_interpret")
@partial(jax.jit, static_argnames=("q_chunk", "prefetch_depth"))
def _chunked_interpret(q, pool_k, pool_v, block_list, block_req, block_pos,
                       kv_lens, token_req, token_pos, *, q_chunk: int = 16,
                       prefetch_depth: int = 0):
    return paged_attention_chunked_pallas(
        q, pool_k, pool_v, block_list, block_req, block_pos, kv_lens,
        token_req, token_pos, q_chunk=q_chunk,
        prefetch_depth=prefetch_depth, interpret=True)


@lru_cache(maxsize=None)
def _sharded_chunked_fn(ndev: int):
    """Jitted shard_map combine over a 1-D mesh of ``ndev`` local devices.

    Cached per device count so repeated calls hit ONE jit cache entry (the
    registry rule: impls are registered pre-jitted; a fresh closure per
    call would retrace every time).
    """
    mesh = auto_mesh((ndev,), ("seq",))
    fn = jax.shard_map(
        partial(paged_attention_chunked_sharded, axis="seq"),
        mesh=mesh,
        in_specs=(P(), P(), P(), P("seq"), P("seq"), P("seq"), P(), P(),
                  P()),
        out_specs=P(), check_vma=False)
    return jax.jit(fn)


@_CHUNKED.register("sharded")
def _chunked_sharded(q, pool_k, pool_v, block_list, block_req, block_pos,
                     kv_lens, token_req, token_pos, *, q_chunk: int = 16,
                     prefetch_depth: int = 0):
    """Family-signature wrapper around the shard_map chunked combine.

    Splits the flat BlockList contiguously across a 1-D mesh over every
    local device (the pool stays replicated — a global BlockList has global
    pool indices) and runs ``paged_attention_chunked_sharded`` per rank.
    The serving engine goes further (sequence-sharded pool + local index
    translation) but reduces to the same per-rank kernel; this form is what
    the registry-enumerated parity suite and standalone callers exercise.
    """
    del q_chunk, prefetch_depth      # DMA strategy is a kernel-backend concern
    ndev = len(jax.devices())
    B = kv_lens.shape[0]
    Tb = block_list.shape[0]
    pad = -Tb % ndev
    if pad:
        block_list = jnp.pad(block_list, (0, pad))
        block_req = jnp.pad(block_req, (0, pad), constant_values=B)
        block_pos = jnp.pad(block_pos, (0, pad))
    return _sharded_chunked_fn(ndev)(q, pool_k, pool_v, block_list,
                                     block_req, block_pos, kv_lens,
                                     token_req, token_pos)


_RAGGED_TUNABLES = ("num_queries_per_block", "num_kv_pages_per_block",
                    "vmem_limit_bytes")


@partial(jax.jit, static_argnames=_RAGGED_TUNABLES)
def _ragged_ref(q, kv_pool, block_list, block_req, block_pos, cu_q_lens,
                cu_kv_lens, seq_slot, *, num_queries_per_block: int = 16,
                num_kv_pages_per_block: int = 1, vmem_limit_bytes: int = 0):
    del num_queries_per_block, num_kv_pages_per_block, vmem_limit_bytes
    return _ragged_jnp(q, kv_pool, block_list, block_req, block_pos,
                       cu_q_lens, cu_kv_lens, seq_slot)


_RAGGED.register("ref")(_ragged_ref)
_RAGGED.register("xla")(_ragged_ref)


@_RAGGED.register("pallas")
@partial(jax.jit, static_argnames=_RAGGED_TUNABLES)
def _ragged_pallas(q, kv_pool, block_list, block_req, block_pos, cu_q_lens,
                   cu_kv_lens, seq_slot, *, num_queries_per_block: int = 16,
                   num_kv_pages_per_block: int = 1, vmem_limit_bytes: int = 0):
    return paged_attention_ragged_pallas(
        q, kv_pool, block_list, block_req, block_pos, cu_q_lens, cu_kv_lens,
        seq_slot, num_queries_per_block=num_queries_per_block,
        num_kv_pages_per_block=num_kv_pages_per_block,
        vmem_limit_bytes=vmem_limit_bytes, interpret=False)


@_RAGGED.register("pallas_interpret")
@partial(jax.jit, static_argnames=_RAGGED_TUNABLES)
def _ragged_interpret(q, kv_pool, block_list, block_req, block_pos,
                      cu_q_lens, cu_kv_lens, seq_slot, *,
                      num_queries_per_block: int = 16,
                      num_kv_pages_per_block: int = 1,
                      vmem_limit_bytes: int = 0):
    return paged_attention_ragged_pallas(
        q, kv_pool, block_list, block_req, block_pos, cu_q_lens, cu_kv_lens,
        seq_slot, num_queries_per_block=num_queries_per_block,
        num_kv_pages_per_block=num_kv_pages_per_block,
        vmem_limit_bytes=vmem_limit_bytes, interpret=True)


@lru_cache(maxsize=None)
def _sharded_ragged_fn(ndev: int):
    """Jitted shard_map ragged combine — the chunked combine's twin over the
    fused pool, with the cu prefix sums replicated (every rank derives the
    same lane metadata; only the BlockList splits)."""
    mesh = auto_mesh((ndev,), ("seq",))
    fn = jax.shard_map(
        partial(paged_attention_ragged_sharded, axis="seq"),
        mesh=mesh,
        in_specs=(P(), P(), P("seq"), P("seq"), P("seq"), P(), P(), P()),
        out_specs=P(), check_vma=False)
    return jax.jit(fn)


@_RAGGED.register("sharded")
def _ragged_sharded(q, kv_pool, block_list, block_req, block_pos, cu_q_lens,
                    cu_kv_lens, seq_slot, *, num_queries_per_block: int = 16,
                    num_kv_pages_per_block: int = 1,
                    vmem_limit_bytes: int = 0):
    del num_queries_per_block, num_kv_pages_per_block, vmem_limit_bytes
    ndev = len(jax.devices())
    B = seq_slot.shape[0]
    Tb = block_list.shape[0]
    pad = -Tb % ndev
    if pad:
        block_list = jnp.pad(block_list, (0, pad))
        block_req = jnp.pad(block_req, (0, pad), constant_values=B)
        block_pos = jnp.pad(block_pos, (0, pad))
    return _sharded_ragged_fn(ndev)(q, kv_pool, block_list, block_req,
                                    block_pos, cu_q_lens, cu_kv_lens,
                                    seq_slot)
