"""Pallas kernel sweeps vs the pure-jnp oracles (interpret=True on CPU).

Interpret-mode Pallas is orders of magnitude slower than compiled jnp, so
the whole module is marked ``slow`` — the fast CI tier (tools/ci_fast.sh)
skips it; the full tier still runs everything.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.paged_kv import BlockAllocator

pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,S,H,KV,hd,causal,dtype", [
    (2, 128, 4, 2, 64, True, jnp.float32),
    (1, 256, 6, 6, 64, False, jnp.float32),
    (2, 64, 8, 2, 128, True, jnp.float32),
    (1, 128, 4, 4, 64, True, jnp.bfloat16),
])
def test_flash_attention_sweep(B, S, H, KV, hd, causal, dtype):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import flash_attention_ref
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, bq=64, bk=64,
                                 interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("NB,BS,KV,hd,H,B,lens", [
    (24, 8, 2, 64, 8, 3, [13, 8, 21]),
    (40, 16, 4, 128, 8, 4, [40, 1, 64, 17]),
    (16, 8, 6, 64, 6, 2, [5, 9]),
    (16, 8, 1, 64, 4, 2, [8, 16]),
])
def test_paged_attention_kernel_sweep(NB, BS, KV, hd, H, B, lens):
    from repro.kernels.paged_attention.kernel import paged_attention_pallas
    from repro.kernels.paged_attention.ref import paged_attention_ref
    ks = jax.random.split(KEY, 3)
    pk = jax.random.normal(ks[0], (NB, BS, KV, hd), jnp.float32)
    pv = jax.random.normal(ks[1], (NB, BS, KV, hd), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, hd), jnp.float32)
    al = BlockAllocator(num_blocks=NB, block_size=BS)
    al._free = np.random.RandomState(1).permutation(NB).tolist()
    for r, L in enumerate(lens):
        al.allocate(r, L)
    tot = sum(-(-L // BS) for L in lens) + 3
    args = [jnp.asarray(x) for x in
            al.build_block_list(list(range(B)), max_total=tot)]
    out = paged_attention_pallas(q, pk, pv, *args, interpret=True)
    ref = paged_attention_ref(q, pk, pv, *args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("NB,BS,KV,hd,H,lens,chunks,q_chunk,depth", [
    (24, 8, 2, 32, 8, [13, 8, 21], [1, 4, 2], 4, 0),
    (40, 16, 4, 32, 8, [40, 1, 64, 17], [3, 1, 5, 2], 8, 0),
    (16, 8, 1, 16, 4, [8, 16], [2, 7], 3, 0),   # q_chunk not dividing T
    # multi-buffered KV-page DMA ring (prefetch_depth >= 2): same math,
    # manual async copies into a depth-slot VMEM ring instead of BlockSpec
    # pipelining — must stay BIT-identical to the depth<=1 path
    (24, 8, 2, 32, 8, [13, 8, 21], [1, 4, 2], 4, 2),
    (40, 16, 4, 32, 8, [40, 1, 64, 17], [3, 1, 5, 2], 8, 3),
    (16, 8, 1, 16, 4, [8, 16], [2, 7], 3, 16),  # depth > #kv blocks
])
def test_paged_attention_chunked_kernel_sweep(NB, BS, KV, hd, H, lens,
                                              chunks, q_chunk, depth):
    """Query-chunk grid kernel vs the jnp chunked-prefill oracle: mixed
    decode/prefill lanes, shuffled pool blocks, trailing padding lanes."""
    from repro.core.attention_api import paged_attention_chunked
    from repro.kernels.paged_attention.kernel import (
        paged_attention_chunked_pallas)
    B = len(lens)
    al = BlockAllocator(num_blocks=NB, block_size=BS)
    al._free = np.random.RandomState(1).permutation(NB).tolist()
    for r, L in enumerate(lens):
        al.allocate(r, L)
    tot = sum(-(-L // BS) for L in lens) + 3
    bl, br, bp, _ = [jnp.asarray(x) for x in
                     al.build_block_list(list(range(B)), max_total=tot)]
    kv_lens = jnp.asarray(lens, jnp.int32)
    treq, tpos = [], []
    for r, c in enumerate(chunks):                # last c positions of req r
        treq += [r] * c
        tpos += list(range(lens[r] - c, lens[r]))
    treq += [B, B]                                # two padding lanes
    tpos += [0, 0]
    T = len(treq)
    ks = jax.random.split(KEY, 3)
    pk = jax.random.normal(ks[0], (NB, BS, KV, hd), jnp.float32)
    pv = jax.random.normal(ks[1], (NB, BS, KV, hd), jnp.float32)
    q = jax.random.normal(ks[2], (T, H, hd), jnp.float32)
    treq = jnp.asarray(treq, jnp.int32)
    tpos = jnp.asarray(tpos, jnp.int32)
    out = paged_attention_chunked_pallas(q, pk, pv, bl, br, bp, kv_lens,
                                         treq, tpos, q_chunk=q_chunk,
                                         prefetch_depth=depth,
                                         interpret=True)
    ref = paged_attention_chunked(q, pk, pv, bl, br, bp, kv_lens, treq, tpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    assert np.all(np.isfinite(np.asarray(out)[-2:])), "pad lanes must be 0"
    np.testing.assert_allclose(np.asarray(out)[-2:], 0.0)
    if depth >= 2:      # the DMA ring cannot drift from the serial path
        serial = paged_attention_chunked_pallas(
            q, pk, pv, bl, br, bp, kv_lens, treq, tpos, q_chunk=q_chunk,
            prefetch_depth=0, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(serial))


def test_paged_attention_chunked_sharded_equals_chunked():
    """Sequence-sharded chunked combine vs the single-device chunked oracle:
    mixed decode/prefill/draft-style lanes over a pool sharded into 4
    contiguous slices, with per-shard LOCAL BlockLists rendered by
    ``build_sharded_block_lists`` — plus the registry's ``sharded`` backend
    (flat-list split, replicated pool) on the same inputs."""
    from conftest import run_multidevice
    snippet = """
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.core.attention_api import (
        paged_attention_chunked, paged_attention_chunked_sharded)
    from repro.core.dispatch import get_op
    from repro.core.paged_kv import BlockAllocator
    from repro.distributed.sharding import auto_mesh

    SHARDS, BS, KV, hd, H = 4, 8, 2, 32, 8
    NB = SHARDS * 6
    lens, chunks = [13, 8, 21], [1, 4, 2]      # decode + prefill-chunk lanes
    B = len(lens)
    al = BlockAllocator(num_blocks=NB, block_size=BS, num_shards=SHARDS)
    for r, L in enumerate(lens):
        al.allocate(r, L)
    kv_lens = jnp.asarray(lens, jnp.int32)
    treq, tpos = [], []
    for r, c in enumerate(chunks):             # last c positions of req r
        treq += [r] * c
        tpos += list(range(lens[r] - c, lens[r]))
    treq += [B, B]                             # padding lanes
    tpos += [0, 0]
    T = len(treq)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    pk = jax.random.normal(ks[0], (NB, BS, KV, hd), jnp.float32)
    pv = jax.random.normal(ks[1], (NB, BS, KV, hd), jnp.float32)
    q = jax.random.normal(ks[2], (T, H, hd), jnp.float32)
    treq = jnp.asarray(treq, jnp.int32)
    tpos = jnp.asarray(tpos, jnp.int32)

    bl, br, bp, _ = al.build_block_list(list(range(B)), max_total=NB)
    ref = paged_attention_chunked(q, pk, pv, jnp.asarray(bl),
                                  jnp.asarray(br), jnp.asarray(bp),
                                  kv_lens, treq, tpos)

    # engine form: sequence-sharded pool + per-shard LOCAL lists
    sbl, sbr, sbp = al.build_sharded_block_lists(
        [(r, r) for r in range(B)], pad_req=B)
    mesh = auto_mesh((SHARDS,), ("model",))
    fn = jax.shard_map(
        lambda q, pk, pv, bl, br, bp: paged_attention_chunked_sharded(
            q, pk, pv, bl[0], br[0], bp[0], kv_lens, treq, tpos,
            axis="model"),
        mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), P("model"),
                  P("model")),
        out_specs=P(), check_vma=False)
    out = jax.jit(fn)(q, pk, pv, jnp.asarray(sbl), jnp.asarray(sbr),
                      jnp.asarray(sbp))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(out)[-2:], 0.0)  # pad lanes

    # registry form: the auto-enrolled `sharded` backend on the flat list
    fam = get_op("paged_attention_chunked")
    out2 = fam(q, pk, pv, jnp.asarray(bl), jnp.asarray(br), jnp.asarray(bp),
               kv_lens, treq, tpos, backend="sharded")
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    print("OK")
    """
    r = run_multidevice(snippet, n_devices=4)
    assert "OK" in r.stdout, (r.stdout[-300:], r.stderr[-2500:])


@pytest.mark.parametrize("R,D,B,T,L,dtype", [
    (64, 128, 3, 4, 5, jnp.float32),
    (32, 256, 2, 10, 20, jnp.float32),
    (64, 128, 2, 4, 1, jnp.bfloat16),
])
def test_batched_embedding_sweep(R, D, B, T, L, dtype):
    from repro.kernels.batched_embedding.kernel import batched_embedding_pallas
    from repro.kernels.batched_embedding.ref import batched_embedding_ref
    tbl = jax.random.normal(KEY, (R * T, D), dtype)
    offs = jnp.arange(T, dtype=jnp.int32) * R
    idx = jax.random.randint(KEY, (B, T, L), 0, R)
    gid = (idx + offs[None, :, None]).reshape(-1)
    out = batched_embedding_pallas(tbl, gid, L, interpret=True)
    ref = batched_embedding_ref(tbl, offs, idx).reshape(B * T, D)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("rows,block_rows,dtype", [
    (512, 16, jnp.float32), (1024, 256, jnp.float32),
    (512, 8, jnp.bfloat16),
])
def test_stream_sweep(rows, block_rows, dtype):
    from repro.kernels.stream.ops import stream_add, stream_scale, stream_triad
    n = rows * 128
    a = jax.random.normal(KEY, (n,), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (n,), dtype)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-6, atol=1e-5)
    # explicit backend: the sweep must exercise the kernel, not auto's jnp
    np.testing.assert_allclose(
        np.asarray(stream_add(a, b, block_rows, backend="pallas_interpret"),
                   np.float32),
        np.asarray(a + b, np.float32), **tol)
    np.testing.assert_allclose(
        np.asarray(stream_scale(a, 3.0, block_rows,
                                backend="pallas_interpret"), np.float32),
        np.asarray(3.0 * a, np.float32), **tol)
    np.testing.assert_allclose(
        np.asarray(stream_triad(a, b, 3.0, block_rows,
                                backend="pallas_interpret"), np.float32),
        np.asarray(3.0 * a + b, np.float32), **tol)


@pytest.mark.parametrize("R,D,N", [(100, 128, 37), (64, 256, 64)])
def test_gather_scatter_sweep(R, D, N):
    from repro.kernels.gather_scatter.ops import vector_gather, vector_scatter
    tbl = jax.random.normal(KEY, (R, D), jnp.float32)
    ids = jax.random.randint(KEY, (N,), 0, R)
    np.testing.assert_allclose(
        np.asarray(vector_gather(tbl, ids, backend="pallas_interpret")),
        np.asarray(jnp.take(tbl, ids, 0)))
    ids_u = jnp.asarray(np.random.RandomState(0).permutation(R)[:N])
    src = jax.random.normal(jax.random.PRNGKey(2), (N, D), jnp.float32)
    out = vector_scatter(tbl, ids_u, src, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(tbl.at[ids_u].set(src)))
