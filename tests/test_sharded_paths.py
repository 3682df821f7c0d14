"""shard_map paths: sharded BlockList paged attention (flash-decoding
combine) and row-sharded BatchedTable embedding — each must equal its
single-device oracle."""
import pytest

from conftest import run_multidevice

# multi-device subprocess sweeps: excluded from the fast tier
pytestmark = pytest.mark.slow


def test_paged_attention_sharded_equals_opt():
    snippet = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.attention_api import (
        paged_attention_opt, paged_attention_sharded)
    from repro.core.paged_kv import BlockAllocator
    from jax import shard_map

    SHARDS, BS, KV, HD, H, B = 4, 4, 2, 16, 4, 3
    NB_PER = 8
    NB = SHARDS * NB_PER
    lens = [14, 7, 22]
    al = BlockAllocator(num_blocks=NB, block_size=BS, num_shards=SHARDS)
    # interleave blocks so every shard owns every 4th block:
    # shard s owns blocks [s*NB_PER, (s+1)*NB_PER); allocate round-robin
    order = [s * NB_PER + i for i in range(NB_PER) for s in range(SHARDS)]
    al._free = list(reversed(order))
    for r, L in enumerate(lens):
        al.allocate(r, L)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    pool_k = jax.random.normal(ks[0], (NB, BS, KV, HD))
    pool_v = jax.random.normal(ks[1], (NB, BS, KV, HD))
    q = jax.random.normal(ks[2], (B, H, HD))

    # oracle: flat list, single device
    bl, br, bp, ll = al.build_block_list(list(range(B)), max_total=NB)
    ref = paged_attention_opt(q, pool_k, pool_v, jnp.asarray(bl),
                              jnp.asarray(br), jnp.asarray(bp),
                              jnp.asarray(ll))

    # sharded: per-shard lists with LOCAL pool indices
    mesh = jax.make_mesh((SHARDS,), ("model",))
    maxp = 8
    sbl = np.zeros((SHARDS, maxp), np.int32)
    sbr = np.full((SHARDS, maxp), B, np.int32)
    sbp = np.zeros((SHARDS, maxp), np.int32)
    fill = [0] * SHARDS
    for r in range(B):
        for k_i, blk in enumerate(al.table(r)):
            s = blk // NB_PER
            j = fill[s]; fill[s] += 1
            sbl[s, j] = blk % NB_PER          # local index within shard pool
            sbr[s, j] = r
            sbp[s, j] = k_i

    def f(q, pk, pv, bl, br, bp, sl):
        return paged_attention_sharded(q, pk[0], pv[0], bl[0], br[0], bp[0],
                                       sl, axis="model")

    out = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), P("model"),
                  P("model"), P()),
        out_specs=P()))(
        q, pool_k.reshape(SHARDS, NB_PER, BS, KV, HD),
        pool_v.reshape(SHARDS, NB_PER, BS, KV, HD),
        jnp.asarray(sbl), jnp.asarray(sbr), jnp.asarray(sbp),
        jnp.asarray(ll))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    print("OK")
    """
    r = run_multidevice(snippet, n_devices=4)
    assert "OK" in r.stdout, (r.stdout[-300:], r.stderr[-2500:])


def test_row_sharded_embedding_equals_dense():
    snippet = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.embedding_api import (
        batched_table_lookup, batched_table_lookup_sharded)
    from jax import shard_map
    SHARDS, T, R, D, B, L = 4, 3, 16, 8, 2, 5
    big = jax.random.normal(jax.random.PRNGKey(0), (T * R, D))
    offs = jnp.arange(T, dtype=jnp.int32) * R
    idx = jax.random.randint(jax.random.PRNGKey(1), (B, T, L), 0, R)
    ref = batched_table_lookup(big, offs, idx)
    mesh = jax.make_mesh((SHARDS,), ("model",))

    def f(tbl, offs, idx):
        return batched_table_lookup_sharded(tbl, offs, idx, axis="model")

    out = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P("model"), P(), P()), out_specs=P()))(
        big, offs, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    print("OK")
    """
    r = run_multidevice(snippet, n_devices=4)
    assert "OK" in r.stdout, (r.stdout[-300:], r.stderr[-2500:])
