"""Ragged prefill+decode kernel over the fused KV pool (docs/ragged_kernel.md).

Four contracts:

* op-level: the ``paged_attention_ragged`` family is BIT-identical per
  backend to ``paged_attention_chunked`` on the registry examples (the
  ragged example re-expresses the chunked one as cu prefix sums over the
  fused pool), and ``ragged_lane_metadata`` reproduces the chunked lane
  arrays exactly — integer derivation, not approximation;
* pool-level: fuse/split-view round-trips are lossless and the allocator's
  whole-block copy primitive moves ONE fused buffer;
* engine-level: greedy streams are bit-identical between ``attn_impl``
  "ragged" and "chunked" across policy triples x spec x overlap (the
  2-device mesh sweep rides in tests/test_sharded_engine.py, which runs the
  default ragged path against the single-device engine);
* autotune: a committed tune table resolves the ragged tunables at engine
  construction (counted ``tuned_resolved``), any miss falls back to the
  registry defaults (counted ``tuned_fallback``).

Backend-enrollment parity for the new family is registry-driven —
tests/test_backend_parity.py enumerates ``dispatch.list_ops()``.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ServeConfig, get_config
from repro.core import dispatch
from repro.core.attention_api import ragged_lane_metadata
from repro.core.paged_kv import copy_pool_blocks, fuse_kv_heads, fused_kv_views
from repro.perf import autotune
from repro.serving.engine import Request, ServingEngine

KEY = jax.random.PRNGKey(0)


def _examples():
    dispatch._ensure_registered()
    ragged = dispatch.get_op("paged_attention_ragged").example()
    chunked = dispatch.get_op("paged_attention_chunked").example()
    return ragged, chunked


# ------------------------------------------------------------------ op level
@pytest.mark.parametrize("backend", ["ref", "xla", "pallas_interpret"])
def test_ragged_matches_chunked_bitwise_per_backend(backend):
    (r_args, r_kw), (c_args, c_kw) = _examples()
    fam_r = dispatch.get_op("paged_attention_ragged")
    fam_c = dispatch.get_op("paged_attention_chunked")
    out_r = fam_r(*r_args, backend=backend, **r_kw)
    out_c = fam_c(*c_args, backend=backend, **c_kw)
    assert np.array_equal(np.asarray(out_r), np.asarray(out_c)), backend


def test_ragged_lane_metadata_reproduces_chunked_lanes():
    (r_args, _), (c_args, _) = _examples()
    _, _, _, _, _, cu_q, cu_kv, seq_slot = r_args
    q, _, _, _, _, _, kv_lens, token_req, token_pos = c_args
    treq, tpos, kvl = ragged_lane_metadata(cu_q, cu_kv, seq_slot,
                                           q.shape[0], kv_lens.shape[0])
    assert np.array_equal(np.asarray(treq), np.asarray(token_req))
    assert np.array_equal(np.asarray(tpos), np.asarray(token_pos))
    assert np.array_equal(np.asarray(kvl), np.asarray(kv_lens))


def test_ragged_tunables_registered():
    fam = dispatch.get_op("paged_attention_ragged")
    assert set(fam.tunables) == set(autotune.TUNABLE_KEYS)
    # Tunable values never change the math, only the grid shape.
    (r_args, _), _ = _examples()
    base = fam(*r_args, backend="pallas_interpret",
               num_queries_per_block=16, num_kv_pages_per_block=1)
    for nq, nk, vmem in [(1, 1, 0), (3, 2, 0), (16, 4, 4096)]:
        out = fam(*r_args, backend="pallas_interpret",
                  num_queries_per_block=nq, num_kv_pages_per_block=nk,
                  vmem_limit_bytes=vmem)
        assert np.array_equal(np.asarray(out), np.asarray(base)), (nq, nk)


# ------------------------------------------------------- the kernel's walk
# Each query tile walks only the BlockList range of its own sequences
# (``ragged_tile_ranges``); these layouts put tiles, sequences and pads where
# a range walk could go wrong.  seqs: (q_len, kv_len) per sequence, in lane
# order; lanes: the step's (bucketed) lane count; tb: BlockList length.
_WALK_LAYOUTS = {
    # a 16-lane tile holding the end of one prompt chunk and the next's start
    "straddle": dict(seqs=[(1, 40), (10, 30), (12, 12), (1, 70)], lanes=32,
                     tb=64),
    # one 2,048-token prefill: 128 tiles, each walking the same 128 pages
    "long_prefill": dict(seqs=[(2048, 2048)], lanes=2048, tb=160),
    # 20 live lanes in a 64-lane bucket: tiles 2 and 3 are padding only
    "pad_tiles": dict(seqs=[(9, 9), (11, 50)], lanes=64, tb=32),
    # the engine's list: padded to the whole pool, nearly all pads
    "full_pool": dict(seqs=[(1, 33), (1, 17), (3, 48)], lanes=8, tb=3072),
    # one decode lane per sequence, 32 sequences: two tiles
    "decode": dict(seqs=[(1, 5 + 23 * j % 200) for j in range(32)],
                   lanes=32, tb=512),
    # decode lanes first, then a prefill chunk, as the engine renders them
    "mixed": dict(seqs=[(1, 100), (1, 7), (1, 64), (45, 61), (19, 19)],
                  lanes=128, tb=64),
}
_WIDTHS = {"hd64_g3": (15, 5, 64), "hd128_g6": (12, 2, 128)}


def _walk_inputs(seqs, lanes, tb, H, KV, hd, *, num_slots=32, bs=16,
                 shards=1, seed=0):
    """Query lanes, a fused pool and the ragged metadata for ``seqs`` on
    shuffled slots, with the BlockList rendered as the engine renders it
    (lane order, each sequence's entries contiguous, pads at the end) —
    or, with ``shards``, shard 0's LOCAL list and pool slice from
    ``BlockAllocator.build_sharded_block_lists``."""
    from repro.core.paged_kv import BlockAllocator
    rng = np.random.default_rng(seed)
    nb = tb * shards
    alloc = BlockAllocator(num_blocks=nb, block_size=bs, num_shards=shards)
    slots = rng.permutation(num_slots)[:len(seqs)]
    for j, (_, kvl) in enumerate(seqs):
        alloc.allocate(j, kvl)
    pairs = [(j, int(slots[j])) for j in range(len(seqs))]
    if shards > 1:
        bl, br, bp = (x[0] for x in alloc.build_sharded_block_lists(
            pairs, pad_req=num_slots, min_per_shard=tb))
        nb = tb
    else:
        bl, br, bp, _ = alloc.build_block_list([j for j, _ in pairs], tb)
        br = np.where(br < len(seqs), slots[np.minimum(br, len(seqs) - 1)],
                      num_slots)
    seq_slot = np.full((num_slots,), num_slots, np.int32)
    seq_slot[:len(seqs)] = slots
    cu_q = np.zeros((num_slots + 1,), np.int32)
    cu_kv = np.zeros((num_slots + 1,), np.int32)
    cu_q[1:len(seqs) + 1] = np.cumsum([q for q, _ in seqs])
    cu_kv[1:len(seqs) + 1] = np.cumsum([k for _, k in seqs])
    cu_q[len(seqs) + 1:] = cu_q[len(seqs)]
    cu_kv[len(seqs) + 1:] = cu_kv[len(seqs)]
    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (lanes, H, hd), jnp.float32)
    pool = jax.random.normal(kp, (nb, KV, bs, 2 * hd), jnp.float32)
    return [q, pool] + [jnp.asarray(np.asarray(x, np.int32)) for x in
                        (bl, br, bp, cu_q, cu_kv, seq_slot)]


@pytest.mark.parametrize("width", sorted(_WIDTHS))
@pytest.mark.parametrize("layout", sorted(_WALK_LAYOUTS) + ["sharded"])
def test_ragged_kernel_range_walk_matches_ref(layout, width):
    from repro.core.attention_api import paged_attention_ragged
    from repro.kernels.paged_attention.kernel import (
        paged_attention_ragged_pallas, ragged_tile_ranges)
    H, KV, hd = _WIDTHS[width]
    if layout == "sharded":
        # shard 0's local list: some sequences have no entry on it, and the
        # others' entries keep lane order, contiguous per sequence
        args = _walk_inputs([(1, 90), (7, 40), (1, 33), (20, 20)], 32, 32,
                            H, KV, hd, shards=2)
    else:
        args = _walk_inputs(**_WALK_LAYOUTS[layout], H=H, KV=KV, hd=hd)
    q, _, bl, br, bp, cu_q, _, seq_slot = args
    out = paged_attention_ragged_pallas(*args, num_queries_per_block=16,
                                        interpret=True)
    ref = paged_attention_ragged(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    # every tile's range holds all of its sequences' entries and no entry
    # of any other live sequence
    n = -(-q.shape[0] // 16)
    lo, hi = (np.asarray(x) for x in ragged_tile_ranges(br, cu_q, seq_slot,
                                                        n, 16))
    cu, slot_of, owner = np.asarray(cu_q), np.asarray(seq_slot), np.asarray(br)
    for i in range(n):
        mine = [int(slot_of[j]) for j in range(len(slot_of))
                if cu[j] < cu[j + 1] and cu[j] < 16 * (i + 1)
                and cu[j + 1] > 16 * i]
        walked = set(owner[lo[i]:hi[i]].tolist()) - {len(slot_of)}
        assert walked == set(mine) & set(owner.tolist()), (i, walked, mine)


# ---------------------------------------------------------------- pool level
def test_fused_pool_roundtrip_and_block_copy():
    NB, BS, KV, HD = 6, 4, 2, 8
    ks = jax.random.split(KEY, 2)
    k = jax.random.normal(ks[0], (3, NB, BS, KV, HD))
    v = jax.random.normal(ks[1], (3, NB, BS, KV, HD))
    fused = fuse_kv_heads(k, v)
    assert fused.shape == (3, NB, KV, BS, 2 * HD)
    k2, v2 = fused_kv_views(fused)
    assert np.array_equal(np.asarray(k2), np.asarray(k))
    assert np.array_equal(np.asarray(v2), np.asarray(v))
    # the allocator's CoW primitive moves ONE buffer; per-channel copies of
    # the split views land in the same places
    srcs, dsts = jnp.asarray([1, 2]), jnp.asarray([4, 5])
    fc = copy_pool_blocks(fused, srcs, dsts)
    kc = copy_pool_blocks(k, srcs, dsts)
    vc = copy_pool_blocks(v, srcs, dsts)
    assert np.array_equal(np.asarray(fc), np.asarray(fuse_kv_heads(kc, vc)))


# -------------------------------------------------------------- engine level
@pytest.fixture(scope="module")
def serving_ref():
    from repro.models.api import build_model
    cfg = get_config("smollm-360m").reduced(dtype="float32")
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _run_engine(cfg, model, params, *, num_blocks=24, n_req=4,
                admission=None, preemption=None, eviction=None, **kw):
    serve = ServeConfig(model=cfg.name, kv_block_size=4, max_batch=3, **kw)
    eng = ServingEngine(model, params, cfg, serve, num_blocks=num_blocks,
                        admission=admission, preemption=preemption,
                        eviction=eviction)
    rng = np.random.default_rng(0)
    for i in range(n_req):
        if i % 2:                       # looping motif: ngram drafts land
            prompt = np.tile(rng.integers(0, cfg.vocab_size, (3,),
                                          dtype=np.int32), 3)
        else:
            prompt = rng.integers(0, cfg.vocab_size,
                                  (int(rng.integers(8, 16)),), dtype=np.int32)
        eng.submit(Request(req_id=i, prompt=prompt, max_new_tokens=5,
                           priority=i % 2))
    eng.run_until_done()
    return {r.req_id: list(r.output) for r in eng.finished}, eng.metrics()


def test_engine_fused_pool_and_metrics(serving_ref):
    cfg, model, params = serving_ref
    outs, m = _run_engine(cfg, model, params)
    assert m["attn_impl"] == "ragged"
    for key in autotune.TUNABLE_KEYS:
        assert key in m, key
        assert m["policy_counters"]["tune.tuned_resolved"] + \
            m["policy_counters"]["tune.tuned_fallback"] == 1
    # ONE fused channel, head-major, K|V on the minor axis: (L, NB, KV, BS,
    # 2*HD)
    eng_serve = ServeConfig(model=cfg.name, kv_block_size=4, max_batch=2)
    eng = ServingEngine(model, params, cfg, eng_serve, num_blocks=8)
    assert set(eng.pools) == {"kv"}
    a = cfg.attention
    assert eng.pools["kv"].shape == (
        cfg.num_layers, 8, a.num_kv_heads, 4, 2 * a.head_dim)


def test_engine_ragged_vs_chunked_greedy_parity(serving_ref):
    cfg, model, params = serving_ref
    ref, m_ref = _run_engine(cfg, model, params, attn_impl="chunked")
    for kw in (dict(), dict(overlap=True), dict(spec="ngram", spec_k=3)):
        outs, m = _run_engine(cfg, model, params, attn_impl="ragged", **kw)
        assert outs == ref, (kw, outs, ref)
        assert m["attn_impl"] == "ragged"
    assert m_ref["attn_impl"] == "chunked"


def test_attn_pages_counts_the_walked_ranges(serving_ref):
    """The engine's ``attn_pages`` counter, computed on the host from the
    tables it renders, equals the entries the kernel's tiles walk: the
    summed ``hi - lo`` the step derives from the same BlockList."""
    from repro.kernels.paged_attention.kernel import ragged_tile_ranges
    cfg, model, params = serving_ref
    tq = 4                              # small tiles: chunks straddle them
    serve = ServeConfig(model=cfg.name, kv_block_size=4, max_batch=3,
                        prefill_chunk=12, backend="ref",
                        num_queries_per_block=tq)
    eng = ServingEngine(model, params, cfg, serve, num_blocks=40)
    rng = np.random.default_rng(3)
    for i, n in enumerate([5, 19, 9, 14]):
        eng.submit(Request(req_id=i, max_new_tokens=4, prompt=rng.integers(
            0, cfg.vocab_size, (n,), dtype=np.int32)))
    seen = []
    step_fn = eng._step_fn

    def spy(params, pools, lists, tokens, *rest):
        seen.append((lists, tokens.shape[0]))
        return step_fn(params, pools, lists, tokens, *rest)

    eng._step_fn = spy
    walked_steps = 0
    while eng.busy:
        before, n_seen = eng.metrics()["attn_pages"], len(seen)
        eng.step()
        if len(seen) == n_seen:
            continue
        lists, lanes = seen[-1]
        t = min(tq, lanes)
        lo, hi = ragged_tile_ranges(lists["block_req"], lists["cu_q_lens"],
                                    lists["seq_slot"], -(-lanes // t), t)
        walked = int(np.sum(np.asarray(hi) - np.asarray(lo)))
        assert eng.metrics()["attn_pages"] - before == walked > 0
        walked_steps += 1
    assert walked_steps == len(seen) > 4


@pytest.mark.slow
def test_engine_ragged_vs_chunked_policy_pressure_sweep(serving_ref):
    cfg, model, params = serving_ref
    triples = [("fcfs", "latest-arrival", "lru"),
               ("priority", "fewest-remaining-tokens", "hit-rate")]
    for adm, pre, evi in triples:
        for nblocks in (24, 10):        # roomy + preemption pressure
            kw = dict(admission=adm, preemption=pre, eviction=evi)
            ref, _ = _run_engine(cfg, model, params, num_blocks=nblocks,
                                 attn_impl="chunked", **kw)
            outs, _ = _run_engine(cfg, model, params, num_blocks=nblocks,
                                  attn_impl="ragged", **kw)
            assert outs == ref, (adm, nblocks)


# ------------------------------------------------------------------ autotune
def _tune_results(cfg_vals, page_size, head_dim, backend):
    derived = ("tune=1;" f"page_size={page_size};head_dim={head_dim};"
               f"backend={backend};"
               + ";".join(f"{k}={v}" for k, v in cfg_vals.items())
               + ";best=1")
    return [{"module": "paged_attention_bench", "schema_version": 1,
             "rows": [{"name": "ragged_tune_test", "us": 1.0,
                       "derived": derived}]}]


def test_autotune_table_resolve_and_fallback(tmp_path):
    cfg_vals = {"num_queries_per_block": 4, "num_kv_pages_per_block": 2,
                "vmem_limit_bytes": 1 << 20}
    path = tmp_path / "BENCH_010.json"
    path.write_text(json.dumps(_tune_results(cfg_vals, 8, 64, "ref")))
    assert autotune.resolve_tunables(8, 64, "ref", str(path)) == cfg_vals
    # misses: wrong cell, absent file — None, never an exception
    assert autotune.resolve_tunables(16, 64, "ref", str(path)) is None
    assert autotune.resolve_tunables(8, 64, "xla", str(path)) is None
    assert autotune.resolve_tunables(8, 64, "ref",
                                     str(tmp_path / "nope.json")) is None
    # best=0 rows never resolve; malformed rows are skipped whole
    res = _tune_results(cfg_vals, 8, 64, "ref")
    res[0]["rows"][0]["derived"] = res[0]["rows"][0]["derived"].replace(
        "best=1", "best=0")
    path.write_text(json.dumps(res))
    assert autotune.resolve_tunables(8, 64, "ref", str(path)) is None


def test_engine_consults_tune_table(serving_ref, tmp_path, monkeypatch):
    cfg, model, params = serving_ref
    a = cfg.attention
    cfg_vals = {"num_queries_per_block": 4, "num_kv_pages_per_block": 2,
                "vmem_limit_bytes": 0}
    path = tmp_path / "BENCH_010.json"
    path.write_text(json.dumps(_tune_results(cfg_vals, 4, a.head_dim, "ref")))
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(path))
    ref, _ = _run_engine(cfg, model, params, attn_impl="chunked",
                         backend="ref")
    outs, m = _run_engine(cfg, model, params, backend="ref")
    assert m["policy_counters"]["tune.tuned_resolved"] == 1
    assert m["policy_counters"]["tune.tuned_fallback"] == 0
    for k, v in cfg_vals.items():
        assert m[k] == v, (k, m[k])
    assert outs == ref             # tunables never change the stream
    # explicit config pins win over the table
    _, m2 = _run_engine(cfg, model, params, backend="ref",
                        num_queries_per_block=7)
    assert m2["num_queries_per_block"] == 7
    assert m2["num_kv_pages_per_block"] == 2       # unpinned: still tuned
    # fallback: no table for this cell -> registry defaults, counted
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(tmp_path / "missing.json"))
    defaults = dispatch.get_op("paged_attention_ragged").tunables
    _, m3 = _run_engine(cfg, model, params, backend="ref")
    assert m3["policy_counters"]["tune.tuned_fallback"] == 1
    for k, v in defaults.items():
        assert m3[k] == v, (k, m3[k])
