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
