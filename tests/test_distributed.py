"""Distribution: sharding rules, compression, pipeline PP, elastic logic.
Multi-device paths run in subprocesses with forced host devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import run_multidevice
from repro.distributed.compression import dequantize_int8, quantize_int8
from repro.distributed.elastic import (
    HeartbeatMonitor, StragglerWatchdog, plan_remesh)

# multi-device subprocess paths: excluded from the fast tier
pytestmark = pytest.mark.slow


# ------------------------------------------------------------------ sharding
def test_sharding_rules_divisibility_fallback():
    from repro.distributed.sharding import ShardingRules
    snippet = """
    import jax, jax.numpy as jnp
    from repro.distributed.sharding import ShardingRules
    from repro.config import get_config
    from repro.models.api import build_model
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    rules = ShardingRules(mesh)
    # granite vocab 49155 %4 != 0 -> unsharded; d_model 1024 %2 == 0 -> fsdp
    spec = rules.param_spec(("embed", "table"), (49155, 1024))
    assert spec == jax.sharding.PartitionSpec(None, None), spec
    spec = rules.param_spec(("layers", "attn", "wq"), (24, 1024, 2048))
    assert spec[1] == "data" and spec[2] == "model", spec
    spec = rules.param_spec(("layers", "moe", "w_gate"), (24, 32, 1024, 512))
    assert spec[1] == "model" and spec[2] == "data", spec
    print("OK")
    """
    r = run_multidevice(snippet)
    assert "OK" in r.stdout, r.stderr[-2000:]


def test_train_step_numerics_match_sharded_vs_single():
    """1-device result == 8-device sharded result (same seed/batch).

    head_dim is passed to ShardingRules so attention projections only
    TP-shard on whole-head boundaries. Without it this config (1 kv head x
    head_dim 16) sharded wk's 16-wide output over the model axis, and jax
    0.4.37's GSPMD partitioner miscompiles that sub-head sharding inside
    the scan-over-layers body: the sharded forward silently diverged from
    the single-device result by ~0.6% (loss 5.9959 vs 6.0306). Bisected:
    the same block applied outside lax.scan, or the same scan with
    scan_layers=False (unrolled), or any whole-head sharding, is exact to
    float32 noise — so this was a partitioner artifact, not accumulation
    order, and the fix is the head-granularity constraint every TP system
    imposes anyway.
    """
    snippet = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.config import get_config
    from repro.models.api import build_model
    from repro.optim import adamw, cosine_warmup
    from repro.training.train_step import init_state, jit_train_step, make_train_step
    from repro.distributed.sharding import ShardingRules

    cfg = get_config("smollm-360m").reduced(dtype="float32", num_layers=2,
                                            d_model=64, vocab_size=256)
    model = build_model(cfg, remat=False)
    opt = adamw()
    lr = cosine_warmup(1e-3, 2, 10)
    state = init_state(model, jax.random.PRNGKey(0), opt)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)
    batch = {"tokens": toks}
    # single-device reference
    _, m_ref = jax.jit(make_train_step(model, opt, lr))(state, batch)
    # sharded (head-granular TP: see the test docstring)
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    rules = ShardingRules(mesh, head_dim=cfg.attention.head_dim)
    step = jit_train_step(model, opt, lr, mesh, rules,
                          jax.eval_shape(lambda: state), batch, donate=False)
    with mesh:
        _, m_sh = step(state, batch)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_sh["loss"]),
                               rtol=1e-4)
    np.testing.assert_allclose(float(m_ref["grad_norm"]),
                               float(m_sh["grad_norm"]), rtol=1e-3)
    print("OK")
    """
    r = run_multidevice(snippet)
    assert "OK" in r.stdout, r.stderr[-2000:]


# --------------------------------------------------------------- compression
def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 3
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_compressed_psum_with_error_feedback():
    snippet = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import compressed_psum
    from jax import shard_map
    from repro.distributed.sharding import auto_mesh
    mesh = auto_mesh((8,), ("x",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

    def f(g, r):
        return compressed_psum(g, r, "x")

    out, res = jax.jit(shard_map(f, mesh=mesh,
        in_specs=(P("x"), P("x")), out_specs=(P("x"), P("x"))))(
        g, jnp.zeros_like(g))
    ref = jnp.mean(g, axis=0)
    # every shard holds the same reduced mean, within int8 quantization err
    for i in range(8):
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                   atol=0.1)
    # error feedback: residual equals what quantization dropped
    assert float(jnp.abs(res).max()) < 0.2
    # accumulated over steps, mean residual-corrected error shrinks
    print("OK")
    """
    r = run_multidevice(snippet)
    assert "OK" in r.stdout, r.stderr[-2000:]


# ------------------------------------------------------------------ pipeline
def test_pipeline_matches_reference():
    snippet = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.pipeline import bubble_fraction, pipeline_forward
    from jax import shard_map
    S, M, mb, D = 4, 6, 2, 8
    mesh = jax.make_mesh((S,), ("pp",))
    ws = jax.random.normal(jax.random.PRNGKey(0), (S, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))

    def layer_fn(w, h):
        return jnp.tanh(h @ w)

    def run(ws_stage, x_all):
        return pipeline_forward(layer_fn, ws_stage[0], x_all,
                                axis="pp", num_stages=S)

    out = jax.jit(shard_map(run, mesh=mesh,
        in_specs=(P("pp"), P()), out_specs=P()))(ws, x)
    # reference: apply all stages sequentially
    ref = x
    for s in range(S):
        ref = layer_fn(ws[s], ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    assert abs(bubble_fraction(S, M) - 3/9) < 1e-9
    print("OK")
    """
    r = run_multidevice(snippet, n_devices=4)
    assert "OK" in r.stdout, r.stderr[-2000:]


# ------------------------------------------------------------------- elastic
def test_heartbeat_monitor():
    hb = HeartbeatMonitor([0, 1, 2], timeout_s=10.0)
    hb.beat(0, now=100.0)
    hb.beat(1, now=95.0)
    hb.beat(2, now=50.0)
    assert hb.dead(now=104.0) == [2]
    assert hb.alive(now=104.0) == [0, 1]


def test_plan_remesh():
    # full 2 pods healthy
    assert plan_remesh(512, 256, model_parallel=16) == (2, 16, 16)
    # one pod lost
    assert plan_remesh(256, 256, model_parallel=16) == (1, 16, 16)
    # partial pod: shrink data by powers of two
    assert plan_remesh(200, 256, model_parallel=16) == (1, 8, 16)
    # not enough for even one model replica
    assert plan_remesh(8, 256, model_parallel=16) is None


def test_straggler_watchdog():
    wd = StragglerWatchdog(threshold=2.0)
    for i in range(10):
        assert not wd.record(i, 1.0)
    assert wd.record(10, 5.0)                  # straggler flagged
    assert wd.slow_steps == [10]
    assert abs(wd.baseline - 1.0) < 1e-6       # baseline unpoisoned
