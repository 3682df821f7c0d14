"""The engine's host spans, named scopes and scheduler counters: spans on
the profiler's clock that nest inside ``engine.step`` and roll up into
``phase_s``; the fused step's ``op_name`` scopes; the first-admission stamp
and the prefill/decode lane split."""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.config import ServeConfig, get_config
from repro.models.api import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.spans import Spans

HOST_SPANS = ("schedule", "render", "drain", "commit")


@pytest.fixture(scope="module")
def env():
    cfg = get_config("qwen2-1.5b").reduced(dtype="float32")
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (6 + i,), dtype=np.int32)
               for i in range(4)]

    def engine(*, overlap=False, spec="off", num_blocks=48, max_batch=4):
        serve = ServeConfig(model=cfg.name, kv_block_size=4,
                            max_batch=max_batch, overlap=overlap, spec=spec,
                            spec_k=3)
        eng = ServingEngine(model, params, cfg, serve, num_blocks=num_blocks)
        for i, p in enumerate(prompts):
            eng.submit(Request(req_id=i, prompt=p, max_new_tokens=10))
        return eng

    return {"cfg": cfg, "engine": engine}


def _host_phases(m):
    p = m["phase_s"]
    return sum(p.get(k, 0.0)
               for k in ("propose", "schedule_render", "commit", "idle"))


def _host_spans(m):
    return sum(m["spans"].get(k, {}).get("s", 0.0)
               for k in ("propose",) + HOST_SPANS)


@pytest.mark.parametrize("overlap, spec", [(False, "off"), (True, "off"),
                                           (True, "ngram")])
def test_phase_s_is_the_roll_up_of_the_spans(env, overlap, spec):
    eng = env["engine"](overlap=overlap, spec=spec, num_blocks=8,
                        max_batch=3)
    eng.run_until_done()
    eng.step()                                  # one idle iteration
    m = eng.metrics()
    spans = m["spans"]
    assert set(spans) >= {"step", "schedule", "render", "drain", "dispatch",
                          "wait", "commit"}
    assert ("propose" in spans) == (spec != "off")
    assert spans["step"]["n"] >= m["steps"] + m["num_idle_steps"]
    assert spans["dispatch"]["n"] == spans["wait"]["n"] == m["steps"]
    assert _host_spans(m) == pytest.approx(_host_phases(m), rel=0.01)
    for v in spans.values():
        assert 0 <= v["max_s"] <= v["s"]
    # the device phase runs from the dispatch to the tokens' return
    assert m["phase_s"]["device"] >= spans["wait"]["s"]


def test_spans_are_on_the_profiler_clock_inside_engine_step(env, tmp_path):
    eng = env["engine"](overlap=True)
    eng.step()                                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    eng.run_until_done()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("engine.")]
    names = {n for n, _, _ in events}
    assert names >= {"engine.step", "engine.schedule", "engine.render",
                     "engine.drain", "engine.dispatch", "engine.wait",
                     "engine.commit"}
    steps = [(a, b) for n, a, b in events if n == "engine.step"]
    for n, a, b in events:
        if n != "engine.step":
            assert any(s <= a and b <= e for s, e in steps), n


def test_span_totals():
    rec = Spans()
    for _ in range(3):
        with rec.span("x") as s:
            pass
        assert s.t1 >= s.t0 and s.s == s.t1 - s.t0
    got = rec.summary()
    assert set(got) == {"x"} and got["x"]["n"] == 3
    assert 0 <= got["x"]["max_s"] <= got["x"]["s"]
    got["x"]["n"] = 0                           # a copy
    assert rec.summary()["x"]["n"] == 3


def test_admitted_at_is_the_first_admission(env):
    eng = env["engine"](num_blocks=8, max_batch=3)
    reqs = list(eng.waiting)
    first = {}
    while eng.busy:
        eng.step()
        for r in reqs:
            if r.admitted_at is not None:
                first.setdefault(r.req_id, r.admitted_at)
                assert r.admitted_at == first[r.req_id]
    assert eng.metrics()["preemptions"] > 0
    assert any(r.num_preemptions > 0 for r in reqs)
    for r in reqs:
        assert r.admitted_at is not None and r.admitted_at >= r.arrival


@pytest.mark.parametrize("overlap", [False, True])
def test_prefill_and_decode_lanes_add_up_to_step_tokens(env, overlap):
    eng = env["engine"](overlap=overlap)
    eng.run_until_done()
    m = eng.metrics()
    step_tokens = m["lane_tokens_per_step"] * m["steps"]
    assert m["prefill_tokens"] == sum(6 + i for i in range(4))
    assert m["prefill_tokens"] + m["decode_tokens"] == pytest.approx(
        step_tokens)
    # every output token but the first of each request is a decode lane
    assert m["decode_tokens"] == 4 * (10 - 1)


def test_step_program_carries_the_scopes(env):
    eng = env["engine"]()
    captured = {}
    step_fn = eng._step_fn

    def capture(*args):
        captured["args"] = args
        return step_fn(*args)

    eng._step_fn = capture
    eng.step()
    text = step_fn.lower(*captured["args"]).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    scopes = {part for n in names for part in n.split("/")}
    assert scopes >= {"embed", "qkv", "kv_append", "attention", "attn_out",
                      "mlp", "unembed", "sample"}
