"""The reduction of a trace through the engine's spans and the step's named
scopes (``bench/engine_trace.py``): scope seconds of leaf operations, idle
gaps labelled by the innermost span of either prefix, the slowest step, and
every number of ``bench/tracing.py`` left as it was."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import engine_trace, tracing

DATA = Path(__file__).resolve().parent / "data"
LOOP = "jit(fused)/while/body/closed_call"

# Times in ns.  Window 0-1000.  Program 1 (100-300): a loop (100-250)
# holding an MLP fusion and the attention kernel, then the sampler's sort;
# program 2 (400-600): an unscoped copy and the kernel.  Idle 0-100 while
# the engine rendered, 300-400 while it committed, 600-1000 while the
# harness waited.
HAND = {
    "device_modules": [["jit_fused(1)", 100, 200], ["jit_fused(1)", 400, 200]],
    "device_ops": [
        ["%while.1", 100, 150, "jit(fused)/while"],
        ["%fusion.2", 100, 50, f"{LOOP}/mlp/dot_general"],
        ["%_ragged_pallas.3", 150, 100, f"{LOOP}/attention/ragged"],
        ["%sort.4", 250, 50, "jit(fused)/sample/vmap()/sort"],
        ["%copy.5", 400, 50, None],
        ["%_ragged_pallas.3", 450, 150, f"{LOOP}/attention/ragged"]],
    "host_spans": [
        ["bench.window", 0, 1000], ["bench.step", 10, 370],
        ["engine.step", 20, 350], ["engine.render", 30, 60],
        ["engine.dispatch", 90, 10], ["engine.wait", 100, 220],
        ["engine.commit", 320, 40], ["bench.observe", 380, 15],
        ["bench.step", 395, 305], ["engine.step", 396, 294],
        ["engine.schedule", 396, 2], ["engine.dispatch", 398, 2],
        ["engine.wait", 400, 250], ["engine.commit", 650, 40],
        ["bench.wait", 700, 300]],
}


def _bench_only(trace):
    return {"device_modules": trace["device_modules"],
            "device_ops": [o[:3] for o in trace["device_ops"]],
            "host_spans": [s for s in trace["host_spans"]
                           if s[0].startswith("bench.")]}


def test_scopes_labels_and_slowest_step():
    obs = engine_trace.reduce(HAND)
    assert obs.scope_seconds == pytest.approx(
        {"mlp": 50e-9, "attention": 250e-9, "sample": 50e-9,
         "unscoped": 50e-9})
    assert obs.scopes()[0] == ["attention", pytest.approx(250e-9)]
    assert [(n, round(g * 1e9), round(a * 1e9)) for n, g, a in obs.gaps] == [
        ("bench.wait", 400, 600), ("engine.render", 100, 0),
        ("engine.commit", 100, 300)]
    st = obs.slowest_step
    assert st["at_s"] == pytest.approx(20e-9)
    assert st["ms"] == pytest.approx(350e-6)
    assert [n for n, _ in st["spans_ms"]] == ["render", "dispatch", "wait",
                                              "commit"]
    wait, = st["waits"]
    assert wait["ms"] == pytest.approx(220e-6)
    assert wait["device_busy_ms"] == pytest.approx(200e-6)
    assert wait["device_idle_ms"] == pytest.approx(20e-6)
    assert wait["top_ops_ms"][0] == ["%_ragged_pallas.3",
                                     pytest.approx(100e-6)]
    assert "%while.1" not in dict(wait["top_ops_ms"])


def test_bench_numbers_are_unchanged_by_the_engine_spans():
    obs = engine_trace.reduce(HAND)
    want = tracing.reduce(_bench_only(HAND))
    assert obs.base == want
    assert [g for _, g, _ in obs.gaps] == [g for _, g, _ in want.gaps]
    assert want.op_seconds["%while.1"] == pytest.approx(150e-9)


def test_recorded_slice_is_unchanged():
    """On the slice recorded on the chip (no op names, no engine spans):
    busy time, time per operation and the gaps read as before."""
    trace = json.loads((DATA / "trace_slice.json").read_text())
    obs = engine_trace.reduce(trace)
    want = tracing.reduce(trace)
    assert obs.base.busy_s == want.busy_s
    assert obs.base.op_seconds == want.op_seconds
    assert obs.gaps == want.gaps
    assert set(obs.scope_seconds) == {engine_trace.UNSCOPED}
    # leaves only: less than all operations together, loops left out
    assert 0 < obs.scope_seconds["unscoped"] < sum(want.op_seconds.values())
    assert obs.scope_seconds["unscoped"] <= want.op_busy_s * (1 + 1e-9)


@pytest.mark.parametrize("op_name, scope", [
    (f"{LOOP}/mlp/dot_general", "mlp"),
    ("jit(fused)/embed/jit(_take)/gather", "embed"),
    ("jit(fused)/sample/jit(_threefry_split)/attention", "sample"),
    ("jit(fused)/broadcast_in_dim", "unscoped"),
    (None, "unscoped"),
])
def test_scope_is_the_first_scope_component(op_name, scope):
    assert engine_trace.scope_of(op_name) == scope


PROGRAM_A = """HloModule jit_fused
%body {
  %fusion.2 = bf16[8,64] fusion(%p), metadata={op_name="LOOP/mlp/dot"}
  ROOT %custom-call.3 = bf16[8,64] custom-call(%x), metadata={op_name="LOOP/attention/ragged"}
}
ENTRY %main {
  %while.1 = (s32[]) while(%t), body=%body
  ROOT %sort.4 = s32[8] sort(%l), metadata={op_name="jit(fused)/sample/sort"}
}""".replace("LOOP", LOOP)
# Another program of the same step: the same instruction names, other parts.
PROGRAM_B = """ENTRY %main {
  fusion.2 = bf16[8,64] fusion(p), metadata={op_name="jit(fused)/unembed/dot"}
  sort.9 = s32[8] sort(l), metadata={op_name="jit(fused)/sample/sort"}
}"""


def test_hlo_op_names_reads_instruction_metadata():
    names = engine_trace.hlo_op_names(PROGRAM_A)
    assert names == {"fusion.2": f"{LOOP}/mlp/dot",
                     "custom-call.3": f"{LOOP}/attention/ragged",
                     "sort.4": "jit(fused)/sample/sort"}
    assert engine_trace.hlo_op_names(PROGRAM_B)["fusion.2"].endswith(
        "unembed/dot")


def test_name_ops_takes_each_execution_from_its_program():
    progs = [engine_trace.hlo_op_names(t) for t in (PROGRAM_B, PROGRAM_A)]
    trace = {"device_modules": [["jit_fused(1)", 100, 200],
                                ["jit_fused(2)", 400, 100]],
             "device_ops": [["%while.1", 100, 150, None],
                            ["%fusion.2", 100, 50, None],
                            ["%custom-call.3", 150, 100, None],
                            ["%sort.4", 250, 50, None],
                            ["%fusion.2", 400, 50, None],
                            ["%sort.9", 450, 50],
                            ["%copy.7", 700, 10, None]]}
    assert engine_trace.name_ops(trace, progs) == 5
    got = [engine_trace.scope_of(o[3]) for o in trace["device_ops"]]
    assert got == ["unscoped", "mlp", "attention", "sample", "unembed",
                   "sample", "unscoped"]
    assert engine_trace.name_ops(trace, []) == 0
