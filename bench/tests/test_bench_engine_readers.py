"""The readers of the engine's own spans and counters, on hand-built runs,
on runs of a program that has neither (they read nothing), and in whole
traced runs of the tiny cells on the CPU."""
from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from bench.metrics import reader
from bench.session import Job, Record, Window
from bench.tests import tiny


def _req(arrival, admitted_at):
    return SimpleNamespace(arrival=arrival, admitted_at=admitted_at)


def _window(with_program_stamps=True):
    """Three requests on the program's clock (arrival 100, 101, 102 s),
    sent at 0, 1 and 2 s on the harness's; the drain closed at 12 s."""
    stamps = [(100.0, 100.5), (101.0, 101.2), (102.0, None)]
    recs = []
    for i, (arr, adm) in enumerate(stamps):
        req = (_req(arr, adm) if with_program_stamps
               else SimpleNamespace(arrival=arr))
        recs.append(Record(rid=i, job=Job(None, 4), due=float(i),
                           sent=float(i), req=req))
    m0 = {"steps": 10, "prefill_tokens": 100, "decode_tokens": 50,
          "spans": {"dispatch": {"s": 1.0, "n": 10, "max_s": 0.2}}}
    m1 = {"steps": 20, "prefill_tokens": 400, "decode_tokens": 90,
          "spans": {"dispatch": {"s": 1.5, "n": 20, "max_s": 0.2}}}
    if not with_program_stamps:
        for m in (m0, m1):
            for k in ("prefill_tokens", "decode_tokens", "spans"):
                del m[k]
    return Window(t0=0.0, t_end=10.0, t_last=9.0, records=recs, steps=[],
                  attempted=3, failed=0, m_start=m0, m_end=m1, closed=12.0)


class _Run:
    def __init__(self, window):
        self.window, self.setup_s, self.trace, self.peak = window, 1.0, None, None


@pytest.mark.parametrize("metric, want", [
    # (1.5 - 1.0) s / 10 steps
    ("dispatch_ms_per_step.decode", 50.0),
    # admitted - arrival: 0.5, 0.2 and (never) 12 + (102 - 2) - 102 = 10
    ("admit_wait_p95_ms.chat", 10000.0),
    # (400 - 100) / 10
    ("prefill_tokens_per_step.chat", 30.0),
])
def test_engine_readers(metric, want):
    assert reader(metric)(_Run(_window())) == pytest.approx(want)
    # a program without the span, the stamp or the counter: nothing to read
    assert reader(metric)(_Run(_window(False))) is None


def test_admit_wait_of_admitted_requests():
    w = _window()
    w.records = w.records[:2]
    assert reader("admit_wait_p95_ms.chat")(_Run(w)) == pytest.approx(500.0)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("engine_readers"))


@pytest.mark.parametrize("workload, metrics", [
    ("tiny.decode", ["dispatch_ms_per_step.decode", "host_ms_per_step.decode"]),
    ("tiny.chat", ["admit_wait_p95_ms.chat", "prefill_tokens_per_step.chat",
                   "queue_wait_p95_ms.chat"]),
])
def test_traced_run_reports_the_engine_metrics(tree, workload, metrics):
    rc, res, err = tiny.cpu_run(tree, workload, seed=4_000_000_007, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    for name in metrics:
        assert res["metrics"][name]["value"] > 0, name
    if workload == "tiny.chat":
        m = res["metrics"]
        # the program's own wait cannot exceed the harness's: a request is
        # constructed when it is sent, after it was due
        assert (m["admit_wait_p95_ms.chat"]["value"]
                <= m["queue_wait_p95_ms.chat"]["value"] + 1e-6)


def test_observe_reads_spans_that_add_up_to_the_phases(tree):
    body = """
from bench import observe
sys.exit(observe.main(["--workload", "tiny.decode", "--seed", "11",
                       "--seconds", "2"], require_tpu=False,
                      backend="pallas_interpret"))
"""
    p = tiny.run_snippet(tree, body)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["compiled_in_window"] == 0
    hp = out["host_phases"]
    assert hp["spans_s"] == pytest.approx(hp["phase_s"], rel=0.01)
    assert out["spans"]["dispatch"]["n"] > 0
    assert out["metrics"]["dispatch_ms_per_step.decode"] > 0
    assert out["span_cost_us"]["off"] > 0
    # no device plane on the CPU: no device breakdown
    assert "scopes" not in out


def test_observe_finds_the_scopes_in_the_compiled_programs(tree, tmp_path):
    # a compile cache of its own: one filled by a program without the scopes
    # would hand back executables without them (the key ignores op names)
    body = f"""
import os
os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
from bench import engine_trace, observe
su = run.prepare("tiny.decode", 13, 2.0, require_tpu=False,
                 backend="pallas_interpret")
texts = observe.program_texts(su)
scopes = {{engine_trace.scope_of(v) for t in texts
          for v in engine_trace.hlo_op_names(t).values()}}
print(len(texts), sorted(scopes))
"""
    p = tiny.run_snippet(tree, body)
    assert p.returncode == 0, p.stderr[-3000:]
    n, scopes = p.stdout.strip().splitlines()[-1].split(" ", 1)
    assert int(n) >= 1
    for s in ("embed", "qkv", "kv_append", "attention", "attn_out", "mlp",
              "unembed", "sample"):
        assert f"'{s}'" in scopes, scopes
