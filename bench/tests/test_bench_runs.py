"""Whole runs on the CPU: a configuration, traffic mixes and a metric
added as new files and entries run without edits to the files that are
there; a host without a TPU gets no result; the comparison that decides
``correct`` fails a run whose served tokens are wrong, and the fp8
control reads far above the program."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests import tiny

REPO = tiny.REPO


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The benchmark with the tiny cells, plus one per-layer metric added
    as a reader file and an entry."""
    t = tiny.make_tree(tmp_path_factory.mktemp("bench_tree"))
    (t / "bench/metrics/steps_in_window.py").write_text(
        "def read(run):\n    return float(len(run.window.steps))\n")
    spec = json.loads((t / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "steps_in_window.decode", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "engine host half",
        "moves": "output_tok_s", "workloads": ["tiny.decode"]})
    (t / "BENCHMARK.json").write_text(json.dumps(spec))
    return t


def _no_compiles_in_window(err):
    line, = [x for x in err.splitlines()
             if "programs inside the window and drain" in x]
    return "compiled 0, cache_hits 0" in line


def test_added_files_run_open_loop(tree):
    rc, res, err = tiny.cpu_run(tree, "tiny.chat", seed=4_000_000_001)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert res["attempted"] == int(6.0 * 2.0) and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p50_ms", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert _no_compiles_in_window(err)
    last = err.strip().splitlines()[-2:]
    assert last[0].startswith("check served_logit_gap")
    assert last[1] == "check compiled_in_window: 0.0 (limit 0)"
    assert res["checks"]["compiled_in_window"] == {"value": 0.0, "limit": 0}


def test_added_files_run_closed_loop_traced(tree):
    rc, res, err = tiny.cpu_run(tree, "tiny.decode", seed=12, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    m = res["metrics"]
    # the added reader is found by its name; the trace-fed readers find no
    # device plane on the CPU and are left out
    assert m["steps_in_window.decode"]["value"] > 0
    assert m["decode_lanes_per_step.decode"]["value"] > 0
    assert "device_idle_share.decode" not in m
    assert res["attempted"] >= 4
    assert _no_compiles_in_window(err)
    assert not (tree / ".bench_trace").exists()


def test_wrong_token_is_not_correct(tree):
    """A token altered where it is produced: the sampler returns the
    runner-up for every greedy row."""
    before = """
import jax.numpy as jnp
from repro.serving import sampling
def runner_up(key, logits, *a, **k):
    top2 = jnp.argsort(logits, axis=-1)[..., -2]
    return top2.astype(jnp.int32)
sampling.sample_batched = runner_up
"""
    rc, res, err = tiny.cpu_run(tree, "tiny.decode", seed=3, before=before)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    c = res["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]


def test_compile_inside_the_window_is_not_correct(tree):
    """A step shape the warm-up misses compiles inside the window: the run
    reads not correct, whatever the reference says of its tokens."""
    before = """
from bench import byname
_load = byname.load
def load(path, prefix):
    mod = _load(path, prefix)
    if prefix == "bench_adapter":
        mod.warm = lambda engine, shapes: None
    return mod
byname.load = load
"""
    rc, res, err = tiny.cpu_run(tree, "tiny.chat", seed=4_000_000_003,
                                before=before)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    c = res["checks"]["compiled_in_window"]
    assert c["value"] > c["limit"] == 0
    s = res["checks"]["served_logit_gap"]
    assert s["value"] <= s["limit"]


def _json_lines(out):
    return [json.loads(x) for x in out.splitlines() if x.startswith("{")]


def test_control_reads_far_above_the_program(tree):
    """The fp8 reference in the program's place (``bench/control.py``): its
    widest gap is many times the program's on the same served tokens, and
    the tiny configuration's limit lies between them."""
    body = """
from bench import control
sys.exit(control.main(["--workload", "tiny.decode", "--seconds", "3",
                       "--seeds", "1,2,3"], require_tpu=False,
                      backend="pallas_interpret"))
"""
    p = tiny.run_snippet(tree, body)
    assert p.returncode == 0, p.stderr[-3000:]
    *rows, summary = _json_lines(p.stdout)
    assert len(rows) == 3 and summary["seeds"] == 3
    limit = tiny.TINY_CONFIG["bench"]["limits"]["served_logit_gap"]
    for r in rows:
        assert r["compared_tokens"] > 0
        assert r["served_logit_gap"] < limit < r["control_logit_gap"], rows
        assert r["program_correct"] is True
        assert r["control_correct"] is False
    assert summary["control_min"] >= 3 * summary["program_max"], rows
    assert summary["program_correct"] is True
    assert summary["control_correct"] is False


def test_sweep_finds_a_knee(tree):
    body = """
from bench import sweep
sys.exit(sweep.main(["--workload", "tiny.chat", "--rates", "1,3",
                     "--seconds", "2"], require_tpu=False,
                    backend="pallas_interpret"))
"""
    p = tiny.run_snippet(tree, body)
    assert p.returncode == 0, p.stderr[-3000:]
    *rows, last = _json_lines(p.stdout)
    assert [r["rate_per_s"] for r in rows] == [1.0, 3.0]
    assert all(r["ok"] and r["failed"] == 0 for r in rows)
    assert last == {"knee_per_s": 3.0, "cell_rate_per_s": 2.4}


def _plain_run(cwd, workload="smollm-360m.chat"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_host_gets_no_result():
    p = _plain_run(REPO)
    assert p.returncode != 0
    assert '"correct": true' not in p.stdout
    assert "not a TPU" in p.stderr


def test_benchmark_files_alone_get_no_result(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _plain_run(tmp_path)
    assert p.returncode != 0
    assert '"correct": true' not in p.stdout


def test_unknown_workload_is_refused():
    p = _plain_run(REPO, workload="no-such.cell")
    assert p.returncode != 0 and p.stdout.strip() == ""
