"""The reader of the engine's ``attn_pages`` counter (BlockList entries the
ragged kernel's query tiles walk in one layer), on hand-built runs, on a
run of a program without the counter (it reads nothing), and in whole
traced runs of the tiny cells on the CPU."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench.metrics import reader
from bench.session import Window
from bench.tests import tiny


def _window(with_counter=True):
    m0 = {"steps": 10, "attn_pages": 800}
    m1 = {"steps": 20, "attn_pages": 9120}
    if not with_counter:
        for m in (m0, m1):
            del m["attn_pages"]
    return Window(t0=0.0, t_end=10.0, t_last=9.0, records=[], steps=[],
                  attempted=0, failed=0, m_start=m0, m_end=m1, closed=10.0)


def _run(window):
    return SimpleNamespace(window=window, setup_s=1.0, trace=None, peak=None)


@pytest.mark.parametrize("metric", ["attn_pages_per_step.chat",
                                    "attn_pages_per_step.decode"])
def test_attn_pages_reader(metric):
    # (9120 - 800) / 10 steps
    assert reader(metric)(_run(_window())) == pytest.approx(832.0)
    # a program without the counter: nothing to read
    assert reader(metric)(_run(_window(False))) is None


def test_attn_pages_reader_without_steps():
    w = _window()
    w.m_end = dict(w.m_end, steps=w.m_start["steps"])
    assert reader("attn_pages_per_step.decode")(_run(w)) is None


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("attn_pages_reader"))


@pytest.mark.parametrize("workload", ["tiny.decode", "tiny.chat"])
def test_traced_run_reports_attn_pages(tree, workload):
    rc, res, err = tiny.cpu_run(tree, workload, seed=4_000_000_011, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    fam = workload.split(".")[-1]
    assert res["metrics"][f"attn_pages_per_step.{fam}"]["value"] > 0
