#!/usr/bin/env python3
"""One traced window of a benchmark cell, read through the engine's own
spans and the step's named scopes.

    python bench/observe.py --workload <cell> --seed <n> --seconds <s>

Set-up is ``bench/run.py``'s.  The window runs under the profiler; the
trace is reduced by :mod:`bench.engine_trace`.  The last line of standard
output is one JSON object: every metric of ``BENCHMARK.json`` that applies
to the cell (end-to-end ones too, read under the profiler), the device
seconds per named scope (``scopes``, with ``sample_ms_per_step``), the idle
gaps labelled by the innermost ``bench.*`` or ``engine.*`` span, the
engine's span totals over the window, the slowest step (its spans, and the
device's busy and idle time inside its wait for tokens), and the cost of
one span with the profiler off and on.  No reference comparison is made:
``bench/run.py`` decides ``correct``.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402  (reads the start time on import)

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import time  # noqa: E402
from types import SimpleNamespace  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace" / "observe"
COST_DIR = ROOT / ".bench_trace" / "span_cost"


def span_cost_us(n: int = 20000) -> dict:
    """Microseconds per ``engine.*`` span (enter and exit of an empty
    body), with the profiler off and then on."""
    from repro.serving.spans import Spans

    from bench import tracing

    def per_span() -> float:
        rec = Spans()
        t = time.perf_counter()
        for _ in range(n):
            with rec.span("cost"):
                pass
        return (time.perf_counter() - t) / n * 1e6

    off = per_span()
    tracing.start(str(COST_DIR))
    on = per_span()
    tracing.stop()
    shutil.rmtree(COST_DIR, ignore_errors=True)
    return {"off": off, "on": on}


def program_texts(su) -> list:
    """The compiled HLO text of each step program the warm-up compiled
    (loaded again from the compile cache), through the adapter's own
    warm-up with the compile call captured."""
    engine = su.ctx.session.engine
    serve = su.cfg_file["bench"]["serve"]
    batch = int(serve["max_batch"])
    lo, hi = su.kind.running_range(su.traffic, batch)
    shapes = su.adapter.buckets(lo, hi, batch, int(serve["prefill_chunk"]))
    step_fn, texts = engine._step_fn, []

    def lower(*args):
        return SimpleNamespace(compile=lambda: texts.append(
            step_fn.lower(*args).compile().as_text()))

    engine._step_fn = SimpleNamespace(lower=lower)
    try:
        su.adapter.warm(engine, shapes)
    finally:
        engine._step_fn = step_fn
    return texts


def _span_delta(m0: dict, m1: dict) -> dict:
    s0, s1 = m0.get("spans", {}), m1.get("spans", {})
    return {k: {"s": v["s"] - s0.get(k, {}).get("s", 0.0),
                "n": v["n"] - s0.get(k, {}).get("n", 0)}
            for k, v in s1.items()}


def _host_phases(m0: dict, m1: dict, spans: dict) -> dict:
    """``phase_s``'s host phases over the window beside the sum of the
    spans they are rolled up from."""
    p0, p1 = m0["phase_s"], m1["phase_s"]
    phase = sum(p1.get(k, 0.0) - p0.get(k, 0.0)
                for k in ("propose", "schedule_render", "commit", "idle"))
    span = sum(spans.get(k, {}).get("s", 0.0)
               for k in ("propose", "schedule", "render", "drain", "commit"))
    return {"phase_s": phase, "spans_s": span}


def main(argv=None, *, require_tpu: bool = True,
         backend: str = "pallas") -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    su = run.prepare(args.workload, args.seed, args.seconds,
                     require_tpu=require_tpu, backend=backend)
    if isinstance(su, int):
        return su
    from bench import engine_trace, tracing
    from bench.metrics import reader

    cost = span_cost_us()
    run.log(f"span cost: {cost['off']:.3f} us off, {cost['on']:.3f} us on")
    setup_s = time.perf_counter() - run.T_START
    c0 = dict(su.counter.counts)
    tracing.start(str(TRACE_DIR))
    window = su.kind.window(su.ctx)
    tracing.stop()
    raw = engine_trace.extract(str(TRACE_DIR))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if raw["device_ops"]:
        t = time.perf_counter()
        progs = [engine_trace.hlo_op_names(x) for x in program_texts(su)]
        named = engine_trace.name_ops(raw, progs)
        scopes = sorted({engine_trace.scope_of(v) for p in progs
                         for v in p.values()})
        run.log(f"op names: {named} of {len(raw['device_ops'])} ops named "
                f"from {len(progs)} compiled programs in "
                f"{time.perf_counter() - t:.1f} s; scopes there: {scopes}")
        if scopes == [engine_trace.UNSCOPED]:
            run.log("op names: the programs carry no named scope; the compile "
                    "cache keys a program without its op names, so one filled "
                    "by a program without the scopes hands those back: run "
                    "with a JAX_COMPILATION_CACHE_DIR of its own")
    obs = engine_trace.reduce(raw) if raw["device_modules"] else None
    out = {"workload": args.workload, "seed": args.seed,
           "compiled_in_window": su.counter.compiles - c0["compiled"],
           "span_cost_us": cost,
           "spans": _span_delta(window.m_start, window.m_end)}
    out["host_phases"] = _host_phases(window.m_start, window.m_end,
                                      out["spans"])
    r = run.Run(window, setup_s, su.shape, su.peak,
                obs.base if obs else None)
    metrics = {}
    for m in su.spec["end_to_end"] + su.spec["per_layer"]:
        if run._applies(m, su.cell["name"]):
            v = reader(m["name"])(r)
            if v is not None:
                metrics[m["name"]] = v
    out["metrics"] = metrics
    if obs is not None:
        steps = len(window.steps)
        out["sample_ms_per_step"] = (
            obs.scope_seconds.get("sample", 0.0) * 1e3 / steps
            if steps else None)
        out["busy_s"], out["window_s"] = obs.base.busy_s, obs.base.window_s
        out["scopes"] = obs.scopes()
        out["device_ops"] = obs.base.top_ops(12)
        out["idle_gaps"] = [[n, g, a] for n, g, a in obs.gaps[:12]]
        by_label: dict = {}
        for n, g, _ in obs.gaps:
            by_label[n] = by_label.get(n, 0.0) + g
        out["idle_by_label"] = sorted(([k, v] for k, v in by_label.items()),
                                      key=lambda kv: -kv[1])
        out["slowest_step"] = obs.slowest_step
        run.log(f"slowest step: {json.dumps(obs.slowest_step)}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
