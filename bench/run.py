#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration (``bench/configs/<config>.json``, whose ``bench.adapter`` and
``bench.reference`` name the modules under ``bench/adapters/`` and
``bench/references/``), its traffic mix (``bench/traffic/<traffic>.json``,
whose ``kind`` names ``bench/traffic/kinds/<kind>.py``) and each metric's
reader (``bench/metrics/<name>.py``).

Set-up (``setup_s``, from process start): compile cache, device check,
weights made on the device from the seed, the engine, every step shape the
traffic can reach compiled (or loaded from the cache), and the traffic's
own set-up.  Then the window of ``--seconds``; with ``--trace 1`` under the
profiler, reporting the per-layer metrics instead of the end-to-end ones.
Then, with the engine freed, the served tokens are compared with the plain
reference (``bench/check.py``).

The last line of standard output is the result, one JSON object; the last
lines of standard error are the numbers compared, each beside its limit.
A host without the chips the cell asks for exits non-zero with no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Run:
    """What the metric readers read of one run."""

    def __init__(self, window, setup_s, shape, peak, trace):
        self.window, self.setup_s, self.shape = window, setup_s, shape
        self.peak, self.trace = peak, trace
        self.notes = []

    @property
    def peak_flops(self) -> float:
        from bench import peaks

        return peaks.flops_per_s(self.peak, self.shape.dtype)

    def note(self, line: str) -> None:
        self.notes.append(line)


class Context:
    """What a traffic kind's ``setup`` and ``window`` work with."""

    def __init__(self, session, traffic, seed, seconds, vocab):
        self.session, self.traffic = session, traffic
        self.seed, self.seconds, self.vocab = seed, seconds, vocab


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class CompileCounter:
    """Compilations in this process, from ``jax.monitoring``: programs
    traced, programs compiled by the backend, and programs found in the
    persistent cache instead."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        import jax

        self.counts = {"traced": 0, "compiled": 0, "cache_hits": 0}

        def on_duration(event, duration, **_):
            if event in self.EVENTS:
                self.counts[self.EVENTS[event]] += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.counts["cache_hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def compiles(self) -> int:
        return self.counts["compiled"]

    def since(self, before: dict) -> str:
        return ", ".join(f"{k} {v - before[k]}"
                         for k, v in self.counts.items())


@functools.cache
def compile_counter() -> CompileCounter:
    """The process's one counter (its listener stays registered)."""
    return CompileCounter()


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


class Setup:
    """A cell made ready for its window: the engine built, every step
    shape compiled, the traffic's set-up done."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def prepare(workload: str, seed: int, seconds: float, *,
            require_tpu: bool = True, backend: str = "pallas"):
    """Everything ``setup_s`` counts, for ``workload`` from ``seed``.

    Returns a :class:`Setup`, or an exit code when the host cannot run the
    cell."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        log(f"run: no workload {workload!r} in BENCHMARK.json")
        return 2
    cell = cells[workload]
    cfg_file = json.loads(
        (ROOT / "bench" / "configs" / f"{cell['config']}.json").read_text())
    bcfg = cfg_file["bench"]

    import jax

    from bench import byname, flops, peaks
    from bench import traffic as traffic_lib
    from bench.session import Session
    from repro.launch import runtime

    cache = runtime.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}; compile cache {cache}")
    if require_tpu and dev["platform"] != "tpu":
        log(f"run: JAX found {dev['platform']!r} devices, not a TPU")
        return 3
    if dev["count"] < int(cell["chips"]):
        log(f"run: the cell needs {cell['chips']} chips, JAX found "
            f"{dev['count']}")
        return 3
    peak = peaks.peaks(dev["kind"]) if require_tpu else None
    counter = compile_counter()

    traffic = traffic_lib.load(cell["traffic"])
    kind = traffic_lib.kind_module(traffic)
    adapter = byname.load(
        ROOT / "bench" / "adapters" / f"{bcfg['adapter']}.py", "bench_adapter")
    shape = flops.Shape.from_config(cfg_file)
    cfg = adapter.program_config(cfg_file)
    model = adapter.build_model(cfg)
    t = time.perf_counter()
    params = adapter.make_weights(model, seed)
    jax.block_until_ready(params)
    log(f"weights: {cell['config']} from seed {seed} in "
        f"{time.perf_counter() - t:.2f} s")
    serve = bcfg["serve"]
    blocks = int(serve["max_batch"]) * -(
        -traffic_lib.max_positions(traffic) // int(serve["block_size"]))
    engine = adapter.build_engine(model, params, cfg, serve, blocks, backend)
    lo, hi = kind.running_range(traffic, int(serve["max_batch"]))
    shapes = adapter.buckets(lo, hi, int(serve["max_batch"]),
                             int(serve["prefill_chunk"]))
    t, c = time.perf_counter(), dict(counter.counts)
    adapter.warm(engine, shapes)
    log(f"warm-up: {len(shapes)} step shapes {shapes} in "
        f"{time.perf_counter() - t:.2f} s ({counter.since(c)}); "
        f"pool {blocks} blocks of {serve['block_size']}")
    Request, State = adapter.request_types()
    ctx = Context(Session(engine, Request, State), traffic, seed, seconds,
                  shape.vocab)
    t = time.perf_counter()
    kind.setup(ctx)
    # Set-up leaves millions of objects behind (traced programs, caches);
    # a full collection over them stalled a window's step for 1.3-3.6 s.
    # Collect once here and keep them out of later collections.
    gc.collect()
    gc.freeze()
    log(f"traffic set-up and collection: {time.perf_counter() - t:.2f} s")
    return Setup(spec=spec, cell=cell, cfg_file=cfg_file, traffic=traffic,
                 kind=kind, adapter=adapter, shape=shape, params=params,
                 ctx=ctx, dev=dev, device=devs[0], peak=peak,
                 counter=counter, seed=seed)


def reference_readings(su, records, control: bool = False) -> dict:
    """The comparison's readings on a seeded sample of ``records``, with
    the engine freed first."""
    from bench import byname, check
    from bench import traffic as traffic_lib

    su.ctx.session.engine = None
    gc.collect()
    ref = byname.load(ROOT / "bench" / "references"
                      / f"{su.cfg_file['bench']['reference']}.py", "bench_ref")
    recs = check.sample(records, su.seed)
    t = time.perf_counter()
    readings = check.gaps(ref, su.adapter.reference_weights(su.params),
                          su.cfg_file, check.sequences(recs),
                          traffic_lib.max_positions(su.traffic), control)
    log(f"reference: {len(recs)} requests, "
        f"{int(readings['compared_tokens'])} served tokens compared in "
        f"{time.perf_counter() - t:.2f} s")
    return readings


def main(argv=None, *, require_tpu: bool = True,
         backend: str = "pallas") -> int:
    """One run.  ``require_tpu=False`` and another ``backend`` are for the
    tests, which drive the rest of a run on the CPU."""
    args = parse(argv)
    su = prepare(args.workload, args.seed, args.seconds,
                 require_tpu=require_tpu, backend=backend)
    if isinstance(su, int):
        return su
    setup_s = time.perf_counter() - T_START
    from bench import check, tracing
    from bench.metrics import reader

    c0 = dict(su.counter.counts)
    if args.trace:
        tracing.start(str(TRACE_DIR))
    window = su.kind.window(su.ctx)
    reduced = None
    if args.trace:
        tracing.stop()
        t = time.perf_counter()
        raw = tracing.extract(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(f"trace: planes {raw['planes']}; device plane "
            f"{raw['device_plane']}: {len(raw['device_modules'])} programs, "
            f"{len(raw['device_ops'])} ops, {len(raw['host_spans'])} host "
            f"spans, read in {time.perf_counter() - t:.2f} s")
        if raw["device_modules"]:
            reduced = tracing.reduce(raw)
            log(f"trace: busy {reduced.busy_s:.4f} s of "
                f"{reduced.window_s:.4f} s; operations cover "
                f"{reduced.op_busy_s:.4f} s; longest gaps (label, s, at s): "
                f"{[(n, round(g, 4), round(a, 3)) for n, g, a in reduced.gaps[:5]]}")
    for line in window.lines:
        log(line)
    import numpy as np

    dur = [(s.end - s.start) * 1e3 for s in window.steps]
    dec = [(s.end - s.start) * 1e3 for s in window.steps
           if all(q == 1 for q, _ in s.seqs)]
    if dur:
        log(f"steps: {len(dur)}, mean {np.mean(dur):.2f} ms, median "
            f"{np.median(dur):.2f} ms, max {max(dur):.2f} ms; "
            f"{len(dec)} decode-only, median "
            f"{np.median(dec) if dec else float('nan'):.2f} ms")
        slow = sorted(window.steps, key=lambda s: s.start - s.end)[:3]
        log("slowest steps (ms, lanes, sequences, at s): " + "; ".join(
            f"{(s.end - s.start) * 1e3:.1f}, {sum(q for q, _ in s.seqs)}, "
            f"{len(s.seqs)}, {s.start - window.t0:.2f}" for s in slow))
    compiled = su.counter.counts["compiled"] - c0["compiled"]
    log(f"window: {window.t_end - window.t0:.2f} s, last step ended "
        f"{window.t_last - window.t_end:+.3f} s after it; "
        f"{len(window.steps)} steps; programs inside the window and drain: "
        f"{su.counter.since(c0)}")
    dev = su.dev
    stats = su.device.memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    readings = reference_readings(su, window.records)
    # A step shape the warm-up missed compiles inside the window: the run
    # then times a compile, so it is refused rather than read as slow.
    readings["compiled_in_window"] = float(compiled)
    correct, checks = check.verdict(
        readings, {**su.cfg_file["bench"]["limits"], "compiled_in_window": 0})

    run = Run(window, setup_s, su.shape, su.peak, reduced)
    kinds = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in su.spec[kinds]:
        if _applies(m, su.cell["name"]):
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for line in run.notes:
        log(line)
    w = window
    in_win = sum(1 for r in w.records for x in r.token_times
                 if w.t0 <= x <= w.t_end)
    log(f"samples: {len(w.records)} requests counted, {w.attempted} "
        f"attempted, {w.failed} failed, "
        f"{sum(1 for r in w.records if r.token_times)} with a first token, "
        f"{in_win} tokens inside the window")
    result = {"correct": bool(correct), "attempted": int(w.attempted),
              "failed": int(w.failed), "metrics": metrics, "device": dev}
    if args.trace and reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": reduced.top_ops(10),
            "idle_gaps": [[n, g] for n, g, _ in reduced.gaps[:10]]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
