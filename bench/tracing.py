"""The profiler trace of a window, and its reduction to numbers.

:func:`start`/:func:`stop` run a window under ``jax.profiler`` and
:func:`extract` keeps what the metrics need from the ``.xplane.pb`` it
wrote, from the first accelerator plane: the programs it executed (the
``XLA Modules`` line) and the operations inside them (``XLA Ops``, nested:
a loop and the operations in its body are both there), each as (name,
start, duration), plus the harness's own host spans (``bench.*``).  An
operation is named by its HLO instruction name (``%_ragged_pallas.10``),
not by the instruction's text, which also names its operands.
:func:`reduce` turns that into busy time (the union of the programs'
intervals), idle gaps labelled by what the host was doing, and time per
operation.  ``extract``'s output is plain JSON, so the reduction is tested
on a slice recorded on the chip.
"""
from __future__ import annotations

import glob
import os
import shutil
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def start(log_dir: str) -> None:
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def extract(log_dir: str) -> dict:
    """Device ops and ``bench.*`` host spans of the trace in ``log_dir``.

    Returns ``{"planes": [names], "device_plane": name, "device_modules":
    [[name, start_ns, dur_ns]], "device_ops": [...], "host_spans": [...]}``
    with every time on the profiler's one clock.
    """
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = {"planes": [], "device_plane": None, "device_modules": [],
           "device_ops": [], "host_spans": []}
    for plane in data.planes:
        out["planes"].append(plane.name)
        if plane.name.startswith("/device:TPU") and not out["device_plane"]:
            out["device_plane"] = plane.name
            for line in plane.lines:
                key = {MODULE_LINE: "device_modules",
                       OP_LINE: "device_ops"}.get(line.name)
                if key:
                    out[key] += [[e.name.split(" = ")[0], e.start_ns,
                                  e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host_spans"] += [
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name.startswith(SPAN_PREFIX)]
    return out


@dataclass
class Reduced:
    window_s: float
    busy_s: float                      # union of the programs
    op_busy_s: float                   # union of the operations
    op_seconds: Dict[str, float]
    gaps: List[Tuple[str, float, float]]   # (label, seconds, start), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def time_of(self, substring: str) -> float:
        """Seconds of device ops whose name contains ``substring``."""
        return sum(t for n, t in self.op_seconds.items() if substring in n)

    def top_ops(self, k: int = 10) -> List[List]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t] for n, t in ops]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def _label(spans: List[Tuple[str, float, float]], t: float) -> str:
    """The innermost harness span (other than the window) holding ``t``."""
    best: Optional[Tuple[str, float, float]] = None
    for name, a, b in spans:
        if name != WINDOW_SPAN and a <= t <= b and (
                best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "outside bench spans"


def _intervals(events):
    return [(n, float(s), float(s) + float(d)) for n, s, d in events]


def reduce(trace: dict) -> Reduced:
    """Busy time, idle gaps and per-op time inside the ``bench.window``
    span (the whole trace when there is none)."""
    ops = _intervals(trace["device_ops"])
    mods = _intervals(trace["device_modules"])
    spans = [(n, float(s), float(s) + float(d))
             for n, s, d in trace["host_spans"]]
    win = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if win:
        w0, w1 = win[0]
    elif mods:
        w0, w1 = min(a for _, a, _ in mods), max(b for _, _, b in mods)
    else:
        raise ValueError("trace has neither a window span nor programs")

    def clip(evs):
        return [(n, max(a, w0), min(b, w1)) for n, a, b in evs
                if b > w0 and a < w1]

    busy = _union([(a, b) for _, a, b in clip(mods)])
    per_op: Dict[str, float] = {}
    for n, a, b in clip(ops):
        per_op[n] = per_op.get(n, 0.0) + (b - a) * 1e-9
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(_label(spans, (a + b) / 2), (b - a) * 1e-9, (a - w0) * 1e-9)
            for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: -g[1])
    op_busy = _union([(a, b) for _, a, b in clip(ops)])
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(b - a for a, b in busy) * 1e-9,
                   op_busy_s=sum(b - a for a, b in op_busy) * 1e-9,
                   op_seconds=per_op, gaps=gaps)
