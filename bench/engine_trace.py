"""The engine's own spans and the step's named scopes in a profiler trace.

The program marks its host work with ``engine.*`` spans
(``repro.serving.spans``) and its fused step's parts with ``jax.named_scope``
(which land in each HLO instruction's ``op_name``).  :func:`extract` keeps
the spans beside what :func:`bench.tracing.extract` keeps, and
:func:`reduce` adds to :func:`bench.tracing.reduce`, whose numbers it leaves
as they are:

* ``scope_seconds``: device seconds of leaf operations per scope, the scope
  being the first component of the operation's ``op_name`` (a fourth field
  of each device op, which :func:`name_ops` fills) that is one of
  :data:`SCOPES` (``unscoped`` otherwise).  A container (a loop whose body's
  operations are on the same line) is not a leaf and is not counted.
* ``gaps``: the same idle gaps, each labelled by the innermost ``bench.*``
  or ``engine.*`` span holding it.
* ``slowest_step``: the longest ``engine.step`` span of the window, its
  engine spans, and what the device did during its ``engine.wait``.

A TPU trace names each device operation by its HLO instruction and carries
no ``op_name`` for it (v5e, JAX 0.9: its stats are the device offset and
duration alone).  :func:`name_ops` takes the names from the compiled text
of the step programs (:func:`hlo_op_names`), giving each program execution
(an ``XLA Modules`` event) the program whose instructions cover most of the
operations it ran.

Nothing here is read by ``bench/run.py``; ``bench/observe.py`` prints it.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from bench import tracing

SCOPES = ("embed", "qkv", "kv_append", "attention", "attn_out", "mlp",
          "unembed", "sample", "verify")
ENGINE_PREFIX = "engine."
STEP_SPAN = "engine.step"
WAIT_SPAN = "engine.wait"
UNSCOPED = "unscoped"
_OP_NAME_IN_TEXT = re.compile(r'op_name="([^"]*)"')


def extract(log_dir: str) -> dict:
    """:func:`bench.tracing.extract`'s output with the ``engine.*`` host
    spans kept beside the ``bench.*`` ones."""
    prefix = tracing.SPAN_PREFIX
    # extract() keeps the host events whose name starts with SPAN_PREFIX,
    # which str.startswith also accepts as a tuple of prefixes
    tracing.SPAN_PREFIX = (prefix, ENGINE_PREFIX)
    try:
        return tracing.extract(log_dir)
    finally:
        tracing.SPAN_PREFIX = prefix


def hlo_op_names(text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of one compiled program's HLO text."""
    out = {}
    for line in text.splitlines():
        head, eq, _ = line.partition(" = ")
        m = _OP_NAME_IN_TEXT.search(line) if eq else None
        if m:
            out[_bare(head.split()[-1])] = m.group(1)
    return out


def _bare(name: str) -> str:
    return name.lstrip("%")


def name_ops(trace: dict, programs: List[Dict[str, str]]) -> int:
    """Fill the missing ``op_name`` of each device op in ``trace`` from the
    program (an :func:`hlo_op_names` map) whose instructions cover most of
    the ops of the execution holding it; returns how many were named."""
    if not programs:
        return 0
    ops = sorted(trace["device_ops"], key=lambda o: float(o[1]))
    named, i = 0, 0
    for _, a, d in sorted(trace["device_modules"], key=lambda m: float(m[1])):
        a, b = float(a), float(a) + float(d)
        while i < len(ops) and float(ops[i][1]) < a:
            i += 1
        j = i
        while j < len(ops) and float(ops[j][1]) < b:
            j += 1
        inside = ops[i:j]
        names = [_bare(o[0]) for o in inside]
        best = max(programs, key=lambda p: sum(n in p for n in names))
        for o, n in zip(inside, names):
            if len(o) < 4:
                o.append(None)
            if o[3] is None and n in best:
                o[3] = best[n]
                named += 1
        i = j
    return named


def scope_of(op_name: Optional[str]) -> str:
    for part in (op_name or "").split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def leaves(ops: List[list]) -> List[list]:
    """The operations ``[name, start, dur, ...]`` that hold no other
    operation of the line."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt[1] >= o[1] + o[2]]


@dataclass
class Observed:
    base: tracing.Reduced                   # bench.tracing.reduce's numbers
    scope_seconds: Dict[str, float]
    gaps: List[Tuple[str, float, float]]    # relabelled, longest first
    slowest_step: Optional[dict]

    def scopes(self) -> List[List]:
        """[scope, seconds], longest first."""
        return [[k, v] for k, v in sorted(self.scope_seconds.items(),
                                          key=lambda kv: -kv[1])]


def _window(spans, mods) -> Tuple[float, float]:
    win = [(a, b) for n, a, b in spans if n == tracing.WINDOW_SPAN]
    if win:
        return win[0]
    return min(a for _, a, _ in mods), max(b for _, _, b in mods)


def _overlap(ivs, a, b) -> float:
    return sum(max(min(y, b) - max(x, a), 0.0) for x, y in ivs)


def _slowest_step(spans, mods, ops, w0, w1) -> Optional[dict]:
    steps = [s for s in spans if s[0] == STEP_SPAN and s[1] >= w0
             and s[2] <= w1]
    if not steps:
        return None
    _, a, b = max(steps, key=lambda s: s[2] - s[1])
    busy = tracing._union([(x, y) for _, x, y in mods])
    inner = sorted((s for s in spans if s[0].startswith(ENGINE_PREFIX)
                    and s[0] != STEP_SPAN and a <= s[1] and s[2] <= b),
                   key=lambda s: s[1])
    waits = []
    for n, x, y in inner:
        if n != WAIT_SPAN:
            continue
        dev = _overlap(busy, x, y)
        per: Dict[str, float] = {}
        for name, s, e in ops:
            d = max(min(e, y) - max(s, x), 0.0)
            if d > 0:
                per[name] = per.get(name, 0.0) + d * 1e-6
        top = sorted(per.items(), key=lambda kv: -kv[1])[:5]
        waits.append({"ms": (y - x) * 1e-6, "device_busy_ms": dev * 1e-6,
                      "device_idle_ms": (y - x - dev) * 1e-6,
                      "top_ops_ms": [[k, v] for k, v in top]})
    return {"at_s": (a - w0) * 1e-9, "ms": (b - a) * 1e-6,
            "spans_ms": [[n[len(ENGINE_PREFIX):], (y - x) * 1e-6]
                         for n, x, y in inner],
            "waits": waits}


def reduce(trace: dict) -> Observed:
    """:func:`bench.tracing.reduce` of the same trace, plus scope seconds,
    gaps labelled by engine spans too, and the slowest step."""
    bench_only = [s for s in trace["host_spans"]
                  if s[0].startswith(tracing.SPAN_PREFIX)]
    base = tracing.reduce({"device_modules": trace["device_modules"],
                           "device_ops": [o[:3] for o in trace["device_ops"]],
                           "host_spans": bench_only})
    spans = tracing._intervals(trace["host_spans"])
    mods = tracing._intervals(trace["device_modules"])
    w0, w1 = _window(spans, mods)
    ops = [[o[0], float(o[1]), float(o[2]), o[3] if len(o) > 3 else None]
           for o in trace["device_ops"]]
    leaf = [(n, max(s, w0), min(s + d, w1), op) for n, s, d, op in leaves(ops)
            if s + d > w0 and s < w1]
    scope_s: Dict[str, float] = {}
    for _, a, b, op in leaf:
        k = scope_of(op)
        scope_s[k] = scope_s.get(k, 0.0) + (b - a) * 1e-9
    gaps = [(tracing._label(spans, w0 + (at + g / 2) * 1e9), g, at)
            for _, g, at in base.gaps]
    return Observed(base=base, scope_seconds=scope_s, gaps=gaps,
                    slowest_step=_slowest_step(
                        spans, mods, [(n, a, b) for n, a, b, _ in leaf],
                        w0, w1))
