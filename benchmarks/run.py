"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--full`` widens sweeps.

``--backend a,b,...`` repeats the run once per backend name, with each pass
scoped under ``dispatch.force_backend`` so every registry-dispatched op
(kernels AND the serving engine) follows the preference; ``--json PATH``
then writes the per-backend rows plus the ``(op, backend)`` pairs that
actually resolved — the paper-style microbenchmark comparison across
software stacks, attributable to the implementation that really ran
(a registered backend that cannot serve a call raises; an op family
without that backend resolves by capability-ranked auto).

``--policy adm/pre/evi,...`` sweeps serving-policy triples the same way:
each triple is scoped under ``repro.serving.policy.force_policies`` so every
serving engine built inside the pass (the bursty / shared-prefix /
memory-pressure / repetitive-suffix scenarios of ``llm_e2e``) runs that
admission/preemption/eviction combination; rows and JSON records carry the
resolved triple.  An axis left empty (``//refcount-aware``) keeps its
default.  Only modules in ``POLICY_SENSITIVE`` (those that build serving
engines) repeat per triple; policy-blind modules run once, under the first
triple — their numbers cannot depend on the policy choice.

``--spec off,ngram,draft-model`` sweeps speculative-decoding proposers the
same way again (scoped under ``repro.serving.spec.force_proposer``); every
llm_e2e engine row carries the resolved proposer plus its acceptance rate,
so multi-token-decode wins are attributable to one proposer.  Like policy
sweeps, only ``SPEC_SENSITIVE`` modules repeat per proposer.  The
``draft-model`` pass runs k extra draft forwards per decode step — treat it
as a slow sweep (it is skipped under ``REPRO_BENCH_SMOKE=1``; the CI smoke
sweeps ``off,ngram`` only).

``--devices 1,2,4`` sweeps host device counts: the XLA device count is
fixed at first jax init, so each count re-runs the selected modules in a
SUBPROCESS under ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>``
and ``JAX_PLATFORMS=cpu``.  Those are CPU devices, so on a host with TPU
chips the sweep refuses to start: the children would contend for the chip
or time the CPU on an accelerator host.
With > 1 device the llm_e2e scenario engines build a serving mesh
(``repro.launch.mesh.make_serving_mesh``) and run the sharded fused step
(docs/sharded_serving.md); every ``--json`` record and row is stamped with
``devices=<n>``, so single-vs-mesh throughput is attributable per count —
the paper-style scale-out comparison for the serving stack.  ``--devices``
composes with the other sweep flags (they are forwarded to each
subprocess).

| module                 | paper figure/table |
|------------------------|--------------------|
| gemm_roofline          | Fig 4, 5, 7        |
| stream                 | Fig 8 / Alg 1      |
| gather_scatter         | Fig 9              |
| collectives            | Fig 10             |
| embedding_tables       | Fig 15 (S4.1)      |
| paged_attention_bench  | Fig 17 a-c (S4.2)  |
| recsys_e2e             | Fig 11 / Table 3   |
| llm_e2e                | Fig 12, 17 d-e     |
| saturation             | S4.2 pipeline      |
| disagg                 | S4.2 disaggregation|
| trace_replay           | S5 trace replay / SLO sweep (docs/perf_gate.md) |

Every ``--json`` result carries provenance: ``schema_version`` (bumped on
incompatible row-grammar changes — ``repro.perf.gate`` refuses to diff a
mismatch), a best-effort ``git_commit``, and per-row ``seed`` where the
module's workload is RNG-generated.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import subprocess
import sys
import tempfile
import time
import traceback

from benchmarks import common
from repro.core import dispatch
from repro.launch import runtime
from repro.serving import policy as policy_lib
from repro.serving import spec as spec_lib

MODULES = [
    "gemm_roofline",
    "stream",
    "gather_scatter",
    "collectives",
    "embedding_tables",
    "paged_attention_bench",
    "recsys_e2e",
    "llm_e2e",
    "saturation",
    "disagg",
    "trace_replay",
]

# Modules that build serving engines — the only ones whose numbers can
# depend on the serving-policy triple. A --policy sweep re-runs just these
# per triple; everything else runs once (under the first triple's scope).
# trace_replay is deliberately NOT here: it sweeps policy triples itself
# with explicit ctor args (which outrank any force_policies scope), so an
# outer --policy pass cannot change its numbers.
POLICY_SENSITIVE = {"llm_e2e", "saturation", "disagg"}
# Likewise for the speculative-decoding proposer (--spec sweep).
SPEC_SENSITIVE = {"llm_e2e"}


def _parse_spec_names(arg):
    """``off,ngram,draft-model`` -> canonical proposer names (validated).

    Aliases (``draft``) normalize here so pass labels, the smoke skip and
    per-row attribution all agree on one spelling."""
    out = []
    for name in arg.split(","):
        name = name.strip()
        if name != spec_lib.OFF:
            try:
                name = spec_lib.get(name).name
            except spec_lib.UnknownProposerError as e:
                raise SystemExit(f"--spec: {e}") from None
        out.append(name)
    return out


def _parse_policy_triples(arg):
    """``adm/pre/evi,adm/pre/evi`` -> list of per-axis override dicts.

    Names are validated here so a typo fails as one usage error before the
    sweep starts, not as a traceback per module."""
    triples = []
    for spec in arg.split(","):
        parts = spec.split("/")
        if len(parts) != 3:
            raise SystemExit(
                f"--policy: expected admission/preemption/eviction, "
                f"got {spec!r}")
        triple = {}
        for axis, name in zip(policy_lib.AXES, parts):
            if name:
                try:
                    policy_lib.get(axis, name)
                except policy_lib.UnknownPolicyError as e:
                    raise SystemExit(f"--policy: {e}") from None
            triple[axis] = name or None
        triples.append(triple)
    return triples


def _resolved_triple(plog):
    """Attribute one policy triple to a pass from its resolution log."""
    by_axis = {}
    for axis, name in plog:
        by_axis.setdefault(axis, set()).add(name)
    return "/".join(
        "/".join(sorted(by_axis[a])) if a in by_axis else policy_lib.DEFAULTS[a]
        for a in policy_lib.AXES)


def _sweep_devices(args) -> int:
    """Re-run the selected modules once per host device count.

    The XLA host-platform device count is frozen at first jax init, so each
    count gets its own subprocess with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` — the child is
    this very module minus ``--devices``/``--json``, plus a temp ``--json``
    whose records the parent merges with a ``devices`` stamp on every
    record and row.
    """
    if runtime.tpu_chips_attached():
        raise SystemExit(
            "--devices sweeps CPU host devices in child processes; this host "
            "has TPU chips, which one process at a time may hold. Run the "
            "sharded engine in one process instead (python chip_smoke.py "
            "--chips 4, or python -m repro.launch.serve --devices N).")
    counts = []
    for c in args.devices.split(","):
        try:
            counts.append(int(c))
        except ValueError:
            raise SystemExit(f"--devices: not a device count: {c!r}")
        if counts[-1] < 1:
            raise SystemExit(f"--devices: device counts are >= 1: {c!r}")
    child_args, skip = [], False
    for a in sys.argv[1:]:
        if skip:
            skip = False
            continue
        if a in ("--devices", "--json"):
            skip = True
            continue
        if a.startswith(("--devices=", "--json=")):
            continue
        child_args.append(a)
    merged, failures = [], 0
    for n in counts:
        print(f"# devices sweep: {n}", file=sys.stderr)
        env = dict(os.environ)
        # APPEND the forced count: XLA flag parsing is last-occurrence-wins,
        # so a pre-existing --xla_force_host_platform_device_count in the
        # user's XLA_FLAGS must not silently override the sweep.
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}"
                            ).strip()
        env["JAX_PLATFORMS"] = "cpu"
        # Engine-building modules (llm_e2e) opt into a serving mesh ONLY on
        # this explicit signal — ambient multi-device hosts keep running the
        # single-device engine so --backend sweeps stay comparable.
        env["REPRO_BENCH_DEVICES"] = str(n)
        fd, tmp = tempfile.mkstemp(suffix=".json", prefix="bench_devices_")
        os.close(fd)
        try:
            r = subprocess.run(
                [sys.executable, "-m", "benchmarks.run", *child_args,
                 "--json", tmp], env=env)
            failures += r.returncode != 0
            try:
                with open(tmp) as f:
                    results = json.load(f)
            except (OSError, json.JSONDecodeError):
                results = []
            for res in results:
                res["devices"] = n
                for row in res["rows"]:
                    row["devices"] = n
            merged.extend(results)
        finally:
            os.unlink(tmp)
        print(f"# devices={n} done", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(merged, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)
    return 1 if failures else 0


def main() -> None:
    # allow_abbrev=False: _sweep_devices re-invokes this module with
    # --devices/--json stripped from sys.argv BY EXACT SPELLING — an
    # abbreviated `--device` would survive the strip, re-trigger the sweep
    # in every child and fork forever.
    p = argparse.ArgumentParser(allow_abbrev=False)
    p.add_argument("--only", default=None, help="comma-separated module list")
    p.add_argument("--full", action="store_true")
    p.add_argument("--backend", default=None,
                   help="comma-separated backend sweep (e.g. "
                        "ref,xla,pallas_interpret); each backend scopes the "
                        "whole run via repro.core.dispatch.force_backend")
    p.add_argument("--policy", default=None,
                   help="comma-separated serving-policy triples "
                        "admission/preemption/eviction (e.g. "
                        "fcfs/latest-arrival/lru,priority/most-blocks/"
                        "hit-rate); each triple scopes the run via "
                        "repro.serving.policy.force_policies")
    p.add_argument("--spec", default=None,
                   help="comma-separated speculative-proposer sweep (e.g. "
                        "off,ngram,draft-model); each name scopes the run "
                        "via repro.serving.spec.force_proposer")
    p.add_argument("--devices", default=None,
                   help="comma-separated host device counts (e.g. 1,2,4); "
                        "each count re-runs the selected modules in a "
                        "subprocess with XLA_FLAGS=--xla_force_host_"
                        "platform_device_count=<n> — multi-device passes "
                        "run the sharded serving engine and every JSON "
                        "row is stamped devices=<n>")
    p.add_argument("--json", default=None,
                   help="write per-backend/per-policy/per-proposer result "
                        "rows (+ resolved (op, backend), (axis, policy) and "
                        "proposer names) to this path")
    args = p.parse_args()
    if args.devices is not None:
        raise SystemExit(_sweep_devices(args))
    runtime.enable_compile_cache()
    mods = args.only.split(",") if args.only else MODULES
    backends = args.backend.split(",") if args.backend else [None]
    policies = (_parse_policy_triples(args.policy) if args.policy
                else [None])
    specs = _parse_spec_names(args.spec) if args.spec else [None]
    print("name,us_per_call,derived")
    failures = 0
    results = []
    commit = common.git_commit()
    for b in backends:
        if b is not None:
            print(f"# backend sweep: {b}", file=sys.stderr)
        for (pi, pol), (si, spc) in itertools.product(enumerate(policies),
                                                      enumerate(specs)):
            pol_kwargs = {a: (pol or {}).get(a) for a in policy_lib.AXES}
            pol_str = ("/".join(pol_kwargs[a] or policy_lib.DEFAULTS[a]
                                for a in policy_lib.AXES)
                       if pol is not None else None)
            if pol_str is not None:
                print(f"# policy sweep: {pol_str}", file=sys.stderr)
            if spc is not None:
                print(f"# spec sweep: {spc}", file=sys.stderr)
            for m in mods:
                if pol is not None and pi > 0 and m not in POLICY_SENSITIVE:
                    continue               # policy-blind: one pass is enough
                if spc is not None and si > 0 and m not in SPEC_SENSITIVE:
                    continue               # proposer-blind: ditto
                mod = __import__(f"benchmarks.{m}", fromlist=["run"])
                t0 = time.time()
                common.RECORDS.clear()
                log, plog, slog = [], [], []
                try:
                    with dispatch.force_backend(b), \
                            dispatch.record_resolutions() as log, \
                            policy_lib.force_policies(**pol_kwargs), \
                            policy_lib.record_resolutions() as plog, \
                            spec_lib.force_proposer(spc), \
                            spec_lib.record_resolutions() as slog:
                        mod.run(quick=not args.full)
                except Exception:
                    traceback.print_exc()
                    failures += 1
                resolved_pol = _resolved_triple(plog) if plog else None
                resolved_spec = (sorted(set(slog))[0]
                                 if len(set(slog)) == 1 else None)
                # sanitize attribution: REPRO_SANITIZE=1 rows ran under the
                # runtime guards (retrace/host-sync/allocator) — stamped per
                # row like policy/spec so guarded and unguarded sweeps are
                # distinguishable in one JSON
                sanitized = os.environ.get("REPRO_SANITIZE") == "1"
                results.append({
                    "module": m,
                    "schema_version": common.SCHEMA_VERSION,
                    "git_commit": commit,
                    "requested_backend": b or "auto",
                    "requested_policy": pol_str or "default",
                    "requested_spec": spc or "default",
                    "sanitize": sanitized,
                    "resolved": sorted({f"{op}={bk}" for op, bk in log}),
                    "resolved_policies": sorted(
                        {f"{ax}={nm}" for ax, nm in plog}),
                    "resolved_spec": sorted(set(slog)),
                    "rows": [dict(r) for r in common.RECORDS],
                })
                for r in results[-1]["rows"]:
                    # setdefault: rows that self-attribute via emit(**attrs)
                    # (trace_replay's internal policy sweep) keep their own
                    # per-row triple over the pass-level rollup.
                    if resolved_pol:
                        r.setdefault("policy", resolved_pol)
                    if resolved_spec:
                        r.setdefault("spec", resolved_spec)
                    r["sanitize"] = sanitized
                print(f"# {m} done in {time.time()-t0:.1f}s"
                      + (f" [backend={b}]" if b else "")
                      + (f" [policy={pol_str}]" if pol_str else "")
                      + (f" [spec={spc}]" if spc else ""),
                      file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
