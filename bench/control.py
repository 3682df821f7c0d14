#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python bench/control.py --workload smollm-360m.decode --seconds 30 \\
        --seeds 11,12,13

For each seed, in one process: the cell is set up and run for one window
as a benchmark run would, the engine is freed, and on the same sampled
requests two numbers are read against the float32 reference: the program's
widest served-token gap (its lower reading) and the control's, the widest
gap of the tokens the reference computed in fp8 ranks first (its upper
reading).  Each is judged by ``check.verdict`` against the configuration's
limits, as a run judges the program: ``program_correct`` should read true
and ``control_correct`` false.  Each seed prints one JSON line; the last
line has the largest program reading, the smallest control reading, and
whether every seed's program and any seed's control read correct.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import check  # noqa: E402
from bench import run as run_lib  # noqa: E402


def main(argv=None, *, require_tpu: bool = True,
         backend: str = "pallas") -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    prog, ctrl, prog_ok, ctrl_ok = [], [], [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        su = run_lib.prepare(args.workload, seed, args.seconds,
                             require_tpu=require_tpu, backend=backend)
        if isinstance(su, int):
            return su
        w = su.kind.window(su.ctx)
        r = run_lib.reference_readings(su, w.records, control=True)
        limits = su.cfg_file["bench"]["limits"]
        prog_ok.append(check.verdict(r, limits)[0])
        ctrl_ok.append(check.verdict(
            dict(r, served_logit_gap=r["control_logit_gap"]), limits)[0])
        prog.append(r["served_logit_gap"])
        ctrl.append(r["control_logit_gap"])
        print(json.dumps({"seed": seed, **r, "program_correct": prog_ok[-1],
                          "control_correct": ctrl_ok[-1]}), flush=True)
        del su, w
    print(json.dumps({"workload": args.workload, "seeds": len(prog),
                      "program_max": max(prog), "control_min": min(ctrl),
                      "program_correct": all(prog_ok),
                      "control_correct": any(ctrl_ok)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
