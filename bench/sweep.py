#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest arrival rate at
which the backlog does not grow over the window.

    python bench/sweep.py --workload smollm-360m.chat --rates 0.2,0.3,0.4 \\
        --seconds 51 --seed 1

One process, one engine: for each rate the cell's traffic is sent at that
rate for ``--seconds`` (the same window and drain as a run), then the
engine is stepped until idle before the next rate.  The backlog is the
requests due but not yet given a first token; after each step of the
window it is read, and a least-squares line through those readings gives
its growth over the window.  A rate is sustained when every request sent
got its first token in the drain and that growth is under ``GROWTH``
requests.  Rates are swept upwards and the sweep stops at the first rate
not sustained.  The last line names the knee and the rate to write into
the cell's traffic file (four fifths of it).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from bench import run as run_lib  # noqa: E402
from bench import stats  # noqa: E402

GROWTH = 2.0
SHARE = 0.8


def backlog_growth(w) -> float:
    """Requests the backlog grew by over the window, by a least-squares
    line through its value after each step."""
    ts = [s.end for s in w.steps if s.end <= w.t_end]
    if len(ts) < 2:
        return 0.0
    first = [r.first_token if r.first_token is not None else float("inf")
             for r in w.records]
    due = [r.due for r in w.records]
    b = [sum(d <= t for d in due) - sum(f <= t for f in first) for t in ts]
    slope = np.polyfit(np.asarray(ts) - w.t0, np.asarray(b, float), 1)[0]
    return float(slope * (w.t_end - w.t0))


def sustained(w) -> dict:
    """TTFT statistics of one rate's window and whether it kept up."""
    ttft = [(r.first_token if r.first_token is not None else w.closed)
            - r.due for r in w.records]
    failed = sum(r.first_token is None for r in w.records)
    growth = backlog_growth(w)
    return {"sent": len(w.records), "failed": failed,
            "ttft_p50_ms": stats.percentile(ttft, 50) * 1e3,
            "ttft_p95_ms": stats.percentile(ttft, 95) * 1e3,
            "backlog_growth": growth,
            "ok": failed == 0 and growth < GROWTH}


def main(argv=None, *, require_tpu: bool = True,
         backend: str = "pallas") -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated arrival rates, requests/s")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    su = run_lib.prepare(args.workload, args.seed, args.seconds,
                         require_tpu=require_tpu, backend=backend)
    if isinstance(su, int):
        return su
    if su.traffic["kind"] != "open_loop":
        run_lib.log("sweep: only an open-loop cell has a knee")
        return 2
    knee = None
    for r in rates:
        su.ctx.traffic = dict(su.traffic, rate_per_s=r)
        su.kind.setup(su.ctx)
        w = su.kind.window(su.ctx)
        while su.ctx.session.busy:
            su.ctx.session.step()
        row = {"rate_per_s": r, **sustained(w)}
        print(json.dumps(row), flush=True)
        if not row["ok"]:
            break                       # rates are swept upwards
        knee = r
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else round(SHARE * knee, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
