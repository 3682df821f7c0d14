"""output_tok_s: output tokens observed inside the window over the
window's length."""
from bench import stats


def read(run):
    w = run.window
    n = sum(sum(1 for x in r.token_times if w.t0 <= x <= w.t_end)
            for r in w.records)
    return stats.rate(n, w.t_end - w.t0)
