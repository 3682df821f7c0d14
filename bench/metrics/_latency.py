"""Per-request latencies of a window, on the harness's clock (seconds)."""
from bench import stats


def ttfts(run):
    """Due time to first token, of every request the window sent; one with
    no first token counts up to the drain's end (a lower bound)."""
    w = run.window
    return [(r.first_token if r.first_token is not None else w.closed)
            - r.due for r in w.records]


def tpots(run):
    """Time per output token of each request over the tokens it received
    inside the window (requests with at least two)."""
    w = run.window
    out = []
    for r in w.records:
        t = stats.tpot([x for x in r.token_times if w.t0 <= x <= w.t_end])
        if t is not None:
            out.append(t)
    return out


def queue_waits(run):
    """Due time to the start of the step that admitted the request."""
    w = run.window
    return [(r.admitted if r.admitted is not None else w.closed) - r.due
            for r in w.records]
