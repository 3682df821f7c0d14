"""admit_wait_p95_ms: 95th percentile over the window's requests of the
wait from arrival to first admission, as the program stamps both on the
request (``Request.arrival``, ``Request.admitted_at``; scheduler layer).

A request never admitted counts to the drain's end, moved onto the
request's clock by its own offset (``req.arrival - sent``).  Nothing to
read from a program without the stamp."""
from bench import stats


def admit_waits(run):
    w = run.window
    out = []
    for r in w.records:
        req = r.req
        if not hasattr(req, "admitted_at"):
            return None
        at = (req.admitted_at if req.admitted_at is not None
              else w.closed + (req.arrival - r.sent))
        out.append(at - req.arrival)
    return out


def read(run):
    v = stats.percentile(admit_waits(run) or [], 95)
    return None if v is None else v * 1e3
