"""dispatch_ms_per_step: host time of the engine's ``engine.dispatch`` span
(the jitted step call, which ``host_ms_per_step`` leaves out) per step over
the window, from ``engine.metrics()["spans"]``, in ms.  Nothing to read
from a program without the span."""


def read(run):
    w = run.window
    s1 = w.m_end.get("spans", {}).get("dispatch")
    steps = w.m_end["steps"] - w.m_start["steps"]
    if s1 is None or steps <= 0:
        return None
    s0 = w.m_start.get("spans", {}).get("dispatch", {"s": 0.0})
    return (s1["s"] - s0["s"]) / steps * 1e3
