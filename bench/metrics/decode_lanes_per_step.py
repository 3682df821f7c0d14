"""decode_lanes_per_step: tokens the engine emitted per step over the
window, from its own counters (``steps``, ``tokens_per_step``)."""


def _emitted(m):
    return m["tokens_per_step"] * m["steps"]


def read(run):
    w = run.window
    steps = w.m_end["steps"] - w.m_start["steps"]
    if steps <= 0:
        return None
    return (_emitted(w.m_end) - _emitted(w.m_start)) / steps
