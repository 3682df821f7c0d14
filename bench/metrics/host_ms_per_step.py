"""host_ms_per_step: the engine's host phases per step over the window
(``propose + schedule_render + commit`` of its ``phase_s``), in ms."""

PHASES = ("propose", "schedule_render", "commit")


def read(run):
    w = run.window
    steps = w.m_end["steps"] - w.m_start["steps"]
    if steps <= 0:
        return None
    s0, s1 = w.m_start["phase_s"], w.m_end["phase_s"]
    host = sum(s1.get(k, 0.0) - s0.get(k, 0.0) for k in PHASES)
    return host / steps * 1e3
