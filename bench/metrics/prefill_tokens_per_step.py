"""prefill_tokens_per_step: prompt tokens the engine's plans put in a step,
over the window, from its own counters (``prefill_tokens``, ``steps``).
Nothing to read from a program without the counter."""


def read(run):
    w = run.window
    steps = w.m_end["steps"] - w.m_start["steps"]
    if "prefill_tokens" not in w.m_end or steps <= 0:
        return None
    return (w.m_end["prefill_tokens"] - w.m_start["prefill_tokens"]) / steps
