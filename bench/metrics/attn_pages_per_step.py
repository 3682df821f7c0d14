"""attn_pages_per_step: BlockList entries the ragged kernel's query tiles
walk in one layer, per step over the window, from the engine's own counters
(``attn_pages``, ``steps``).  Nothing to read from a program without the
counter."""


def read(run):
    w = run.window
    steps = w.m_end["steps"] - w.m_start["steps"]
    if "attn_pages" not in w.m_end or steps <= 0:
        return None
    return (w.m_end["attn_pages"] - w.m_start["attn_pages"]) / steps
