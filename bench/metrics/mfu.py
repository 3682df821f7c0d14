"""mfu: the model FLOPs of the work the window's steps did, over the
window's length times the chip's peak, in % (the whole step's share of the
peak)."""
from bench import flops


def read(run):
    w = run.window
    if run.peak is None or not w.steps or w.t_last <= w.t0:
        return None
    work = sum(flops.step_flops(run.shape, s.seqs, s.sampled)
               for s in w.steps)
    return 100.0 * work / ((w.t_last - w.t0) * run.peak_flops)
