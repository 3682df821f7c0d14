"""ragged_attn_roofline: the least time the window's attention work needs
on this chip (max of FLOPs over peak and bytes over HBM bandwidth, from
each step's per-sequence (q_len, kv_len)) over the device time of the
ragged attention kernel's trace events, in %."""
from bench import flops

KERNEL = "ragged"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t_kernel = run.trace.time_of(KERNEL)
    seqs = [q for s in run.window.steps for q in s.seqs]
    if t_kernel <= 0 or not seqs:
        return None
    t_min, bound = flops.least_time(
        flops.attention_flops(run.shape, seqs),
        flops.attention_bytes(run.shape, seqs), run.peak_flops,
        run.peak["hbm_bytes_per_s"])
    run.note(f"ragged_attn_roofline: {bound}-bound, least "
             f"{t_min * 1e3:.3f} ms against {t_kernel * 1e3:.3f} ms of "
             f"kernel time over {len(run.window.steps)} steps")
    return 100.0 * t_min / t_kernel
