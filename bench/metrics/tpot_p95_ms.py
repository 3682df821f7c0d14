"""tpot_p95_ms: 95th percentile over requests of each one's time per output
token, over the tokens it received in the window."""
from bench import stats
from bench.metrics._latency import tpots


def read(run):
    v = stats.percentile(tpots(run), 95)
    return None if v is None else v * 1e3
