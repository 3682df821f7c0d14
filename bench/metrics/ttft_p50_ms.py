"""ttft_p50_ms: median time to first token over every request sent in the
window, from when it was due."""
from bench import stats
from bench.metrics._latency import ttfts


def read(run):
    v = stats.percentile(ttfts(run), 50)
    return None if v is None else v * 1e3
