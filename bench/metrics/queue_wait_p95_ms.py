"""queue_wait_p95_ms: 95th percentile of the wait from due time to the
start of the step whose schedule admitted the request (scheduler layer)."""
from bench import stats
from bench.metrics._latency import queue_waits


def read(run):
    v = stats.percentile(queue_waits(run), 95)
    return None if v is None else v * 1e3
