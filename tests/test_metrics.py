"""serving/metrics: nearest-rank percentile edge cases and the
EngineMetrics rollup — pure host-side, no jax."""
import pytest

from repro.serving.metrics import EngineMetrics, LatencyTracker, percentile


# ------------------------------------------------------------- percentiles
def test_empty_tracker_reports_zeros():
    t = LatencyTracker()
    assert t.percentile(50) == 0.0
    assert t.percentile(99) == 0.0
    assert t.mean == 0.0
    assert t.summary() == {"mean": 0.0, "p50": 0.0, "p90": 0.0, "p99": 0.0,
                           "n": 0.0}


def test_single_sample_is_every_percentile():
    t = LatencyTracker()
    t.record(3.5)
    for p in (0, 1, 50, 90, 99, 100):
        assert t.percentile(p) == 3.5
    assert t.mean == 3.5


def test_nearest_rank_small_n():
    """Nearest-rank: smallest sample whose rank >= ceil(p/100 * n)."""
    t = LatencyTracker()
    for v in (4.0, 1.0, 3.0, 2.0):              # insertion order irrelevant
        t.record(v)
    assert t.samples == [1.0, 2.0, 3.0, 4.0]    # sorted insertion
    assert t.percentile(50) == 2.0              # ceil(0.5*4)=2 -> rank 2
    assert t.percentile(25) == 1.0              # ceil(0.25*4)=1
    assert t.percentile(75) == 3.0
    assert t.percentile(99) == 4.0              # ceil(0.99*4)=4
    assert t.percentile(100) == 4.0
    assert t.percentile(0) == 1.0               # clamped to first sample


def test_p50_p99_on_n100_hit_exact_ranks():
    t = LatencyTracker()
    for v in range(100, 0, -1):                 # 1..100 reversed
        t.record(float(v))
    assert t.percentile(50) == 50.0
    assert t.percentile(99) == 99.0
    assert t.percentile(90) == 90.0
    assert t.mean == pytest.approx(50.5)


# ------------------------------------------------------- module-level helper
def test_percentile_helper_matches_tracker():
    """The free function is THE percentile definition — LatencyTracker and
    the replay SLO scorer (repro.perf.replay) both delegate to it, so a
    replayed p99 and an engine p99 over the same samples always agree."""
    samples = [4.0, 1.0, 3.0, 2.0]
    t = LatencyTracker()
    for v in samples:
        t.record(v)
    for p in (0, 25, 50, 75, 90, 99, 100):
        assert percentile(samples, p) == t.percentile(p)
    assert percentile(samples, 50) == 2.0       # input order irrelevant
    assert percentile([], 99) == 0.0
    assert percentile([7.5], 1) == 7.5
    assert percentile(list(range(1, 11)), 90) == 9


# ----------------------------------------------------------- engine rollup
def test_engine_metrics_summary_keys_and_types():
    m = EngineMetrics(backend="xla")
    m.record_finished(ttft=0.2, tpot=0.01, num_output_tokens=5,
                      arrival=100.0, done_at=101.0)
    m.record_finished(ttft=0.4, tpot=0.02, num_output_tokens=5,
                      arrival=100.5, done_at=102.0)
    s = m.summary()
    assert set(s) == {"backend", "finished", "output_tokens",
                      "mean_ttft_s", "p50_ttft_s", "p90_ttft_s", "p99_ttft_s",
                      "mean_tpot_s", "p50_tpot_s", "p90_tpot_s", "p99_tpot_s",
                      "throughput_tok_s", "steps", "num_idle_steps",
                      "tokens_per_step", "lane_tokens_per_step",
                      "prefill_tokens", "decode_tokens", "attn_pages",
                      "phase_s"}
    assert s["backend"] == "xla"
    assert s["finished"] == 2
    assert s["output_tokens"] == 10
    assert s["p50_ttft_s"] == 0.2 and s["p99_ttft_s"] == 0.4
    assert s["p90_ttft_s"] == 0.4 and s["p90_tpot_s"] == 0.02
    # wall clock spans first arrival -> last finish
    assert m.elapsed_s == pytest.approx(2.0)
    assert s["throughput_tok_s"] == pytest.approx(10 / 2.0)


def test_engine_metrics_empty_run_no_division_by_zero():
    s = EngineMetrics().summary()
    assert s["finished"] == 0
    assert s["throughput_tok_s"] == 0.0
    assert s["mean_ttft_s"] == 0.0 and s["p99_tpot_s"] == 0.0


def test_engine_metrics_step_accounting_and_phase_buckets():
    """record_step: tokens-per-step means emitted OUTPUT tokens per step
    (speculative decoding pushes it past one per decode lane), lane tokens
    count the fused program's width, and phase walls accumulate per key."""
    m = EngineMetrics()
    m.record_step(num_tokens=8, emitted_tokens=1,
                  phases={"propose": 0.1, "device": 0.5})
    m.record_step(num_tokens=4, emitted_tokens=3,
                  phases={"propose": 0.2, "device": 0.5, "commit": 0.25})
    s = m.summary()
    assert s["steps"] == 2
    assert s["tokens_per_step"] == pytest.approx(2.0)       # (1 + 3) / 2
    assert s["lane_tokens_per_step"] == pytest.approx(6.0)  # (8 + 4) / 2
    assert s["phase_s"] == pytest.approx(
        {"propose": 0.3, "device": 1.0, "commit": 0.25})


def test_engine_metrics_zero_steps_no_division_by_zero():
    s = EngineMetrics().summary()
    assert s["steps"] == 0
    assert s["tokens_per_step"] == 0.0
    assert s["lane_tokens_per_step"] == 0.0
    assert s["phase_s"] == {}


def test_engine_metrics_none_latencies_skip_trackers():
    """A request preempted before its first token has ttft/tpot None —
    recorded as finished without poisoning the percentile trackers."""
    m = EngineMetrics()
    m.record_finished(ttft=None, tpot=None, num_output_tokens=1,
                      arrival=10.0, done_at=11.0)
    s = m.summary()
    assert s["finished"] == 1
    assert s["mean_ttft_s"] == 0.0
    assert float(m.ttft.summary()["n"]) == 0.0
