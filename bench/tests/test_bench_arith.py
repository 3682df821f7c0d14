"""The harness's arithmetic on synthetic inputs: percentiles, TPOT,
rates, the latency readers, and FLOP and byte counts."""
from __future__ import annotations

import math

import pytest

from bench import flops, stats
from bench.metrics import reader
from bench.session import Job, Record, Step, Window


def test_percentile_is_nearest_rank():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 95) == 5.0          # ceil(4.75) = 5th
    assert stats.percentile(xs, 20) == 1.0
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile([], 50) is None


def test_tpot_and_rate():
    assert stats.tpot([1.0, 1.5, 2.0, 3.0]) == pytest.approx(2.0 / 3)
    assert stats.tpot([1.0]) is None
    assert stats.rate(300, 30.0) == 10.0
    assert stats.rate(1, 0.0) is None


def _window():
    """Three requests due at 0, 1 and 2 s in a window [0, 10] whose drain
    closed at 12 s."""
    recs = [
        Record(rid=0, job=Job(None, 4), due=0.0, admitted=0.5,
               token_times=[1.0, 2.0, 3.0, 4.0]),
        Record(rid=1, job=Job(None, 4), due=1.0, admitted=1.5,
               token_times=[3.0, 9.0, 11.0]),        # one token after t_end
        Record(rid=2, job=Job(None, 4), due=2.0),    # never served
    ]
    steps = [Step(start=0.0, end=1.0, seqs=[(100, 100)], sampled=1),
             Step(start=1.0, end=2.0, seqs=[(1, 101), (50, 50)], sampled=2)]
    m0 = {"steps": 10, "tokens_per_step": 2.0,
          "phase_s": {"propose": 0.1, "schedule_render": 0.2,
                      "commit": 0.0, "device": 5.0}}
    m1 = {"steps": 20, "tokens_per_step": 2.5,
          "phase_s": {"propose": 0.2, "schedule_render": 0.4,
                      "commit": 0.1, "device": 9.0}}
    return Window(t0=0.0, t_end=10.0, t_last=2.0, records=recs, steps=steps,
                  attempted=3, failed=1, m_start=m0, m_end=m1, closed=12.0)


class _Run:
    def __init__(self, window):
        self.window, self.setup_s, self.trace, self.peak = window, 7.5, None, None


@pytest.mark.parametrize("metric, want", [
    # TTFT: 1.0, 2.0 and (censored at the drain's end) 10.0 s
    ("ttft_p50_ms", 2000.0),
    ("ttft_p95_ms", 10000.0),
    # TPOT over in-window tokens: (4-1)/3 = 1.0 and (9-3)/1 = 6.0
    ("tpot_p95_ms", 6000.0),
    # 4 + 2 tokens inside [0, 10]
    ("output_tok_s", 0.6),
    # admitted - due: 0.5, 0.5 and (never) 12 - 2 = 10
    ("queue_wait_p95_ms.chat", 10000.0),
    # (20 * 2.5 - 10 * 2.0) / 10
    ("decode_lanes_per_step.decode", 3.0),
    # (0.1 + 0.2 + 0.1) / 10 steps, in ms
    ("host_ms_per_step.decode", 40.0),
    ("setup_s", 7.5),
    # no trace and no peak on the CPU: nothing to read
    ("device_idle_share.chat", None),
    ("mfu.chat", None),
    ("ragged_attn_roofline.decode", None),
])
def test_metric_readers(metric, want):
    got = reader(metric)(_Run(_window()))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


SHAPE = flops.Shape(layers=2, d_model=8, d_ff=16, heads=4, kv_heads=2,
                    head_dim=2, vocab=10, dtype="bfloat16")


def test_causal_pairs():
    assert flops.causal_pairs(1, 10) == 10          # a decode token
    assert flops.causal_pairs(4, 4) == 10           # 1 + 2 + 3 + 4
    assert flops.causal_pairs(2, 6) == 11           # 5 + 6


@pytest.mark.parametrize("seqs, pairs, kv_tokens, q_tokens", [
    ([(1, 10)], 10, 10, 1),
    ([(4, 4), (1, 7)], 17, 11, 5),
    ([(2, 6), (3, 3), (1, 1)], 18, 10, 6),
])
def test_attention_counts(seqs, pairs, kv_tokens, q_tokens):
    # 4 FLOPs per (pair, head, dim) per layer
    assert flops.attention_flops(SHAPE, seqs) == 4 * 4 * 2 * pairs * 2
    # K and V of every cached position, q read + o written, bf16, 2 layers
    want = (kv_tokens * 2 * 2 * 2 + q_tokens * 4 * 2 * 2) * 2 * 2
    assert flops.attention_bytes(SHAPE, seqs) == want


def test_step_flops_and_roofline():
    # layer matrices: attn 8*8*2 + 8*4*2 = 192, mlp 3*8*16 = 384 -> 576 x 2
    assert SHAPE.block_params == 1152
    seqs = [(3, 3)]
    want = 2 * 1152 * 3 + flops.attention_flops(SHAPE, seqs) + 2 * 8 * 10
    assert flops.step_flops(SHAPE, seqs, sampled=1) == want
    t, bound = flops.least_time(100.0, 10.0, peak_flops=10.0, peak_bw=10.0)
    assert (t, bound) == (10.0, "compute")
    t, bound = flops.least_time(1.0, 10.0, peak_flops=10.0, peak_bw=2.0)
    assert (t, bound) == (5.0, "memory")


def test_shape_from_config_files():
    import json
    from pathlib import Path

    d = Path(__file__).resolve().parents[1] / "configs"
    q = flops.Shape.from_config(json.loads((d / "qwen2-1.5b.json").read_text()))
    assert (q.layers, q.d_model, q.heads, q.kv_heads, q.head_dim,
            q.vocab) == (28, 1536, 12, 2, 128, 151936)
    s = flops.Shape.from_config(json.loads(
        (d / "smollm-360m.json").read_text()))
    assert (s.layers, s.head_dim, s.d_ff) == (32, 64, 2560)
    # weights as served: about 0.72 GB and 3.1 GB of bf16
    assert math.isclose(flops.weight_bytes(s), 0.72e9, rel_tol=0.03)
    assert math.isclose(flops.weight_bytes(q), 3.09e9, rel_tol=0.03)


def test_peaks_refuse_unknown_device():
    from bench import peaks

    row = peaks.peaks("TPU v5 lite")
    assert peaks.flops_per_s(row, "bfloat16") == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.flops_per_s(row, "float64")
