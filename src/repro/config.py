"""Configuration system for the repro framework.

Frozen dataclasses describing models, parallelism, training and serving.
Every assigned architecture lives in ``repro.configs.<id>`` and registers a
``ModelConfig`` under its ``--arch`` id via :func:`register`.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Model-family tags (mirrors the assignment table).
# ---------------------------------------------------------------------------
DENSE = "dense"
MOE = "moe"
SSM = "ssm"
HYBRID = "hybrid"
VLM = "vlm"
AUDIO = "audio"
RECSYS = "recsys"


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts sub-config (token-choice top-k, capacity-based)."""

    num_experts: int
    top_k: int
    d_expert: int                 # per-expert FFN hidden size
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # Shared dense expert ala granite/qwen-moe shared expert (0 disables).
    d_shared_expert: int = 0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2-style SSD sub-config (used by zamba2)."""

    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 128

    @property
    def num_heads_for(self) -> Callable[[int], int]:  # pragma: no cover
        raise AttributeError


@dataclass(frozen=True)
class RWKVConfig:
    """RWKV6 "Finch" sub-config: data-dependent decay time mix."""

    head_size: int = 64
    decay_lora: int = 64          # low-rank dim of the data-dependent decay
    token_shift: bool = True


@dataclass(frozen=True)
class AttentionConfig:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    causal: bool = True
    # Sliding window (0 = full attention).
    window: int = 0


@dataclass(frozen=True)
class ModelConfig:
    """A single architecture. Field names follow the assignment table."""

    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    attention: Optional[AttentionConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    # Hybrid (zamba2): attention block shared across the stack, applied every
    # `hybrid_attn_every` layers.
    hybrid_attn_every: int = 0
    # Encoder-decoder (whisper): encoder depth; num_layers is decoder depth.
    encoder_layers: int = 0
    encoder_seq: int = 0           # fixed encoder sequence (audio frames)
    # VLM: number of vision-stub tokens prepended (internvl).
    vision_tokens: int = 0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    act: str = "silu"              # silu (swiglu) | gelu
    dtype: str = "bfloat16"
    # Max position embeddings are irrelevant for RoPE; kept for reporting.
    max_seq: int = 524_288
    source: str = ""               # provenance string from assignment

    # ---- derived ---------------------------------------------------------
    @property
    def head_dim(self) -> int:
        assert self.attention is not None
        return self.attention.head_dim

    def num_params(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        emb = V * d
        head = 0 if self.tie_embeddings else V * d
        per_layer = 0
        if self.family in (DENSE, MOE, VLM, AUDIO):
            a = self.attention
            per_layer += d * a.num_heads * a.head_dim  # q
            per_layer += 2 * d * a.num_kv_heads * a.head_dim  # k,v
            per_layer += a.num_heads * a.head_dim * d  # o
            if self.moe is not None:
                m = self.moe
                per_layer += d * m.num_experts  # router
                per_layer += m.num_experts * 3 * d * m.d_expert
                if m.d_shared_expert:
                    per_layer += 3 * d * m.d_shared_expert
            else:
                per_layer += 3 * d * self.d_ff  # swiglu
            per_layer += 2 * d  # norms
        elif self.family == SSM:  # rwkv6
            per_layer += 4 * d * d            # r,k,v,o (time mix)
            per_layer += d * self.d_ff + self.d_ff * d + d * d  # channel mix
            per_layer += 2 * d
        elif self.family == HYBRID:  # zamba2: mamba2 blocks + shared attn
            s = self.ssm
            d_inner = s.expand * d
            per_layer += d * (2 * d_inner + 2 * s.d_state + d_inner // s.head_dim)
            per_layer += d_inner * d
            per_layer += 2 * d
            a = self.attention
            shared_attn = (
                d * a.num_heads * a.head_dim
                + 2 * d * a.num_kv_heads * a.head_dim
                + a.num_heads * a.head_dim * d
                + 3 * d * self.d_ff
            )
            return emb + head + L * per_layer + shared_attn
        total = emb + head + L * per_layer
        if self.encoder_layers:  # whisper encoder (self-attn + mlp, gelu: 2 mats)
            a = self.attention
            enc_layer = (
                d * a.num_heads * a.head_dim
                + 2 * d * a.num_kv_heads * a.head_dim
                + a.num_heads * a.head_dim * d
                + 2 * d * self.d_ff
                + 2 * d
            )
            # decoder cross-attention adds another attention block per layer
            total += self.encoder_layers * enc_layer
            total += self.num_layers * (
                d * a.num_heads * a.head_dim
                + 2 * d * a.num_kv_heads * a.head_dim
                + a.num_heads * a.head_dim * d
            )
        return total

    def num_active_params(self) -> int:
        """Active (per-token) params — differs from num_params for MoE."""
        if self.moe is None:
            return self.num_params()
        m = self.moe
        d, L = self.d_model, self.num_layers
        dense_total = self.num_params()
        all_experts = L * m.num_experts * 3 * d * m.d_expert
        active_experts = L * m.top_k * 3 * d * m.d_expert
        return dense_total - all_experts + active_experts

    @property
    def depth_units(self) -> int:
        """Repeating-unit count (layers; groups for hybrid)."""
        if self.family == HYBRID:
            return self.num_layers // self.hybrid_attn_every
        return self.num_layers

    def with_depth(self, units: int) -> "ModelConfig":
        """Same width, reduced depth — used by roofline cost probes."""
        if self.family == HYBRID:
            return dataclasses.replace(
                self, num_layers=self.hybrid_attn_every * units)
        if self.encoder_layers:
            return dataclasses.replace(self, num_layers=units,
                                       encoder_layers=units)
        return dataclasses.replace(self, num_layers=units)

    def reduced(self, **overrides: Any) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        small: Dict[str, Any] = dict(
            num_layers=2,
            d_model=64,
            d_ff=128,
            vocab_size=256,
        )
        if self.attention is not None:
            ah = self.attention
            ratio = max(1, ah.num_heads // max(1, ah.num_kv_heads))
            kv = max(1, 4 // ratio)
            small["attention"] = dataclasses.replace(
                ah, num_heads=kv * ratio, num_kv_heads=kv, head_dim=16
            )
        if self.moe is not None:
            small["moe"] = dataclasses.replace(
                self.moe, num_experts=4, top_k=2, d_expert=32,
                d_shared_expert=32 if self.moe.d_shared_expert else 0,
            )
        if self.ssm is not None:
            small["ssm"] = dataclasses.replace(
                self.ssm, d_state=16, head_dim=16, chunk_size=16
            )
        if self.rwkv is not None:
            small["rwkv"] = dataclasses.replace(self.rwkv, head_size=16, decay_lora=8)
        if self.encoder_layers:
            small["encoder_layers"] = 2
            small["encoder_seq"] = 16
        if self.vision_tokens:
            small["vision_tokens"] = 8
        if self.hybrid_attn_every:
            small["hybrid_attn_every"] = 2
        small.update(overrides)
        return dataclasses.replace(self, **small)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)


@dataclass(frozen=True)
class DLRMConfig:
    """DLRM-DCNv2 config (paper Table 3, RM1/RM2)."""

    name: str
    num_tables: int
    num_embeddings: int            # rows per table
    embedding_dim: int             # vector width (bytes swept in benchmarks)
    gathers_per_table: int         # pooling factor (bag size)
    bottom_mlp: Tuple[int, ...]
    top_mlp: Tuple[int, ...]
    cross_rank: int                # DCNv2 low-rank dim
    cross_layers: int
    dense_features: int = 13
    family: str = RECSYS

    def num_params(self) -> int:
        emb = self.num_tables * self.num_embeddings * self.embedding_dim
        mlp = 0
        dims = (self.dense_features,) + self.bottom_mlp
        for a, b in zip(dims[:-1], dims[1:]):
            mlp += a * b + b
        # DCNv2 interaction input: concat([bottom_out, emb_1..emb_T])
        inter_in = self.bottom_mlp[-1] + self.num_tables * self.embedding_dim
        dims = (inter_in,) + self.top_mlp
        for a, b in zip(dims[:-1], dims[1:]):
            mlp += a * b + b
        cross = self.cross_layers * 2 * inter_in * self.cross_rank
        return emb + mlp + cross


# ---------------------------------------------------------------------------
# Input-shape cells (assignment: 4 shapes per LM arch).
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str      # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class ParallelConfig:
    """How a step is laid out on the mesh."""

    data_axis: Tuple[str, ...] = ("pod", "data")
    model_axis: str = "model"
    fsdp_axis: Optional[str] = "data"       # param sharding over data (FSDP)
    expert_axis: Optional[str] = "model"    # expert-parallel axis
    remat: str = "full"                     # none | full | dots
    scan_layers: bool = True
    # Beyond-paper knobs (hillclimbed in EXPERIMENTS.md §Perf):
    seq_shard_long: bool = True             # SP for long-context SSM scan
    compress_grads: bool = False            # int8 all-reduce w/ error feedback


@dataclass(frozen=True)
class TrainConfig:
    model: str
    shape: str = "train_4k"
    lr: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    warmup_steps: int = 100
    total_steps: int = 1000
    grad_clip: float = 1.0
    seed: int = 0
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    checkpoint_every: int = 200
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3


@dataclass(frozen=True)
class ServeConfig:
    model: str
    shape: str = "decode_32k"
    kv_block_size: int = 128       # tokens per paged KV block
    max_blocks: int = 0            # 0 = derived from shape
    max_batch: int = 128
    max_new_tokens: int = 128
    prefill_chunk: int = 2048
    use_block_list: bool = True    # paper technique ON (False = padded baseline)
    # Operator-backend preference for registry-dispatched ops (the config
    # level of repro.core.dispatch precedence: overridden by explicit args,
    # force_backend scopes and REPRO_BACKEND; a registered backend that
    # can't serve this platform/call raises BackendUnavailableError).
    backend: str = "auto"          # auto | ref | xla | pallas | pallas_interpret
    # Serving-policy preferences (the config level of repro.serving.policy
    # precedence: overridden by explicit ctor args and force_policies scopes;
    # names are validated strictly — there is no capability fallback).
    # "auto" on any axis delegates to the per-scenario winner measured in the
    # committed perf table (repro.perf, docs/perf_gate.md);
    # "predicted-length" admission ranks by a trace-learned decode estimate.
    admission: str = "fcfs"        # fcfs | priority | deadline-slo |
    #                                predicted-length | auto
    preemption: str = "latest-arrival"   # | fewest-remaining-tokens |
    #                                      most-blocks | auto
    eviction: str = "lru"          # lru | hit-rate | refcount-aware |
    #                                tiered | auto
    # Speculative decoding (repro.serving.spec): proposer name resolved
    # through the spec registry ("off" = one token per request per step),
    # and the max draft tokens verified per request per step.
    spec: str = "off"              # off | ngram | draft-model
    spec_k: int = 4
    # Async overlapped engine loop (docs/async_engine.md): step N+1's host
    # work (propose/schedule/render) runs while step N's fused program is
    # still on device; commit happens when the device future resolves.
    # Greedy streams are bit-identical overlap on vs off.
    overlap: bool = False
    # KV-page DMA ring depth for the Pallas chunked-attention kernel
    # (0/1 = BlockSpec pipeline, >= 2 = multi-buffered manual DMA —
    # `prefetch_depth` tunable of the paged_attention_chunked op family).
    prefetch_depth: int = 0
    # Query-chunk tile rows for the chunked paged-attention kernel
    # (`q_chunk` tunable of the paged_attention_chunked op family).
    q_chunk: int = 16
    # Which attention op family the fused step dispatches per layer:
    # "ragged" = paged_attention_ragged (ONE launch for prefill chunks +
    # decode lanes via cu_q_lens/cu_kv_lens metadata over the fused KV
    # pool), "chunked" = the PR-6 token-lane path on
    # split views of the same pool.  Greedy streams are bit-identical.
    attn_impl: str = "ragged"      # ragged | chunked
    # Ragged-kernel tunables (paged_attention_ragged op family,
    # docs/ragged_kernel.md). 0 = consult the committed autotune table
    # (BENCH_010.json via repro.perf.autotune, counted tuned_resolved /
    # tuned_fallback), falling back to the registry defaults; > 0 pins the
    # value explicitly.
    num_queries_per_block: int = 0   # query-tile rows per ragged grid step
    num_kv_pages_per_block: int = 0  # fused KV pages per ragged grid step
    vmem_limit_bytes: int = 0        # VMEM cap for the fused-page DMA ring
    # Mesh-native serving (docs/sharded_serving.md): device count of the
    # serving mesh's model axis. 0/1 = single-device engine; > 1 makes
    # ``repro.launch.serve`` build a mesh (repro.launch.mesh) and the engine
    # run the sharded fused step — params TP-sharded, KV pool
    # sequence-sharded, per-layer log-sum-exp combine over the axis.
    devices: int = 0
    # Disaggregated serving (docs/disaggregated.md): "" = monolithic engine;
    # "prefill,decode" (alias "split") makes ``repro.launch.serve`` build the
    # two-role DisaggEngine — prompts prefill on one engine, committed KV
    # blocks hand off through the allocator's reserve/commit API, decode runs
    # on the other. Greedy streams stay bit-identical to the monolithic
    # engine.
    roles: str = ""
    # Host-memory KV tier capacity in blocks (0 = HBM-only): cached-free
    # blocks evicted from the HBM pool demote into a host LRU instead of
    # dropping their content (gated by the eviction policy's `demote` hook —
    # the `tiered` policy scores it on BlockStats) and promote back into HBM
    # on a prefix hit.
    host_blocks: int = 0
    # Runtime sanitizers (docs/static_analysis.md, repro.analysis.sanitize):
    # retrace guard on the engine step loop, host-sync guard around the
    # overlap build half (allowlisted: disagg-handoff, tier-drain), and
    # BlockAllocator.check_invariants after every commit.  Counters surface
    # in metrics() as sanitize.*; violations raise SanitizeError.
    sanitize: bool = False
    # Trace replay (repro.perf, docs/perf_gate.md): path to a Trace JSON the
    # launcher replays in deterministic virtual time instead of the synthetic
    # workload ("" = synthetic).  The trace's scenario keys the `auto`
    # triple's perf-table lookup and its history fits the predicted-length
    # cost model.
    trace: str = ""
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    seed: int = 0


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Any] = {}


def register(cfg: Any) -> Any:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> Any:
    if not _REGISTRY:
        _load_all()
    if name not in _REGISTRY:
        _load_all()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown arch {name!r}; known: {sorted(_REGISTRY)}"
        ) from None


def list_configs() -> Sequence[str]:
    _load_all()
    return sorted(_REGISTRY)


def _load_all() -> None:
    import repro.configs  # noqa: F401  (import side effect registers all)
