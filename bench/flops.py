"""Operations and bytes the served work needs, from shapes alone.

Counted from the configuration file's sizes and from each step's
per-sequence ``(q_len, kv_len)``: ``q_len`` tokens of one sequence were
processed in the step, ending at position ``kv_len`` (so the sequence's
cache holds ``kv_len`` positions after the step).  Nothing here reads the
program's BlockList, pool size or compiled HLO, so the counts are the same
whatever implements the step.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


@dataclass(frozen=True)
class Shape:
    """The sizes of a decoder-only transformer that the counts need."""

    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        """From a configuration file in the Hugging Face ``config.json``
        naming."""
        heads = int(c["num_attention_heads"])
        return cls(layers=int(c["num_hidden_layers"]),
                   d_model=int(c["hidden_size"]),
                   d_ff=int(c["intermediate_size"]),
                   heads=heads,
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c.get("head_dim")
                                or c["hidden_size"] // heads),
                   vocab=int(c["vocab_size"]),
                   dtype=str(c["torch_dtype"]))

    @property
    def itemsize(self) -> int:
        return _ITEMSIZE[self.dtype]

    @property
    def block_params(self) -> int:
        """Matrix parameters of the layer stack (norms and biases left out:
        they cost no matrix FLOPs)."""
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        mlp = 3 * d * self.d_ff
        return self.layers * (attn + mlp)


def causal_pairs(q_len: int, kv_len: int) -> int:
    """(query, key) pairs of ``q_len`` queries ending at ``kv_len``, each
    attending causally to every earlier position and itself."""
    return q_len * kv_len - q_len * (q_len - 1) // 2


def attention_flops(shape: Shape, seqs: Iterable[Tuple[int, int]]) -> float:
    """QK^T and PV FLOPs of all layers for ``seqs`` = [(q_len, kv_len)]."""
    pairs = sum(causal_pairs(q, kv) for q, kv in seqs)
    return 4.0 * shape.heads * shape.head_dim * pairs * shape.layers


def attention_bytes(shape: Shape, seqs: Iterable[Tuple[int, int]]) -> float:
    """Bytes attention must move in all layers: each sequence's whole cache
    (K and V) read once, its queries read and its outputs written once."""
    seqs = list(seqs)
    kv = sum(kv for _, kv in seqs) * shape.kv_heads * shape.head_dim * 2
    qo = sum(q for q, _ in seqs) * shape.heads * shape.head_dim * 2
    return float(kv + qo) * shape.itemsize * shape.layers


def step_flops(shape: Shape, seqs: Iterable[Tuple[int, int]],
               sampled: int) -> float:
    """Model FLOPs of one served step: 2 x the layer stack's parameters per
    token processed, attention over each token's context, and the unembed
    (2 x d_model x vocab) for each of the ``sampled`` tokens the step
    produced.  Rows that no one samples need no unembed."""
    seqs = list(seqs)
    tokens = sum(q for q, _ in seqs)
    return (2.0 * shape.block_params * tokens
            + attention_flops(shape, seqs)
            + 2.0 * shape.d_model * shape.vocab * sampled)


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> Tuple[float, str]:
    """The roofline's least time for the work and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def weight_bytes(shape: Shape) -> float:
    """Bytes of the served weights (layer matrices plus the embedding)."""
    return float(shape.block_params + shape.vocab * shape.d_model) * (
        shape.itemsize)
