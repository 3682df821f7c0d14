"""Sharding rules: param/batch/cache PartitionSpecs for every model family.

Strategy (DESIGN.md §4):
  * TP over ``model``: column weights (in→hidden) shard the output dim;
    row weights (hidden→out) shard the input dim; vocab shards over model.
  * FSDP over ``data``: the *other* matmul dim.
  * MoE EP: expert dim over ``model``; expert matrices additionally FSDP on
    d_model.
  * DP over ``pod`` (+ optionally FSDP over ('data','pod') — hillclimb knob).
  * Every rule degrades gracefully: a dim that doesn't divide the axis size
    is left unsharded (e.g. granite's vocab 49155 on 16-way model).

Rules are name+shape driven so one walker serves all seven model families.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def auto_mesh(shape, axes, **kw):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    Every mesh in this repo is built here: its code leaves the partitioning
    of ordinary array code to GSPMD and indexes sharded results freely,
    which ``jax.make_mesh``'s default ``Explicit`` axes forbid.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


# weight-name classification
_COL = {"wq", "wk", "wv", "w_gate", "w_up", "w_in", "in_proj", "wr", "w1",
        "u", "router"}          # shard OUTPUT (last) dim over model
_ROW = {"wo", "w_down", "w_out", "out_proj", "wv_cm", "w2", "v"}
# rwkv channel-mix wv is hidden->d (row); plain dict key is "wv" inside "cm".


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _fits(dim: int, mesh: Mesh, axis) -> bool:
    s = _axis_size(mesh, axis)
    return s > 1 and dim % s == 0


class ShardingRules:
    def __init__(self, mesh: Mesh, *, fsdp_axis="data", model_axis="model",
                 fsdp_over_pod: bool = False,
                 head_dim: Optional[int] = None):
        """``head_dim``: attention head width, when the caller knows it.

        With it set, attention projections (wq/wk/wv output, wo input) are
        TP-sharded only on whole-head boundaries — the standard Megatron
        constraint. Sub-head TP shards are never useful (RoPE and softmax
        need the full head together, so XLA reshards before attention
        anyway) and sharding a fraction of a head across ``model`` inside a
        scan-over-layers body miscompiles under jax 0.4.37's GSPMD
        partitioner: the sharded forward silently diverges from the
        single-device result by ~0.6% (bisected in test_distributed —
        identical math unrolled or applied outside lax.scan is exact).
        ``None`` preserves the raw divisibility rule for callers that don't
        know the attention geometry.
        """
        self.mesh = mesh
        names = mesh.axis_names
        self.model = model_axis if model_axis in names else None
        fsdp = fsdp_axis if fsdp_axis in names else None
        if fsdp_over_pod and "pod" in names and fsdp is not None:
            fsdp = ("pod", fsdp)
        self.fsdp = fsdp
        self.dp = tuple(a for a in ("pod", "data") if a in names) or None
        self.head_dim = head_dim

    def _head_granular(self, d: int) -> bool:
        """Would sharding ``d`` over ``model`` keep whole heads per shard?"""
        if self.head_dim is None or self.head_dim <= 0:
            return True
        if d % self.head_dim != 0:
            return False
        return (d // self.head_dim) % _axis_size(self.mesh, self.model) == 0

    # ----------------------------------------------------------------- params
    def param_spec(self, path: Tuple[str, ...], shape) -> P:
        """Spec for one parameter given its tree path and shape."""
        name = path[-1]
        parent = path[-2] if len(path) > 1 else ""
        nd = len(shape)
        none = (None,) * nd

        def spec(*entries):
            # pad leading dims (layer stacking) with None
            return P(*(none[:nd - len(entries)] + tuple(entries)))

        def m_if(d, heads=False):
            if not (self.model and _fits(d, self.mesh, self.model)):
                return None
            if heads and not self._head_granular(d):
                return None
            return self.model

        def f_if(d):
            return self.fsdp if self.fsdp and _fits(d, self.mesh, self.fsdp) else None

        if name == "table":
            # Vocab-parallel embedding/head (Megatron): vocab→model, d
            # REPLICATED. Sharding d over fsdp makes the unembed einsum emit
            # a partial-sum all-reduce of the full (B,S,V) logits (≈200 GB
            # for 4k×152k) — measured in the first dry-run iteration.
            return P(m_if(shape[0]), None)
        # MoE expert tensors: (..., E, D, F) or (..., E, F, D)
        if _is_moe_path(path) and name in ("w_gate", "w_up"):
            E, D, F = shape[-3:]
            return spec(m_if(E), f_if(D), None)
        if _is_moe_path(path) and name == "w_down":
            E, F, D = shape[-3:]
            return spec(m_if(E), None, f_if(D))
        if _is_moe_path(path) and name == "router":
            D, E = shape[-2:]
            return spec(f_if(D), None)
        if nd >= 2 and name in _ROW:
            din, dout = shape[-2:]
            return spec(m_if(din, heads=name == "wo"), f_if(dout))
        if nd >= 2 and name in _COL:
            din, dout = shape[-2:]
            return spec(f_if(din), m_if(dout, heads=name in ("wq", "wk", "wv")))
        if nd >= 2 and name == "conv_w":         # (…, K, conv_dim)
            return spec(None, m_if(shape[-1]))
        if nd >= 2 and name in ("w", ):          # dlrm mlp
            din, dout = shape[-2:]
            return spec(f_if(din), m_if(dout))
        if name == "embedding":                  # dlrm big table: rows→model
            return P(m_if(shape[0]), None)
        return P(*none)                          # norms, biases, scalars

    def params_tree(self, params_shape):
        """PartitionSpec pytree matching a params (shape) pytree."""
        def walk(path, leaf):
            keys = tuple(_key_name(p) for p in path)
            return self.param_spec(keys, leaf.shape)
        return jax.tree_util.tree_map_with_path(walk, params_shape)

    def named(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    # ------------------------------------------------------------- batch/cache
    def batch_spec(self, shape) -> P:
        """Batch arrays: dim0 over data axes when divisible."""
        b = shape[0]
        dp = self.dp if self.dp and b % _axis_size(self.mesh, self.dp) == 0 else None
        return P(dp, *(None,) * (len(shape) - 1))

    def batch_tree(self, specs):
        return jax.tree.map(lambda s: self.batch_spec(s.shape), specs)

    def cache_spec(self, path: Tuple[str, ...], shape) -> P:
        """Decode caches. Contiguous KV (L,B,S,KV,HD): B→dp, S→model
        (sequence-sharded flash-decoding). States: B→dp, heads→model."""
        name = path[-1]
        nd = len(shape)

        def m_if(d):
            return self.model if self.model and _fits(d, self.mesh, self.model) else None

        def dp_if(d):
            return self.dp if self.dp and d % _axis_size(self.mesh, self.dp) == 0 else None

        if name in ("k", "v", "xk", "xv") and nd == 5:    # (L,B,S,KV,HD)
            return P(None, dp_if(shape[1]), m_if(shape[2]), None, None)
        if name == "seq_lens":
            return P(dp_if(shape[0]))
        if name == "S" and nd == 5:                        # rwkv (L,B,H,N,N)
            return P(None, dp_if(shape[1]), m_if(shape[2]), None, None)
        if name in ("tm_shift", "cm_shift") and nd == 3:   # (L,B,D)
            return P(None, dp_if(shape[1]), m_if(shape[2]))
        if name == "conv" and nd == 5:                     # (G,PG,B,K,convd)
            return P(None, None, dp_if(shape[2]), None, m_if(shape[4]))
        if name == "h" and nd == 6:                        # (G,PG,B,H,hd,N)
            return P(None, None, dp_if(shape[2]), m_if(shape[3]), None, None)
        return P(*(None,) * nd)

    def cache_tree(self, cache_shape):
        def walk(path, leaf):
            keys = tuple(_key_name(p) for p in path)
            return self.cache_spec(keys, leaf.shape)
        return jax.tree_util.tree_map_with_path(walk, cache_shape)


def _key_name(entry) -> str:
    if hasattr(entry, "key"):
        return str(entry.key)
    if hasattr(entry, "idx"):
        return str(entry.idx)
    return str(entry)


def _is_moe_path(path: Tuple[str, ...]) -> bool:
    # shared-expert weights are plain dense mats, not (E, ., .) stacks
    return "moe" in path and "shared" not in path
