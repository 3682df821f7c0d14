"""BatchedTable fused embedding-bag Pallas kernel (paper §4.1, Fig 14b).

ONE kernel launch serves every (table, bag) pair: the concatenated table
lives in HBM; scalar-prefetched *global* row ids (local index + tableOffset,
computed on the host exactly like FBGEMM's BatchedTable) drive the BlockSpec
index_map, so each grid step DMAs one (1, D) embedding row into VMEM and
accumulates it into the bag's VMEM scratch. This is the TPU analogue of the
paper's TPC-C kernel: the per-table launch overhead of SingleTable is gone
and row fetches from *different tables* overlap in the same HBM→VMEM
pipeline (the paper's "chip-wide memory-level parallelism").

Grid (num_bags, L): L (pooling factor) is innermost/sequential so the bag
accumulator persists; bags are parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _embed_kernel(global_ids, row_ref, o_ref, acc_ref, *, pool_l: int):
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += row_ref[...].astype(jnp.float32)

    @pl.when(l == pool_l - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def batched_embedding_pallas(big_table, global_ids, pool_l: int, *,
                             interpret: bool = True):
    """big_table (R, D); global_ids (num_bags * pool_l,) -> (num_bags, D)."""
    R, D = big_table.shape
    num_bags = global_ids.shape[0] // pool_l

    def row_map(b, l, ids):
        return (ids[b * pool_l + l], 0)

    def out_map(b, l, ids):
        return (b, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_bags, pool_l),
        in_specs=[pl.BlockSpec((1, D), row_map)],
        out_specs=pl.BlockSpec((1, D), out_map),
        scratch_shapes=[pltpu.VMEM((1, D), jnp.float32)],
    )
    kernel = functools.partial(_embed_kernel, pool_l=pool_l)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_bags, D), big_table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(global_ids, big_table)
