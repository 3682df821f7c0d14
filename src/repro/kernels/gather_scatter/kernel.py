"""GUPS-style random vector gather / scatter Pallas kernels (paper Fig 9).

Vector width D is the swept parameter: on Gaudi the cliff is at 256 B
(minimum access granularity); on TPU the analogous cliff is the (8, 128)
tile — a D < 128·dtype row still moves a full lane tile HBM→VMEM, wasting
bandwidth in exactly the way the paper measures for sub-256 B vectors.
Scalar-prefetched indices drive the BlockSpec index_map (the gather/scatter
never touches rows it doesn't need).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _gather_kernel(idx, row_ref, o_ref):
    o_ref[...] = row_ref[...]


def _scatter_kernel(idx, src_ref, tbl_ref, o_ref):
    del tbl_ref  # present only as the aliased output buffer
    o_ref[...] = src_ref[...]


def gather_pallas(table, idx, *, interpret: bool = True):
    """table (R, D); idx (N,) -> (N, D)."""
    R, D = table.shape
    N = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N,),
        in_specs=[pl.BlockSpec((1, D), lambda i, ids: (ids[i], 0))],
        out_specs=pl.BlockSpec((1, D), lambda i, ids: (i, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, D), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, table)


def scatter_pallas(table, idx, src, *, interpret: bool = True):
    """Write src (N, D) rows into table (R, D) at idx (N,). Last write wins."""
    R, D = table.shape
    N = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N,),
        in_specs=[pl.BlockSpec((1, D), lambda i, ids: (i, 0)),
                  pl.BlockSpec((1, D), lambda i, ids: (ids[i], 0))],
        out_specs=pl.BlockSpec((1, D), lambda i, ids: (ids[i], 0)),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), table.dtype),
        input_output_aliases={2: 0},     # table buffer updated in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, src, table)
