"""STREAM kernels (paper Alg 1: ADD / SCALE / TRIAD) as Pallas pipelines.

The paper sweeps Gaudi data-access granularity (256 B cliff) and unroll
factor; the TPU analogue is the BlockSpec tile shape: ``block_rows`` rows of
128 lanes per grid step. The benchmark harness sweeps block_rows to expose
the HBM→VMEM pipeline-efficiency curve (the TPU's "access granularity" —
small tiles under-utilize the DMA engine exactly like sub-256 B accesses on
Gaudi; the pipelined grid is the analogue of loop unrolling).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128


def _add_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] + b_ref[...]


def _scale_kernel(s_ref, a_ref, o_ref):
    o_ref[...] = s_ref[0] * a_ref[...]


def _triad_kernel(s_ref, a_ref, b_ref, o_ref):
    o_ref[...] = s_ref[0] * a_ref[...] + b_ref[...]


def _call(kernel, args, rows, block_rows, dtype, n_scalar=0,
          interpret=True):
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANES), lambda i, *_: (i, 0))
    n_in = len(args) - n_scalar
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalar,
        grid=grid,
        in_specs=[spec] * n_in,
        out_specs=spec,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)


def add_pallas(a, b, *, block_rows: int = 256, interpret: bool = True):
    """a, b (rows, 128)."""
    return _call(_add_kernel, (a, b), a.shape[0], block_rows, a.dtype,
                 interpret=interpret)


def scale_pallas(a, scalar, *, block_rows: int = 256, interpret: bool = True):
    s = jnp.asarray([scalar], a.dtype)
    return _call(_scale_kernel, (s, a), a.shape[0], block_rows, a.dtype,
                 n_scalar=1, interpret=interpret)


def triad_pallas(a, b, scalar, *, block_rows: int = 256,
                 interpret: bool = True):
    s = jnp.asarray([scalar], a.dtype)
    return _call(_triad_kernel, (s, a, b), a.shape[0], block_rows, a.dtype,
                 n_scalar=1, interpret=interpret)
