"""Flash attention Pallas TPU kernel (prefill/train path).

Grid (B, H, nq, nk); nk is the innermost (sequential) dimension so the VMEM
scratch accumulators (acc, m, l) persist across KV blocks of one (b, h, iq)
tile — the canonical TPU online-softmax pipeline. Block shapes are explicit
BlockSpecs: q/o tiles (1, bq, 1, hd), k/v tiles (1, bk, 1, hd); with
bq=bk=512, hd=128 the working set is ≈ 0.8 MB << 16 MB VMEM, leaving room
for double buffering of the HBM→VMEM stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  sm_scale: float, causal: bool, bq: int, bk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, :, 0, :]
    k = k_ref[0, :, 0, :]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if causal:
        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = rows >= cols
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    l_new = l_ref[:, 0] * corr + p.sum(axis=-1)
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, :, 0, :],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[:, 0] = m_new
    l_ref[:, 0] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, :, 0, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, sm_scale=None,
                           bq: int = 512, bk: int = 512,
                           interpret: bool = True):
    """q (B,S,H,hd); k,v (B,S,KV,hd) -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = float(sm_scale if sm_scale is not None else hd ** -0.5)
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    grid = (B, H, S // bq, S // bk)

    kernel = functools.partial(_flash_kernel, sm_scale=scale, causal=causal,
                               bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, hd), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, i, j: (b, j, h // G, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, i, j: (b, j, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, hd), lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
