"""GPipe-style pipeline parallelism over shard_map + collective_permute.

For depth-dominated models (qwen3-moe's 94 layers) at >512 chips, PP trades
the per-layer FSDP all-gathers for point-to-point boundary transfers. This
module implements the schedule as a pure function so it composes with the
GSPMD layers INSIDE each stage:

  * stage s owns layers [s·L/S, (s+1)·L/S);
  * the loop runs S + M - 1 ticks (M microbatches); at each tick a stage
    processes one microbatch and `collective_permute`s its boundary
    activation to the next stage — compute and the permute overlap since
    the permute of microbatch m is independent of compute on m+1;
  * bubble fraction = (S-1)/(S+M-1), reported by :func:`bubble_fraction`.

Used by ``examples/pipeline_train.py`` and unit-tested against the
unpipelined reference (identical outputs).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_stages + num_microbatches - 1)


def pipeline_forward(layer_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                     stage_params: Any, x_micro: jnp.ndarray, *,
                     axis: str, num_stages: int) -> jnp.ndarray:
    """Run microbatched pipeline forward inside shard_map.

    layer_fn(stage_params, x) applies THIS stage's layers. x_micro
    (M, mb, ...) microbatches, already sharded so each stage rank holds the
    full microbatch set (stage 0 feeds real data; later stages receive via
    permute). Returns (M, mb, ...) outputs valid on the LAST stage.
    """
    stage = jax.lax.axis_index(axis)
    M = x_micro.shape[0]
    S = num_stages
    perm = [(i, i + 1) for i in range(S - 1)]

    def tick(carry, t):
        buf, outs = carry                    # buf: activation in flight here
        # stage 0 injects microbatch t; other stages use the permuted buffer
        inject = jax.lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, M - 1), keepdims=False)
        cur = jnp.where(stage == 0, inject, buf)
        y = layer_fn(stage_params, cur)
        # microbatch id at this stage this tick; invalid ids compute garbage
        # that is never stored (warm-up / drain bubbles)
        mid = t - stage
        valid = (mid >= 0) & (mid < M) & (stage == S - 1)
        upd = jax.lax.dynamic_update_index_in_dim(
            outs, y, jnp.clip(mid, 0, M - 1), 0)
        outs = jnp.where(valid, upd, outs)
        nxt = jax.lax.ppermute(y, axis, perm)
        return (nxt, outs), None

    # carries become device-varying through ppermute; mark them as such
    buf0 = jax.lax.pcast(jnp.zeros_like(x_micro[0]), (axis,), to="varying")
    outs0 = jax.lax.pcast(jnp.zeros_like(x_micro), (axis,), to="varying")
    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(S + M - 1))
    # broadcast the last stage's outputs to every rank (replicated result);
    # a production loss would instead consume outs on the last stage only
    return jax.lax.psum(jnp.where(stage == S - 1, outs, 0), axis)
