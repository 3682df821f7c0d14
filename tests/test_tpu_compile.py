"""The paged-attention Pallas kernels compile for a TPU v5e chip.

Interpret mode accepts layouts the TPU compiler (Mosaic) refuses, so these
tests lower the kernels with ``interpret=False`` for a DESCRIBED v5e chip
(nothing runs, no chip needed) at the served model's head shapes
(smollm-360m: 15 query heads, 5 KV heads, head_dim 64, page 16, bf16) and
check the compiled program holds the kernel.  The topology is described
only inside a fixture: the TPU library may be loaded by one process at a
time, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import (
    paged_attention_chunked_pallas, paged_attention_pallas,
    paged_attention_ragged_pallas)

BS, NB, T, TB, B = 16, 64, 64, 32, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # Unless told otherwise, the TPU library writes its logs under the
    # system's temporary directory when it loads.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiles_kernel(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _ragged_shapes(H, KV, hd):
    i32 = jnp.int32
    return [((T, H, hd), jnp.bfloat16), ((NB, KV, BS, 2 * hd), jnp.bfloat16),
            ((TB,), i32), ((TB,), i32), ((TB,), i32), ((B + 1,), i32),
            ((B + 1,), i32), ((B,), i32)]


@pytest.mark.parametrize("H,KV,hd,pages", [
    (15, 5, 64, 1),        # smollm-360m, one page per grid step
    (15, 5, 64, 4),        # four pages per grid step
    (32, 8, 128, 2),       # a head_dim-128 model (llama-3.1-8b heads)
])
def test_ragged_kernel_compiles_for_v5e(one_chip, H, KV, hd, pages):
    def fn(*a):
        return paged_attention_ragged_pallas(
            *a, num_kv_pages_per_block=pages, interpret=False)
    _compiles_kernel(one_chip, fn, *_ragged_shapes(H, KV, hd))


@pytest.mark.parametrize("H,KV,hd", [(15, 5, 64), (12, 2, 128)])
@pytest.mark.parametrize("lanes", [32, 4096])
def test_ragged_walk_compiles_at_served_sizes(one_chip, H, KV, hd, lanes):
    # smollm-360m's and qwen2-1.5b's heads, a decode step's 32 lanes and the
    # largest prefill bucket, over a served pool and a BlockList of its size
    i32, bf16, nb, slots = jnp.int32, jnp.bfloat16, 3072, 32
    _compiles_kernel(
        one_chip,
        lambda *a: paged_attention_ragged_pallas(*a, interpret=False),
        ((lanes, H, hd), bf16), ((nb, KV, BS, 2 * hd), bf16), ((nb,), i32),
        ((nb,), i32), ((nb,), i32), ((slots + 1,), i32),
        ((slots + 1,), i32), ((slots,), i32))


def test_chunked_and_decode_kernels_compile_for_v5e(one_chip):
    H, KV, hd, i32, bf16 = 15, 5, 64, jnp.int32, jnp.bfloat16
    pool = ((NB, BS, KV, hd), bf16)
    _compiles_kernel(
        one_chip,
        lambda *a: paged_attention_chunked_pallas(*a, interpret=False),
        ((T, H, hd), bf16), pool, pool, ((TB,), i32), ((TB,), i32),
        ((TB,), i32), ((B,), i32), ((T,), i32), ((T,), i32))
    _compiles_kernel(
        one_chip, lambda *a: paged_attention_pallas(*a, interpret=False),
        ((B, H, hd), bf16), pool, pool, ((TB,), i32), ((TB,), i32),
        ((TB,), i32), ((B,), i32))
