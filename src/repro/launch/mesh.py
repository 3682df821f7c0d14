"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
sets ``xla_force_host_platform_device_count`` before first jax init.

Every mesh here has ``Auto`` axes (``repro.distributed.sharding.auto_mesh``).
"""
from __future__ import annotations

import jax

from repro.distributed.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_smoke_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (tests on 1 CPU device)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return auto_mesh((data, model), ("data", "model"))


def make_serving_mesh(model: int = 0):
    """(1, model) mesh for the sharded serving engine.

    ``model`` is the model-axis device count (``ServeConfig.devices``);
    0 means "all local devices".  An explicit count the host cannot supply
    raises — silently serving on fewer devices than requested would make
    every ``devices=``-attributed number a lie.  The engine TP-shards
    params over ``model`` and sequence-shards the KV pool's block dimension
    over it — the data axis exists (size 1) so ``ShardingRules`` sees its
    usual axis names (docs/sharded_serving.md).
    """
    n = len(jax.devices())
    if model > n:
        raise ValueError(
            f"make_serving_mesh: {model} model-axis devices requested but "
            f"only {n} local device(s) exist (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={model} on CPU hosts)")
    model = n if model <= 0 else model
    return auto_mesh((1, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Batch-sharding axes for a mesh (includes 'pod' when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
