"""Process-level runtime setup shared by the entry points.

* :func:`enable_compile_cache` places JAX's persistent compilation cache.
  A cold run of the 32-layer serving step compiles one program per
  token-lane bucket; with the cache, a second process (or a second run on
  the same machine) reads them back instead.
* :func:`tpu_chips_attached` counts TPU chips from the PCI bus WITHOUT
  initialising a JAX backend — a parent that initialises one holds the
  chip, and a child that needs it then fails or hangs.
* :func:`device_line` names the devices a run used, for its output.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# Fixed, in the checkout: a cache directory that moves between runs is never
# found again.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache goes to ``.jax_cache/``
    at the checkout root.
    """
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def tpu_chips_attached() -> int:
    """Number of TPU chips on this host's PCI bus (0 on other hosts).

    Reads ``/sys`` only (JAX's own probe), so it never initialises a JAX
    backend.
    """
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def device_line() -> str:
    """``platform=tpu kind=TPU v5 lite count=1`` for the devices JAX found."""
    devs = jax.devices()
    return (f"platform={devs[0].platform} kind={devs[0].device_kind} "
            f"count={len(devs)}")
