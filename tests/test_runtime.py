"""Process-level runtime setup: the compile-cache placement and the TPU
probe that must not touch a JAX backend."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import runtime

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_env_var_is_left_to_jax(monkeypatch, config_updates,
                                              tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert config_updates == []            # no directory set in code


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_cache")
    assert runtime.enable_compile_cache() == want
    assert runtime.enable_compile_cache() == want     # same every call
    assert config_updates == [("jax_compilation_cache_dir", want)] * 2
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_tpu_probe_initialises_no_backend():
    """A parent that initialised a JAX backend would hold the chip."""
    r = subprocess.run(
        [sys.executable, "-c",
         "from repro.launch import runtime\n"
         "from jax._src import xla_bridge\n"
         "n = runtime.tpu_chips_attached()\n"
         "assert n >= 0 and not xla_bridge._backends, xla_bridge._backends\n"
         "print('OK')"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": str(ROOT / "src")})
    assert r.stdout.strip() == "OK", r.stderr[-2000:]


def test_device_line():
    line = runtime.device_line()
    assert line.startswith(f"platform={jax.devices()[0].platform} ")
    assert line.endswith(f"count={len(jax.devices())}")
