"""``chip_smoke.py``'s phases at ``.reduced()`` size on the CPU.

The script itself runs on a TPU only; these tests call its phase functions
with the Pallas kernel in interpret mode, so the script cannot rot between
chip runs, and check that it refuses a host without a TPU.
"""
import importlib.util
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import run_multidevice
from repro.config import get_config

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reduced(smoke):
    cfg = get_config(smoke.ARCH).reduced()
    model, params = smoke.build(cfg, seed=0)
    return cfg, model, params


def test_device_phase_refuses_cpu(smoke):
    assert smoke.check_device("cpu")["platform"] == "cpu"
    with pytest.raises(SystemExit):
        smoke.check_device("tpu")


def test_script_exits_nonzero_without_tpu():
    r = subprocess.run([sys.executable, str(SMOKE)], capture_output=True,
                       text=True, timeout=300, cwd=ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "not 'tpu'" in r.stderr


def test_kernel_phase_reduced(smoke, reduced):
    cfg, _, _ = reduced
    err = smoke.check_kernel(cfg, "pallas_interpret", seed=0)
    assert 0 <= err <= smoke.KERNEL_RTOL * 10


def test_engine_phase_reduced(smoke, reduced):
    cfg, model, params = reduced
    outs = smoke.check_engine(cfg, model, params, "pallas_interpret",
                              prompt_lens=(40, 24, 33), max_new=4,
                              logit_prompts=(32, 32))
    assert sorted(outs) == [0, 1, 2]
    assert all(len(o) == 4 for o in outs.values())


def test_mesh_phase_reduced():
    snippet = """
    import chip_smoke as smoke
    from repro.config import get_config
    cfg = get_config(smoke.ARCH).reduced()
    model, params = smoke.build(cfg, seed=0)
    agree = smoke.check_mesh(cfg, model, params, 2, "pallas_interpret",
                             prompt_lens=(40, 24), max_new=3,
                             logit_prompts=(16, 16))
    assert 0.0 <= agree <= 1.0
    print("OK")
    """
    r = run_multidevice(snippet, n_devices=2)
    assert "OK" in r.stdout, (r.stdout[-1500:], r.stderr[-2500:])
