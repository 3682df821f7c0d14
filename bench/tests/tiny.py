"""A copy of the benchmark with one tiny configuration and tiny traffic
mixes, added as files and entries the way a later change adds a cell, for
driving whole runs on the CPU."""
from __future__ import annotations

import copy
import json
import os
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "model_type": "llama", "attention_bias": False, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 6, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16", "vocab_size": 256,
    "bench": {"program_arch": "smollm-360m", "adapter": "decoder",
              "reference": "decoder",
              "serve": {"block_size": 8, "max_batch": 4,
                        "prefill_chunk": 32, "attn_impl": "ragged"},
              "limits": {"served_logit_gap": 0.1}},
}
TINY_CHAT = {"kind": "open_loop", "rate_per_s": 6.0, "drain_s": 60,
             "prompt": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
             "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 10}}
TINY_DECODE = {"kind": "closed_loop", "clients": 4,
               "prompt": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
               "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 12}}


def make_tree(dest: Path, config: dict = TINY_CONFIG) -> Path:
    """``dest`` holds ``bench/``, ``BENCHMARK.json`` with the cells
    ``tiny.chat`` and ``tiny.decode`` added, and a link to ``src/``."""
    dest = Path(dest)
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "src", dest / "src")
    (dest / "bench/configs/tiny.json").write_text(json.dumps(config))
    (dest / "bench/traffic/tiny-chat.json").write_text(json.dumps(TINY_CHAT))
    (dest / "bench/traffic/tiny-decode.json").write_text(
        json.dumps(TINY_DECODE))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec = copy.deepcopy(spec)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "CPU test"})
    for t in ("chat", "decode"):
        spec["workloads"].append({"name": f"tiny.{t}", "config": "tiny",
                                  "traffic": f"tiny-{t}", "chips": 1,
                                  "why": "CPU test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            fam = m["workloads"][0].split(".")[-1]
            m["workloads"].append(f"tiny.{fam}")
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dest


def run_snippet(tree: Path, body: str, timeout: int = 900):
    """Run ``body`` in a fresh CPU process with ``tree`` first on the path;
    ``run`` is ``bench.run`` of the tree."""
    import subprocess
    import sys
    import textwrap

    code = ("import sys\n"
            f"sys.path[:0] = [{str(tree)!r}, {str(tree / 'src')!r}]\n"
            "from bench import run\n" + textwrap.dedent(body))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", code], cwd=tree, env=env,
                          capture_output=True, text=True, timeout=timeout)


def cpu_run(tree: Path, workload: str, seed: int = 5, seconds: float = 2.0,
            trace: int = 0, before: str = ""):
    """One whole run of ``workload`` on the CPU (no chip check, kernels in
    interpret mode); returns (exit code, result or None, stderr)."""
    body = before + f"""
sys.exit(run.main(["--workload", {workload!r}, "--seed", "{seed}",
                   "--seconds", "{seconds}", "--trace", "{trace}"],
                  require_tpu=False, backend="pallas_interpret"))
"""
    p = run_snippet(tree, body)
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    result = json.loads(last) if last.startswith("{") else None
    return p.returncode, result, p.stderr
