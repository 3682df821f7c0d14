"""Load a module of the benchmark from its file, by name.

Configurations, traffic kinds, metric readers, adapters and references are
files whose names come from data (``BENCHMARK.json`` and the files it
names), and some of those names are not Python identifiers
(``qwen2-1.5b``), so they are loaded by path rather than imported.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path


def load(path: Path, prefix: str):
    """The module in ``path``, registered under ``prefix_<stem>``."""
    path = Path(path)
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
