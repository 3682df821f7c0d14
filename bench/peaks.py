"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` (``bench/peaks.json``, with its source).

A device kind that is not in the table is an error, never a default: a
share of a peak computed against another chip's peak is meaningless.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no row in ``peaks.json``."""


def peaks(device_kind: str, path: Path = TABLE) -> dict:
    """The row of ``device_kind``: ``flops_per_s`` by dtype,
    ``hbm_bytes_per_s``, ``hbm_bytes``."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path} "
            f"(known: {sorted(table)})")
    return table[device_kind]


def flops_per_s(row: dict, dtype: str) -> float:
    """Peak FLOP/s of ``row`` for operands of ``dtype``."""
    if dtype not in row["flops_per_s"]:
        raise UnknownDevice(f"no {dtype} peak in {row}")
    return float(row["flops_per_s"][dtype])
