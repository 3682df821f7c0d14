"""Traffic mixes: data files under ``bench/traffic/`` read by one generator.

A mix is ``bench/traffic/<name>.json``.  It may name a ``base`` mix whose
keys it extends (a chat mix at another rate is a file of one line).  Its
``kind`` names the arrival process, a module ``bench/traffic/kinds/<kind>.py``
that drives the session; the lengths are drawn here for every kind.

Lengths are stratified: for ``n`` requests, the ``n`` quantiles
``(i + 0.5) / n`` of a log-normal with the mix's median and sigma, rounded
and clipped to the mix's range, in an order shuffled once by a fixed
generator (``ORDER``), as are the stratified arrival gaps.  So every seed
sends the same requests at the same times; the seed draws the token ids
(uniform over the vocabulary) and the weights.  With the order drawn from
the seed too, the chat cell's TTFT median and 95th percentile spread by
37% and 69% across six seeds on the chip, against under 1% between two
runs of one seed: the order, not the seed's inputs, set them.
"""
from __future__ import annotations

import json
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

DIR = Path(__file__).resolve().parent
ORDER = 20_251_017           # the one generator that orders lengths and gaps


def load(name: str, directory: Path = DIR) -> dict:
    """The mix ``name`` with its ``base`` chain merged in (own keys win)."""
    spec = json.loads((Path(directory) / f"{name}.json").read_text())
    base = spec.pop("base", None)
    if base is None:
        return spec
    merged = load(base, directory)
    merged.update(spec)
    return merged


def kind_module(spec: dict, directory: Path = DIR):
    """The arrival-process module the mix's ``kind`` names."""
    from bench import byname

    return byname.load(Path(directory) / "kinds" / f"{spec['kind']}.py",
                       "bench_kind")


def max_positions(spec: dict) -> int:
    """The longest sequence a request of the mix can reach."""
    return int(spec["prompt"]["max"]) + int(spec["output"]["max"])


def stratified(dist: dict, n: int) -> List[int]:
    """``n`` stratified lengths of ``dist`` (median, sigma, min, max), in
    ascending order."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = round(float(dist["median"]) * float(np.exp(dist["sigma"] * z)))
        out.append(int(min(max(v, dist["min"]), dist["max"])))
    return out


def stratified_gaps(n: int, rate: float) -> List[float]:
    """``n`` stratified exponential inter-arrival gaps, scaled so that they
    sum to exactly ``n / rate`` (ascending order)."""
    gaps = [-np.log1p(-(i + 0.5) / n) for i in range(n)]
    scale = n / rate / sum(gaps)
    return [g * scale for g in gaps]


def shuffled(values, *key) -> np.ndarray:
    """``values`` in the fixed order of stream ``key`` (not the seed's)."""
    return np.random.default_rng([ORDER, *key]).permutation(values)


def prompts(rng: np.random.Generator, lengths: List[int],
            vocab: int) -> List[np.ndarray]:
    """Uniform token ids, one prompt per length."""
    return [rng.integers(0, vocab, (n,), dtype=np.int32) for n in lengths]
