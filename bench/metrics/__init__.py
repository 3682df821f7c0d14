"""Metric readers, one module per metric, found by name.

A metric named ``<reader>`` or ``<reader>.<suffix>`` is read by
``bench/metrics/<reader>.py``, whose ``read(run)`` returns the number in
the metric's unit, or None when the run holds nothing to read (the harness
then leaves the metric out of the result).  ``run`` is the
:class:`bench.run.Run` of one run: its window, set-up time, model shape,
peaks and, in a traced run, the reduced trace.
"""
from __future__ import annotations

from pathlib import Path

from bench import byname

DIR = Path(__file__).resolve().parent


def reader(metric: str, directory: Path = DIR):
    """The ``read`` function of ``metric``."""
    path = Path(directory) / f"{metric.split('.')[0]}.py"
    return byname.load(path, "bench_metric").read
