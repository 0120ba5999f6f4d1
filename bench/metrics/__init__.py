"""One reader per per-layer metric, found by the metric's name.

Each module ``bench/metrics/<name>.py`` defines ``read(ctx)``, which
takes the metric from ``ctx`` (a :class:`bench.harness.Readings`: the
reduced trace, the program's counters and the cell's sizes) and returns
a number, or ``None`` when it finds nothing to read; the harness then
leaves the metric out of the result line.
"""

from __future__ import annotations

import importlib


def reader(name: str):
    """The ``read`` function of metric ``name``."""
    return importlib.import_module(f"{__name__}.{name}").read
