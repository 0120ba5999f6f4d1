"""Drivers: what one kind of deployment puts through the harness.

A configuration file may name its driver, ``"driver": "<name>"``, which
is the module ``bench/drivers/<name>.py``; without the key it is
``ycsb``.  The harness (:mod:`bench.harness`) owns everything every cell
shares (chips, set-up timing, warm-up, the closed-loop window, the
profiler window, compile counting, the memory peak, the end-to-end
values and the per-layer readers) and asks the driver for the rest.
A driver module defines:

``open_plane(config, seed, devices)``
    What serves the batches: the system under test, built on
    ``devices`` with its data made from ``seed``.
``traffic(config, mix, seed)``
    The batch source of the traffic mix ``mix`` (the parsed
    ``bench/traffic/<name>.json``, which gives the harness
    ``warmup_batches``): ``.r``, the ops one batch submits, and
    ``.next()``, the next batch, the same sequence for the same seed.
``serve(plane, batch)``
    One call of the system on one batch: its result, or ``None`` when
    the system refused the batch.
``record(batch, result)``
    What is kept of a served or refused (``result`` None) batch, for
    the check: a record with ``ops`` (ops it completed), ``rounds``
    (coherence rounds it took) and ``failed`` (it was refused).
``check(config, mix, seed, records, plane)``
    After the window, the comparison with the plain reference over
    every record (warm-up first, in order), with any read-back through
    ``plane``: ``(checks, compared)``, ``checks`` being ``{name:
    {"value": v, "limit": l}}`` and ``compared`` the number of ops it
    compared.
``least_bytes(config, records)``
    The least HBM bytes the served records had to move (the
    ``plane_roofline`` numerator).

Optional, for ``bench.compile_check`` and ``bench.spans``, which skip a
driver without them:

``lower_verb(config, devices)``
    The jitted program one ``serve`` runs, lowered at the cell's
    shapes for ``devices`` (real or described).
``lower_build(config, devices)``
    The jitted state build of ``open_plane``, lowered the same way.

The per-layer readers read the results the driver's ``serve`` returned
in the traced window: ``rounds``; ``host_s`` with ``dispatch``,
``telemetry`` and ``results``; ``telemetry`` with ``latch_requests``,
``latch_failed``, ``local_hits`` and ``served``.  A result without them
leaves those metrics unread for its cell.
"""

from __future__ import annotations

import importlib

DEFAULT = "ycsb"


def driver(name: str | None):
    """The driver module ``name`` (``ycsb`` when ``None``)."""
    return importlib.import_module(f"{__name__}.{name or DEFAULT}")
