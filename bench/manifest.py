"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell names its configuration and its traffic mix; the configuration's
entry names its file, which may name its driver (``"driver"``, the
module ``bench/drivers/<driver>.py``; ``ycsb`` without the key), the
mix is ``bench/traffic/<traffic>.json``, and each per-layer metric is
read by ``bench/metrics/<name>.py``.  Nothing here knows any cell, mix,
driver or metric by name.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

from .drivers import driver

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: ModuleType
    end_to_end: list
    per_layer: list


def load(path: Path = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def _for(metrics: list, cell: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def cell(name: str, manifest: dict | None = None) -> Cell:
    """Everything one cell needs, read from the files it names."""
    man = load() if manifest is None else manifest
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in man["configs"]}[w["config"]]
    with open(ROOT / cfg["file"]) as f:
        config = json.load(f)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, driver=driver(config.get("driver")),
                end_to_end=_for(man["end_to_end"], name),
                per_layer=_for(man["per_layer"], name))
