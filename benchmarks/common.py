"""Shared benchmark driver for the DES-based paper figures.

All figures run scaled-down op counts (DES on one core); every knob that
determines the paper's RATIOS (sharing, skew, locality, read mix, cache
size relative to data) is preserved.  Each run prints a CSV row:

    figure,series,x,metric,value

Protocols resolve through the v2 backend registry
(``repro.core.available_protocols()``): figures can sweep every
registered backend — including out-of-tree ones — without edits here.
"""

from __future__ import annotations

import json
import platform
import sys
import time

sys.path.insert(0, "src")  # allow `python -m benchmarks.run` from repo root

from repro.apps.btree import BLinkTree                     # noqa: E402
from repro.apps.txn import TxnConfig, TxnEngine            # noqa: E402
from repro.apps.workloads import (MicroConfig, TPCCConfig,  # noqa: E402
                                  TPCCTables, YCSBConfig, micro_worker,
                                  tpcc_worker, ycsb_worker)
from repro.core import (ClusterConfig, GAMConfig,           # noqa: E402
                        SELCCConfig, SELCCLayer,
                        available_protocols, get_protocol)

__all__ = [                 # re-exported for the fig*.py drivers
    "BLinkTree", "TxnConfig", "TxnEngine", "MicroConfig", "TPCCConfig",
    "TPCCTables", "YCSBConfig", "micro_worker", "tpcc_worker",
    "ycsb_worker", "ClusterConfig", "GAMConfig", "SELCCConfig",
    "SELCCLayer", "available_protocols", "BASELINES", "HARD_LIMIT",
    "build_layer", "run_micro", "emit", "timer", "write_bench_json",
    "shard_mesh",
]

HARD_LIMIT = 300.0          # sim-seconds safety net

# Baseline sweep used by the comparison figures; any registered backend
# name is a valid series.
BASELINES = ("selcc", "sel", "gam", "rpc")


def shard_mesh(n_shards: int):
    """A ``("shards",)`` mesh over the first ``n_shards`` devices of this
    process.  Cells run in-process (one process per chip), so a CPU run
    that needs several devices gets them from the caller's environment:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    import jax
    devices = jax.devices()
    if len(devices) < n_shards:
        raise RuntimeError(
            f"a {n_shards}-shard cell needs {n_shards} devices, JAX has "
            f"{len(devices)}; on "
            f"the CPU set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={n_shards}")
    return jax.make_mesh((n_shards,), ("shards",),
                         devices=devices[:n_shards])


def build_layer(protocol: str, n_compute: int, threads: int,
                cache_entries: int = 4096, consistency: str = "SEQ",
                seed: int = 11) -> SELCCLayer:
    try:
        get_protocol(protocol)     # CLI-friendly unknown-name error only
    except ValueError as e:
        raise SystemExit(str(e)) from None
    selcc = SELCCConfig(cache_capacity=cache_entries)
    gam = GAMConfig(cache_capacity=cache_entries, consistency=consistency)
    return SELCCLayer(ClusterConfig(
        n_compute=n_compute, n_memory=max(2, n_compute),
        threads_per_node=threads, protocol=protocol, selcc=selcc, gam=gam,
        seed=seed))


def run_micro(protocol: str, n_compute: int, threads: int,
              mcfg: MicroConfig, cache_entries: int = 4096,
              consistency: str = "SEQ", seed: int = 11):
    layer = build_layer(protocol, n_compute, threads, cache_entries,
                        consistency, seed)
    gcls = layer.allocate_many(mcfg.n_gcls)
    procs = []
    for node in layer.nodes:
        for t in range(threads):
            procs.append(layer.env.process(micro_worker(
                node, gcls, mcfg, node.node_id, n_compute, t, seed)))
    layer.env.run_until_complete(procs, hard_limit=HARD_LIMIT)
    return layer


def emit(figure: str, series: str, x, metric: str, value,
         rows: list | None = None) -> None:
    """Print one CSV row; if ``rows`` is given, also collect it for a
    ``BENCH_*.json`` trajectory file (see :func:`write_bench_json`)."""
    print(f"{figure},{series},{x},{metric},{value:.6g}"
          if isinstance(value, float) else
          f"{figure},{series},{x},{metric},{value}", flush=True)
    if rows is not None:
        rows.append({"series": series, "x": x, "metric": metric,
                     "value": value})


def write_bench_json(name: str, rows: list, meta: dict | None = None,
                     path: str | None = None) -> str:
    """Write a machine-readable benchmark trajectory ``BENCH_<name>.json``
    (the artifact the CI smoke job uploads, seeding the perf history).

    Schema: ``{"bench": name, "meta": {...}, "rows": [{series, x,
    metric, value}, ...]}``."""
    out = path or f"BENCH_{name}.json"
    doc = {
        "bench": name,
        "meta": dict(meta or {}, python=platform.python_version(),
                     timestamp=time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())),
        "rows": rows,
    }
    with open(out, "w") as f:
        json.dump(doc, f, indent=1, default=float)
        f.write("\n")
    print(f"# wrote {out} ({len(rows)} rows)", flush=True)
    return out


class timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.wall = time.time() - self.t0
