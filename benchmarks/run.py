"""Benchmark runner — one module per paper table/figure + the roofline
report + the device-plane rounds sweep.

``python -m benchmarks.run [--quick] [--smoke] [--only figN,...]``

Prints ``figure,series,x,metric,value`` CSV rows per figure, plus wall
time per figure.  ``--smoke`` is the CI trajectory job: a fast subset
that writes the machine-readable ``BENCH_*.json`` artifacts — the
device-plane rounds sweeps, the DES plane (``BENCH_selcc.json``), and
the serving engine (``BENCH_serving.json``).

Everything runs in this one process (one process per chip).  The
sharded cells take the first devices JAX reports; on the CPU the caller
provides them: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

from __future__ import annotations

import argparse
import sys
import time


def smoke() -> None:
    """CI smoke: one small DES micro-run + the device rounds sweeps
    (flat + mesh-sharded + the payload data plane), all persisted as
    BENCH_*.json for the per-commit perf trajectory (gated by
    benchmarks.check_regression)."""
    from . import (bench_serving, fig7_rounds, fig9_rounds,
                   fig10_btree_rounds, fig_rounds, fig_rounds_data)
    from .common import MicroConfig, emit, run_micro, timer, \
        write_bench_json

    rows: list = []
    for read_ratio, series in ((0.95, "read_int"), (0.5, "write_int")):
        mcfg = MicroConfig(n_gcls=2_000, sharing_ratio=1.0,
                           read_ratio=read_ratio, ops_per_thread=100)
        with timer() as t:
            layer = run_micro("selcc", 4, 8, mcfg)
        emit("selcc_smoke", series, 4, "mops",
             layer.throughput() / 1e6, rows=rows)
        emit("selcc_smoke", series, 4, "mean_latency_us",
             layer.mean_latency() * 1e6, rows=rows)
        emit("selcc_smoke", series, 4, "inv_ratio", layer.inv_ratio(),
             rows=rows)
        emit("selcc_smoke", series, 4, "hit_rate",
             layer.cache_stats().get("hits", 0)
             / max(1, layer.total_ops()), rows=rows)
        emit("selcc_smoke", series, 4, "wall_s", t.wall, rows=rows)
    write_bench_json("selcc", rows, meta={"smoke": True})
    fig_rounds.main(smoke=True)              # writes BENCH_rounds.json
    fig7_rounds.main(smoke=True)      # writes BENCH_rounds_sharded.json
    fig_rounds_data.main(smoke=True)     # writes BENCH_rounds_data.json
    fig9_rounds.main(smoke=True)         # writes BENCH_rounds_skew.json
    fig10_btree_rounds.main(smoke=True)  # writes BENCH_btree_rounds.json
    bench_serving.main(smoke=True)           # writes BENCH_serving.json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced op counts (CI mode)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset emitting BENCH_*.json artifacts")
    ap.add_argument("--only", default="",
                    help="comma list: fig7,fig7r,fig8,fig9,fig9r,"
                         "rounds_skew,fig10,btree_rounds,fig11,"
                         "fig12,rounds,rounds_data,serving,"
                         "roofline")
    args = ap.parse_args()

    sys.path.insert(0, "src")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("figure,series,x,metric,value")
    if args.smoke:
        t0 = time.time()
        smoke()
        print(f"# smoke done in {time.time() - t0:.1f}s", flush=True)
        return

    from . import (bench_serving, fig7_rounds, fig7_scalability,
                   fig8_locality, fig9_rounds, fig9_skew,
                   fig10_btree_rounds, fig10_ycsb_btree, fig11_tpcc,
                   fig12_2pc, fig_rounds,
                   fig_rounds_data, roofline_report)
    figures = {
        "fig7": fig7_scalability.main,
        "fig7r": fig7_rounds.main,
        "fig8": fig8_locality.main,
        "fig9": fig9_skew.main,
        "fig9r": fig9_rounds.main,
        "rounds_skew": fig9_rounds.main,
        "fig10": fig10_ycsb_btree.main,
        "btree_rounds": fig10_btree_rounds.main,
        "fig11": fig11_tpcc.main,
        "fig12": fig12_2pc.main,
        "rounds": fig_rounds.main,
        "rounds_data": fig_rounds_data.main,
        "serving": bench_serving.main,
        "roofline": roofline_report.main,
    }
    only = [x for x in args.only.split(",") if x]
    for name, fn in figures.items():
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            fn(quick=args.quick)
        except Exception as e:  # noqa: BLE001
            print(f"# {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            raise
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
