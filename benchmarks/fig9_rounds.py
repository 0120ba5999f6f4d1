"""fig9_rounds — Zipf-skew placement sweep on the DEVICE plane.

The paper's skew experiment (Sec. 4, Fig. 9) shows SELCC holding
throughput under Zipf access skew because ownership migrates to the
hot nodes.  This sweep reproduces the device-plane analogue for the
mesh-sharded rounds engine: HOME placement (not ownership) is the
degree of freedom, and the congestion telemetry the fused loop
accumulates in its carry is what drives it.  Three planes run the SAME
op stream on a 4-shard mesh:

* ``static``  — the hard-wired ``line % n_shards`` stripe (no home
  directory): hot lines land where the address math says;
* ``rehome``  — home-directory plane: a short probe phase collects
  ``PlaneResult.telemetry.line_hits``, ``placement.plan_rehome`` turns
  them into greedy hottest-to-coldest slot swaps, and
  ``plane.rehome`` migrates the slab rows before the timed phase;
* ``replica`` — re-homing plus ``plan_replication`` +
  ``plane.replicate``: read-mostly hot lines additionally serve
  S-latch reads from every shard's local replica.

The line id mapping is ADVERSARIAL for the static stripe: Zipf rank r
maps to line ``(r % (L/S)) * S + r // (L/S)``, which collapses the
hottest L/S ranks onto shard 0.  With a small ``bucket_cap`` the hot
home's request buckets overflow, ops defer, and the spin loop pays
extra rounds — exactly the congestion the telemetry counters expose
and re-homing repairs.  Uniform traffic (theta=0) runs as the control:
placement must not cost anything when there is nothing to fix.

All cells run in this process on the first four devices (on the CPU:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``); legs are
interleaved batch-by-batch and summarized by median per-batch time (same
methodology note as fig7_rounds).  Emits CSV rows plus
``BENCH_rounds_skew.json``; ``meta.speedup_floors`` relaxes the gate
to the calibrated floors (``rehome_speedup`` >= 1.3 on the skewed
write-intent leg), and ``meta.telemetry`` folds the per-home
served/deferred counters from the skewed cells into the artifact so
CI trajectories record WHERE the load sat, not just how fast it went.
"""

from __future__ import annotations

import argparse
import time

N_SHARDS = 4
N_NODES = 8
N_LINES = 256
R_SLOTS = 64
BUCKET_CAP = 1              # one slot per (source, home) per round — a
                            # single-doorbell transport: hot homes MUST
                            # drain serially until placement fixes them
MAX_ROUNDS = 256
PROBE_BATCHES = 2           # telemetry-gathering prefix (untimed)
MAX_MOVES = 16              # per plan_rehome pass
REHOME_PASSES = 4
TOP_K_REPLICAS = 32
MAX_WRITE_FRAC = 0.2        # replicate lines written < 20% of touches


def _remap(line):
    """Zipf rank -> line id, adversarial for the static stripe: ranks
    0..L/S-1 (the hottest) all land on shard 0 (line % S == 0)."""
    lps = N_LINES // N_SHARDS
    return ((line % lps) * N_SHARDS + line // lps).astype(line.dtype)


def _cells(iters: int) -> dict:
    """Every cell, on the first ``N_SHARDS`` devices."""
    import numpy as np

    from repro.apps.workloads import (DeviceRoundsConfig,
                                      device_rounds_batches)
    from repro.core import rounds as rp

    from .common import shard_mesh
    mesh = shard_mesh(N_SHARDS)

    def open_plane(home_directory=False, replicas=False):
        state = rp.make_sharded_state(
            N_NODES, N_LINES, mesh, home_directory=home_directory,
            replicas=replicas)
        return rp.DevicePlane.open(state, mesh, n_nodes=N_NODES,
                                   max_rounds=MAX_ROUNDS,
                                   bucket_cap=BUCKET_CAP)

    def run_cell(theta: float, read_ratio: float, seed: int) -> dict:
        cfg = DeviceRoundsConfig(
            n_nodes=N_NODES, n_lines=N_LINES, r_slots=R_SLOTS,
            read_ratio=read_ratio, zipf_theta=theta,
            iters=iters + PROBE_BATCHES)
        batches = [(n, _remap(l), w)
                   for n, l, w in device_rounds_batches(cfg, seed=seed)]
        planes = {
            "static": open_plane(),
            "rehome": open_plane(home_directory=True),
            "replica": open_plane(home_directory=True, replicas=True),
        }
        # --- probe: warm the jit caches AND collect telemetry --------
        hits = {k: np.zeros(N_LINES, np.int64) for k in planes}
        whits = {k: np.zeros(N_LINES, np.int64) for k in planes}
        for node, line, isw in batches[:PROBE_BATCHES]:
            for name, p in planes.items():
                res = p.ops(node, line, isw)
                hits[name] += res.telemetry.line_hits
                whits[name] += res.telemetry.line_whits
        # --- placement: migrate hot lines, replicate read-mostly -----
        for name in ("rehome", "replica"):
            p = planes[name]
            for _ in range(REHOME_PASSES):
                lines, homes, victims = rp.plan_rehome(
                    hits[name], np.asarray(p.state["home"]), N_SHARDS,
                    max_moves=MAX_MOVES)
                if lines.size == 0 or p.rehome(lines, homes,
                                               victims) == 0:
                    break
        repl = rp.plan_replication(hits["replica"], whits["replica"],
                                   top_k=TOP_K_REPLICAS,
                                   max_write_frac=MAX_WRITE_FRAC)
        if repl.size:
            planes["replica"].replicate(repl)
        # --- timed phase: interleaved, median per-batch --------------
        times: dict = {k: [] for k in planes}
        tele: dict = {k: {} for k in planes}
        for node, line, isw in batches[PROBE_BATCHES:]:
            for name, p in planes.items():
                t0 = time.perf_counter()
                res = p.ops(node, line, isw)
                times[name].append(time.perf_counter() - t0)
                for key in ("served_per_home", "deferred",
                            "replica_served"):
                    tele[name][key] = (
                        tele[name].get(key, 0)
                        + np.asarray(res.telemetry[key], np.int64))

        def med(name):
            ts = sorted(times[name])
            return ts[len(ts) // 2]

        st, rh, rl = med("static"), med("rehome"), med("replica")
        out = {
            "static_mops": R_SLOTS / st / 1e6,
            "rehome_mops": R_SLOTS / rh / 1e6,
            "replica_mops": R_SLOTS / rl / 1e6,
            "rehome_speedup": st / rh if rh > 0 else 0.0,
            "replica_speedup": st / rl if rl > 0 else 0.0,
            "telemetry": {
                name: {k: np.asarray(v).tolist()
                       for k, v in t.items()}
                for name, t in tele.items()},
        }
        for name, p in planes.items():
            p.check()
        return out

    cells = {}
    for series, read_ratio in (("write_int", 0.5), ("read_int", 0.95)):
        for theta in (0.0, 0.99):
            cells[f"{series}/{theta}"] = run_cell(theta, read_ratio,
                                                  seed=13)
    return cells


def main(quick: bool = False, smoke: bool = False) -> list:
    from .common import emit, write_bench_json
    iters = 9 if (smoke or quick) else 15
    cells = _cells(iters)
    rows: list = []
    telemetry = {}
    for key, m in cells.items():
        series, theta = key.split("/")
        theta = float(theta)
        skewed = theta > 0
        for metric in ("static_mops", "rehome_mops", "replica_mops"):
            emit("fig9_rounds", series, theta, metric, m[metric],
                 rows=rows)
        # speedup metrics are GATED (check_regression): emit them only
        # where the floor is meaningful — re-homing on the skewed
        # write-intent leg, replication on the skewed read-intent leg.
        # Uniform cells have nothing to fix (speedup ~1.0 by design).
        if skewed and series == "write_int":
            emit("fig9_rounds", series, theta, "rehome_speedup",
                 m["rehome_speedup"], rows=rows)
        if skewed and series == "read_int":
            emit("fig9_rounds", series, theta, "replica_speedup",
                 m["replica_speedup"], rows=rows)
        if skewed:
            telemetry[series] = m["telemetry"]
    write_bench_json(
        "rounds_skew", rows,
        meta={"n_shards": N_SHARDS, "n_nodes": N_NODES,
              "n_lines": N_LINES, "r_slots": R_SLOTS,
              "bucket_cap": BUCKET_CAP, "smoke": smoke, "quick": quick,
              # placement cells are ~10x smaller than the fig7 sweep
              # (256 lines, cap 1), so absolute mops jitter more across
              # runs; the within-run speedup RATIOS carry the gate.
              "gate_max_regress": 0.5,
              "speedup_floors": {"rehome_speedup": 1.3,
                                 "replica_speedup": 1.2},
              "telemetry": telemetry})
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    main(quick=args.quick, smoke=args.smoke)
