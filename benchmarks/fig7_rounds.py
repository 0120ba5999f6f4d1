"""fig7_rounds — the Fig. 7 scalability sweep on the DEVICE plane.

The paper's headline scalability claim (Sec. 4, Fig. 7) is that SELCC
scales with compute nodes because the memory side does zero protocol
compute.  This sweep reproduces the shape of that experiment for the
mesh-sharded rounds engine: 1 -> N home shards, three drivers over the
SAME YCSB-style Zipf op stream (apps/workloads.device_rounds_batches):

* ``fused``  — ``rounds.run_rounds_sharded``: the whole spin in ONE jit
  call, requests routed home and replies routed back by two all_to_alls
  per round, zero host<->device syncs;
* ``host``   — ``rounds.coherence_round_sharded`` re-dispatched from a
  host loop with a sync after EVERY round (the baseline the fused loop
  deletes — MIND's per-op round-trip overhead);
* ``single`` — the unsharded PR-2 engine (``rounds.run_rounds``) as the
  flat reference the sharded planes must match.

Every shard count runs in this process, on the first ``shards`` devices
(one process per chip: a child could not reach a device its parent
holds).  On the CPU the caller provides the devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  Emits CSV rows
plus ``BENCH_rounds_sharded.json`` via
``benchmarks.common.write_bench_json`` — the artifact the CI
``bench-gate`` job uploads and gates on (benchmarks/check_regression.py).
"""

from __future__ import annotations

import argparse
import math
import os
import time

N_NODES = 8
N_LINES = 1024
R_SLOTS = 64
MAX_ROUNDS = 128
READ_RATIO = 0.3            # write-intense: coherence traffic dominates
ZIPF_THETA = 1.1            # hotter than YCSB default: ~6.5 rounds/batch,
                            # so the per-round host sync the fused loop
                            # deletes is a structural, not marginal, cost


def _cell(shards: int, write_back: bool, iters: int) -> dict:
    """One shard count, on the first ``shards`` devices."""
    import jax
    import numpy as np

    from repro.apps.workloads import (DeviceRoundsConfig,
                                      device_rounds_batches)
    from repro.core import rounds as rp

    from .common import shard_mesh
    mesh = shard_mesh(shards)
    cfg = DeviceRoundsConfig(n_nodes=N_NODES, n_lines=N_LINES,
                             r_slots=R_SLOTS, read_ratio=READ_RATIO,
                             zipf_theta=ZIPF_THETA, iters=iters + 1)
    batches = device_rounds_batches(cfg, seed=7)

    # Timing methodology: the three drivers run INTERLEAVED, batch by
    # batch, each step synced, and every driver is summarized by its
    # MEDIAN per-batch time.  Back-to-back block timing of ~10ms-scale
    # work on a shared CPU is dominated by frequency/scheduler drift
    # between the blocks (order bias) and by GC/throttle spikes;
    # interleaving exposes all drivers to the same drift and the median
    # discards the spikes.  The per-batch sync is fair: the host loop
    # syncs every ROUND regardless — that per-round sync is exactly
    # what the fused driver deletes.
    rounds_used = []

    def fused_step(states, node, line, isw):
        states[0], vers, _, rounds, ok, _tele = rp.run_rounds_sharded(
            states[0], node, line, isw, mesh=mesh, n_nodes=N_NODES,
            max_rounds=MAX_ROUNDS)
        jax.block_until_ready(vers)
        rounds_used.append(int(rounds))
        assert bool(ok), "sharded ops unserved in bound"

    def host_step(states, node, line, isw):
        pending = line.copy()
        rounds = 0
        while (pending >= 0).any() and rounds < MAX_ROUNDS:
            states[0], served, _, _ = rp.coherence_round_sharded(
                states[0], node, pending, isw, mesh=mesh,
                n_nodes=N_NODES)
            pending = np.where(np.asarray(served), -1, pending)  # SYNC
            rounds += 1
        assert (pending < 0).all(), "host loop left ops unserved"

    def single_step(states, node, line, isw):
        states[0], vers, _, _, ok, _tele = rp.run_rounds(
            states[0], node, line, isw, n_nodes=N_NODES,
            max_rounds=MAX_ROUNDS)
        jax.block_until_ready(vers)
        assert bool(ok), "flat ops unserved in bound"

    drivers = {
        "fused": (fused_step,
                  [rp.make_sharded_state(N_NODES, N_LINES, mesh,
                                         write_back=write_back)]),
        "host": (host_step,
                 [rp.make_sharded_state(N_NODES, N_LINES, mesh,
                                        write_back=write_back)]),
        "single": (single_step,
                   [rp.make_state(N_NODES, N_LINES,
                                  write_back=write_back)]),
    }

    times: dict = {name: [] for name in drivers}
    for name, (step, states) in drivers.items():  # warmup = compile
        step(states, *batches[0])
    rounds_used.clear()
    for node, line, isw in batches[1:]:
        for name, (step, states) in drivers.items():
            t0 = time.perf_counter()
            step(states, node, line, isw)
            times[name].append(time.perf_counter() - t0)

    def med(name):
        ts = sorted(times[name])
        return ts[len(ts) // 2]

    fused_s, host_s, single_s = med("fused"), med("host"), med("single")
    out = {
        "fused_mops": R_SLOTS / fused_s / 1e6,
        "host_mops": R_SLOTS / host_s / 1e6,
        "single_mops": R_SLOTS / single_s / 1e6,
        "fused_speedup": host_s / fused_s if fused_s > 0 else 0.0,
        "rounds_per_batch": sum(rounds_used) / max(1, len(rounds_used)),
    }

    # Recorder-overhead leg (shards == 1 only — the flat plane is the
    # same at every shard count): ONE plane, ONE op stream, with the
    # FlightRecorder toggled on/off between whole passes over the
    # batch stream via ``attach_recorder`` — exactly what a user pays
    # for attaching a recorder to a live plane.  (Driving a second,
    # recorder-free plane instead reads ~5% high: two planes thrash
    # each other's state out of cache on every switch, a cost real
    # recorder usage never pays.)  Per quad the passes run A B B A
    # (on/off/off/on), so linear clock/frequency drift cancels inside
    # the quad; each pass is summarized by its median per-batch time,
    # each quad by the log-ratio of its on/off medians, each
    # repetition by the trimmed geometric mean over its quads.  The
    # reported figure is the MIN over independent repetitions —
    # timeit's rationale: ambient co-tenant interference only ever
    # contaminates a repetition upward, so the smallest estimate is
    # the least-contaminated one.  What survives IS the flight
    # recorder's whole cost: same dispatch, same telemetry
    # materialization, only the span/metrics/heat updates differ.
    if shards == 1:
        from repro.obs import FlightRecorder
        rec = FlightRecorder(capacity=4096)
        plane = rp.DevicePlane.open(
            rp.make_state(N_NODES, N_LINES, write_back=write_back),
            n_nodes=N_NODES, max_rounds=MAX_ROUNDS)
        plane.ops(*batches[0])                    # warmup = compile
        work = batches[1:]

        def pass_med(recorder):
            plane.attach_recorder(recorder)
            ts = []
            for node, line, isw in work:
                t0 = time.perf_counter()
                plane.ops(node, line, isw)
                ts.append(time.perf_counter() - t0)
            ts.sort()
            return ts[len(ts) // 2]

        pass_med(rec)                     # warm the recorder path
        reps, quads = 4, 10
        estimates = []
        for _rep in range(reps):
            logs = []
            for _quad in range(quads):
                a1 = pass_med(rec)
                b1 = pass_med(None)
                b2 = pass_med(None)
                a2 = pass_med(rec)
                logs.append(0.5 * math.log((a1 * a2) / (b1 * b2)))
            logs.sort()
            logs = logs[1:-1]             # drop the extreme quads
            estimates.append(math.exp(sum(logs) / len(logs)))
        out["recorder_overhead"] = min(estimates)
        assert rec.total == (1 + reps * quads * 2) * len(work), \
            "recorder missed dispatches"  # warm pass + 2 on-passes/quad
    return out


def main(quick: bool = False, smoke: bool = False) -> list:
    from .common import emit, write_bench_json
    if smoke:
        shard_counts, iters, modes = [1, 2], 8, (False,)
    elif quick:
        shard_counts, iters, modes = [1, 2, 4], 8, (False,)
    else:
        shard_counts, iters, modes = [1, 2, 4], 16, (False, True)
    rows: list = []
    rec_overheads: list = []
    for write_back in modes:
        series = "wb" if write_back else "wt"
        for s in shard_counts:
            m = _cell(s, write_back, iters)
            for metric, value in m.items():
                emit("fig7_rounds", series, s, metric, value, rows=rows)
            if "recorder_overhead" in m:
                rec_overheads.append(m["recorder_overhead"])
    # the recorder-overhead ratio rides meta UNGATED (check_regression
    # only reads speedup_floors); bench-smoke asserts the budget here,
    # where the run is short and the signal fresh.  The default 1.05
    # budget is the quiet-machine truth (the recorder's direct span
    # cost is ~3% of a fig7 dispatch); on noisy shared runners the
    # measured differential also carries allocator/cache second-order
    # effects and co-tenant jitter, so CI widens the budget via
    # BENCH_RECORDER_OVERHEAD_MAX to cliff-detection width — the same
    # stopgap pattern as the BENCH_GATE_MAX_REGRESS throughput budgets
    meta = {"n_nodes": N_NODES, "n_lines": N_LINES,
            "r_slots": R_SLOTS, "read_ratio": READ_RATIO,
            "zipf_theta": ZIPF_THETA, "smoke": smoke, "quick": quick,
            "recorder_overhead": (min(rec_overheads)
                                  if rec_overheads else None)}
    write_bench_json("rounds_sharded", rows, meta=meta)
    if smoke and rec_overheads:
        budget = float(os.environ.get("BENCH_RECORDER_OVERHEAD_MAX",
                                      "1.05"))
        best = min(rec_overheads)
        assert best <= budget, (
            f"flight recorder overhead {best:.3f}x exceeds "
            f"{budget:.2f}x budget (override with "
            f"BENCH_RECORDER_OVERHEAD_MAX)")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    main(quick=args.quick, smoke=args.smoke)
