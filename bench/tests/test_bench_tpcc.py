"""The ``tpcc`` driver through the unchanged harness, on the CPU at a
small size: a sound run is correct; the incoherent control (the
reference's store with caches a peer's write never invalidates) and a
planted lost update are not.  The traced readers, generic and
transaction-only, read a number from the driver's results."""

import dataclasses
import time

import pytest

from bench import harness, manifest
from bench.control import CONTROLS
from bench.drivers import tpcc
from bench.metrics import reader

SEED = 2 ** 32 + 29
SMALL = dict(warehouses=2, districts=3, customers=20, items=50, orders=20,
             new_orders=6, ring=30, history_lines=1, n_lines=512, n_nodes=3)


def small_cell():
    cell = manifest.cell("tpcc_mix.w3")
    return dataclasses.replace(
        cell, config=dict(cell.config, **SMALL),
        traffic=dict(cell.traffic, warmup_batches=2,
                     terminals_per_warehouse=4))


def run(factory=None, batches=5):
    return harness.run(small_cell(), seed=SEED, seconds=0.0, trace=False,
                       t0=time.perf_counter(), require_tpu=False,
                       plane_factory=factory, batches=batches,
                       log=lambda _: None)


def test_cells_resolve_their_drivers():
    """The YCSB cells keep the default driver; the TPC-C cell's
    configuration names ``tpcc``."""
    from bench.drivers import ycsb
    for w in manifest.load()["workloads"]:
        cell = manifest.cell(w["name"])
        want = tpcc if cell.config.get("driver") == "tpcc" else ycsb
        assert cell.driver is want, w["name"]


def test_tpcc_cell_is_declared():
    cell = manifest.cell("tpcc_mix.w3")
    assert cell.driver is tpcc and cell.chips == 1
    assert [m["name"] for m in cell.per_layer][-2:] == [
        "txn_iters_per_batch", "txn_restart_pct"]
    assert cell.config["warehouses"] == 3 and cell.config["n_nodes"] == 8
    sc = tpcc._scale(cell.config)
    assert sc.base["end"] == 522_477 <= sc.n_lines == 2 ** 19
    assert cell.config["recordcount"] == sc.base["end"]


def test_sound_run_is_correct():
    out = run()
    assert out["correct"] is True
    assert out["attempted"] == 5 * 8 and out["failed"] == 0
    assert out["checks"] == {
        "output_mismatches": {"value": 0, "limit": 0},
        "image_mismatches": {"value": 0, "limit": 0},
        "consistency_violations": {"value": 0, "limit": 0},
        "order_ring_overruns": {"value": 0, "limit": 0}}
    assert set(out["metrics"]) == {"ops_per_s", "op_p95_ms", "setup_s"}


def test_incoherent_control_is_not_correct():
    out = run(CONTROLS["incoherent"])
    assert out["correct"] is False
    assert out["checks"]["output_mismatches"]["value"] > 0
    assert out["checks"]["image_mismatches"]["value"] > 0


class LostUpdate:
    """``DevicePlane.txn`` whose third batch leaves the state as it was:
    every write of that batch is lost."""

    def __init__(self, plane):
        self.plane, self.calls = plane, 0

    def txn(self, *a, **k):
        before = self.plane.state
        res = self.plane.txn(*a, **k)
        self.calls += 1
        if self.calls == 3:
            self.plane.state = before
        return res

    def ops(self, *a, **k):
        return self.plane.ops(*a, **k)


def test_lost_update_is_not_correct():
    out = run(lambda cfg, seed, devs: LostUpdate(
        tpcc.open_plane(cfg, seed, devs)))
    assert out["correct"] is False
    assert out["checks"]["image_mismatches"]["value"] > 0


def test_readers_on_traced_txn_results():
    """Every per-layer reader of the cell, generic and transaction-only,
    reads a number from what a ``--trace 1`` run hands it: the results
    ``serve`` returned and their records, with a recorded v5e device
    trace standing in for the profiler's (the CPU has no device
    trace)."""
    import gzip
    import json
    from pathlib import Path

    import jax
    from bench import trace
    from bench.peaks import peak
    cell = small_cell()
    plane = tpcc.open_plane(cell.config, SEED, jax.devices()[:1])
    gen = tpcc.traffic(cell.config, cell.traffic, SEED)
    batches = [gen.next() for _ in range(2)]
    results = [tpcc.serve(plane, b) for b in batches]
    records = [tpcc.record(b, r) for b, r in zip(batches, results)]
    data = Path(__file__).parent / "data" / "trace_v5e_small.json.gz"
    ctx = harness.Readings(
        trace=trace.reduce(json.loads(gzip.decompress(data.read_bytes()))),
        rounds=[r.rounds for r in results],
        traced_rounds=sum(r.rounds for r in results),
        traced_bytes=tpcc.least_bytes(cell.config, records), n_chips=1,
        peak=peak("TPU v5 lite"), traced_results=results)
    for m in cell.per_layer:
        v = reader(m["name"])(ctx)
        assert isinstance(v, float) and v >= 0, m["name"]
    assert reader("txn_iters_per_batch")(ctx) == pytest.approx(
        sum(r.iters for r in results) / 2)
    attempts = sum(r.counters["attempts"] for r in results)
    assert attempts > 2 * 8
    assert reader("txn_restart_pct")(ctx) == pytest.approx(
        100.0 * sum(r.counters["restarts"] for r in results) / attempts)
    assert set(results[0].host_s) == {"pad", "dispatch", "sync",
                                      "telemetry", "results"}


def test_txn_readers_find_nothing_without_counters():
    from types import SimpleNamespace
    old = SimpleNamespace(rounds=3, iters=4, telemetry=None)
    ctx = SimpleNamespace(traced_results=[old], trace={})
    assert reader("txn_iters_per_batch")(ctx) is None
    assert reader("txn_restart_pct")(ctx) is None
    assert reader("txn_restart_pct")(SimpleNamespace(
        traced_results=[], trace={})) is None


def test_lower_verb_names_the_round_phases():
    """The driver's compile hook lowers the loop ``plane.txn`` runs."""
    import jax
    from bench import spans
    text = tpcc.lower_verb(small_cell().config,
                           jax.devices()[:1]).compile().as_text()
    assert {"round/latch", "round/boundary"} <= set(
        spans.scopes_from_hlo(text).values())
