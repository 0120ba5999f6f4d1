"""Drivers: a cell's configuration names the driver that puts its traffic
through the harness.

The YCSB driver reproduces, batch for batch and byte for byte, what the
harness served and compared when the YCSB loop was its own; the values
below were recorded from that harness at the fault tests' small size.
A test-only driver puts 2PL transaction batches through
``DevicePlane.txn`` and the same ``harness.run``, checked against the
host ``TxnEngine``, which shows that a verb other than ``ops`` fits the
seam without a harness edit.
"""

import dataclasses
import hashlib
import json
import sys
import time
import types

import numpy as np
import pytest

from bench import harness, manifest
from bench.drivers import driver, ycsb

SEED = 2**32 + 17
READ_ONLY = {"read_proportion": 1.0, "update_proportion": 0.0}

# recorded from the harness before drivers existed: the digest of every
# batch as sent and answered (warm-up first), of the read-back's plan
# and replies, and the rounds of each batch
BEFORE = {
    "ycsb_a": dict(
        mix={},
        served="4475254c6fde141a192a402335c48911"
               "3cc2286aaba53949dd0301dd11f92664",
        readback="ca738fbdceb37acda56c813cc432003b"
                 "fe10d38dd13c685a799f9972ab1a74d0",
        n_readback=256,
        rounds=[3, 6, 7, 6, 4, 6, 5, 4, 5, 4, 5, 4, 5, 4]),
    "read_only": dict(
        mix=READ_ONLY,
        served="bf027cc198e3b0ab79365c1951e39351"
               "261bd7f20bb4b01bdac3c13d818204c5",
        readback="e3b0c44298fc1c149afbf4c8996fb924"
                 "27ae41e4649b934ca495991b7852b855",
        n_readback=0,
        rounds=[1] * 14),
}


def _digest(rows) -> str:
    h = hashlib.sha256()
    for arrays, extra in rows:
        for a in arrays:
            h.update(np.ascontiguousarray(a).tobytes())
        if extra is not None:
            h.update(str(extra).encode())
    return h.hexdigest()


@pytest.mark.parametrize("case", sorted(BEFORE))
def test_ycsb_driver_serves_as_before(case, monkeypatch):
    want = BEFORE[case]
    records, readback = [], []

    def record(batch, result):
        rec = ycsb.record(batch, result)
        records.append(rec)
        return rec

    def read_back(plane, lines, nodes, r, width):
        got = read_back0(plane, lines, nodes, r, width)
        readback.append((lines.copy(), nodes.copy()) + got)
        return got

    read_back0 = ycsb._read_back
    monkeypatch.setattr(ycsb, "_read_back", read_back)
    drv = types.SimpleNamespace(**{k: getattr(ycsb, k) for k in (
        "open_plane", "traffic", "serve", "check", "least_bytes")},
        record=record)
    cell = manifest.cell("ycsb_a.l19")
    cell = dataclasses.replace(
        cell, driver=drv, config=dict(cell.config, recordcount=1024),
        traffic=dict(cell.traffic, warmup_batches=2, readback_records=256,
                     **want["mix"]))
    out = harness.run(cell, seed=SEED, seconds=0.0, trace=False,
                      t0=time.perf_counter(), require_tpu=False,
                      batches=12, log=lambda _: None)
    assert _digest([((b.node, b.line, b.is_write, b.tags, b.version,
                      b.data), (b.rounds, b.failed))
                    for b in records]) == want["served"]
    assert [b.rounds for b in records] == want["rounds"]
    assert readback[0][0].size == want["n_readback"]
    assert _digest([(rb, None) for rb in readback]) == want["readback"]
    assert out["attempted"] == 3072 and out["failed"] == 0
    assert out["checks"] == {
        "reply_mismatches": {"value": 0, "limit": 0},
        "readback_mismatches": {"value": 0, "limit": 0}}
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]


def test_driver_resolves_by_name():
    assert driver(None) is ycsb and driver("ycsb") is ycsb
    with pytest.raises(ModuleNotFoundError):
        driver("no_such_driver")
    for w in manifest.load()["workloads"]:
        assert manifest.cell(w["name"]).driver is ycsb


def test_config_names_its_driver(tmp_path, monkeypatch):
    """A configuration file's ``driver`` key picks the module; without
    it the driver is ``ycsb``."""
    man = manifest.load()
    cfg = json.loads((manifest.ROOT / man["configs"][0]["file"])
                     .read_text())
    named = tmp_path / "named.json"
    named.write_text(json.dumps(dict(cfg, driver="from_config")))
    monkeypatch.setattr(manifest, "ROOT", tmp_path)
    man = dict(man, configs=[dict(man["configs"][0], file="named.json")])
    named_driver = types.ModuleType("bench.drivers.from_config")
    monkeypatch.setitem(sys.modules, named_driver.__name__, named_driver)
    cell = manifest.cell(man["workloads"][0]["name"], man)
    assert cell.driver is named_driver


# ------------------------------------------- a verb other than plane.ops

def test_txn_driver_through_the_harness():
    from bench.tests import txn_driver
    cell = manifest.Cell(
        name="txn_2pl.test", chips=1, config=txn_driver.CONFIG,
        traffic={"warmup_batches": 2}, driver=txn_driver,
        end_to_end=[{"name": "ops_per_s", "unit": "ops/s"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[])
    out = harness.run(cell, seed=SEED, seconds=0.0, trace=False,
                      t0=time.perf_counter(), require_tpu=False,
                      batches=4, log=lambda _: None)
    assert out["correct"] is True
    assert out["attempted"] == 4 * 16 and out["failed"] == 0
    assert out["checks"] == {
        "decision_mismatches": {"value": 0, "limit": 0},
        "image_mismatches": {"value": 0, "limit": 0}}
    assert set(out["metrics"]) == {"ops_per_s", "setup_s"}


def test_txn_driver_catches_an_altered_image():
    """The same run with one lane of the device's image altered is not
    correct: the comparison with the host engine can fail."""
    from bench.tests import txn_driver

    def open_plane(config, seed, devices):
        eng = txn_driver.open_plane(config, seed, devices)
        final = eng.final_image

        def altered():
            img = final().copy()
            img[np.unravel_index(np.argmax(img != 0), img.shape)] ^= 1
            return img
        eng.final_image = altered
        return eng

    cell = manifest.Cell(
        name="txn_2pl.test", chips=1, config=txn_driver.CONFIG,
        traffic={"warmup_batches": 1}, driver=txn_driver,
        end_to_end=[], per_layer=[])
    out = harness.run(cell, seed=SEED, seconds=0.0, trace=False,
                      t0=time.perf_counter(), require_tpu=False,
                      plane_factory=open_plane, batches=2,
                      log=lambda _: None)
    assert out["correct"] is False
    assert out["checks"]["image_mismatches"]["value"] > 0


def test_compile_check_skips_a_driver_without_hooks():
    """``bench.compile_check`` compiles what a driver's ``lower_verb``
    gives; a driver without it (the test driver) is skipped, not a
    crash, and needs no topology to be skipped."""
    from bench import compile_check
    from bench.tests import txn_driver
    cell = manifest.Cell(
        name="txn_2pl.test", chips=1, config=txn_driver.CONFIG,
        traffic={}, driver=txn_driver, end_to_end=[], per_layer=[])
    assert compile_check.check(cell, 1, topo=None) is None
    assert callable(ycsb.lower_verb) and callable(ycsb.lower_build)


def test_ycsb_lower_verb_names_the_round_phases():
    """The YCSB driver's compile hook lowers the fused loop the plane
    runs, whose instructions carry the round body's scopes."""
    import jax
    from bench import spans
    cfg = dict(manifest.cell("ycsb_c.l19").config, recordcount=64,
               n_nodes=2, clients_per_node=4, payload_width=4)
    text = ycsb.lower_verb(cfg, jax.devices()[:1]).compile().as_text()
    assert {"round/latch", "round/boundary"} <= set(
        spans.scopes_from_hlo(text).values())
