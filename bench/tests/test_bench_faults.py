"""A whole run on the CPU, with the timed path broken underneath.

Each test skips the harness's look for a chip and drives the rest of a
run (set-up, warm-up, window, reference, read-back) at a small size.
The sound run comes out correct; each fault the cells can have comes
out not correct: a step that leaves the state unchanged, half of each
batch left out, an answer altered where it is produced, and, on the
four-way sharded plane, the exchange between chips left out.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness, manifest
from bench.drivers import ycsb

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**32 + 17


def small(name: str, records: int):
    cell = manifest.cell(name)
    return dataclasses.replace(
        cell, config=dict(cell.config, recordcount=records),
        traffic=dict(cell.traffic, warmup_batches=2, readback_records=256))


class Faulty:
    """``DevicePlane.ops`` with one fault planted."""

    def __init__(self, plane, fault: str):
        self.plane, self.fault = plane, fault

    def ops(self, node, line, isw, wdata):
        if self.fault == "half_batch":
            line = line.copy()
            line[line.size // 2:] = -1
        before = self.plane.state
        res = self.plane.ops(node, line, isw, wdata)
        if self.fault == "state_unchanged":
            self.plane.state = before
        if self.fault == "answer_altered":
            data = np.array(res.data)
            data[0, 0] ^= 1
            res = dataclasses.replace(res, data=data)
        return res


def run(fault=None, workload="ycsb_a.l19", **mix):
    def factory(cfg, seed, devices):
        plane = ycsb.open_plane(cfg, seed, devices)
        return plane if fault is None else Faulty(plane, fault)
    cell = small(workload, 1024)
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, **mix))
    return harness.run(cell, seed=SEED, seconds=0.0,
                       trace=False, t0=time.perf_counter(),
                       require_tpu=False, plane_factory=factory,
                       batches=12, log=lambda _: None)


def test_sound_run_is_correct():
    out = run()
    assert out["correct"] is True
    assert out["attempted"] == 12 * 256 and out["failed"] == 0
    assert set(out["metrics"]) == {"ops_per_s", "op_p95_ms", "setup_s"}
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("mix", [
    {"read_proportion": 1.0, "update_proportion": 0.0},
    {"request_distribution": "uniform"}], ids=["read_only", "uniform"])
def test_other_mixes_are_correct(mix):
    # mixes a later cell can bring as a traffic file alone
    out = run(**mix)
    assert out["correct"] is True
    assert out["checks"]["reply_mismatches"]["value"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_is_not_correct(fault):
    out = run(fault)
    assert out["correct"] is False
    assert out["checks"]["reply_mismatches"]["value"] > 0 \
        or out["checks"]["readback_mismatches"]["value"] > 0


def test_read_only_cell_sound_run_is_correct():
    out = run(workload="ycsb_c.l19")
    assert out["correct"] is True
    assert out["attempted"] == 12 * 256 and out["failed"] == 0
    assert all(c["value"] == 0 for c in out["checks"].values())


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_read_only_cell_fault_is_not_correct(fault):
    # the read-only cell cannot have a step that leaves the state
    # unchanged: no read changes a record, so every answer stays right
    out = run(fault, "ycsb_c.l19")
    assert out["correct"] is False
    assert out["checks"]["reply_mismatches"]["value"] > 0 \
        or out["checks"]["readback_mismatches"]["value"] > 0


SHARDED = textwrap.dedent("""
    import dataclasses, json, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    from bench import harness, manifest
    # the one-chip cell's configuration, striped over four devices
    cell = manifest.cell("ycsb_a.l19")
    cell = dataclasses.replace(
        cell, chips=4, config=dict(cell.config, recordcount=4096),
        traffic=dict(cell.traffic, warmup_batches=2, readback_records=256))
    def go():
        out = harness.run(cell, seed={seed}, seconds=0.0, trace=False,
                          t0=time.perf_counter(), require_tpu=False,
                          batches=8, log=lambda _: None)
        return {{"correct": out["correct"], "count": out["device"]["count"],
                 "checks": out["checks"]}}
    sound = go()
    # the exchange between chips left out: every all_to_all returns its
    # own shard's buckets
    jax.lax.all_to_all = lambda x, *a, **k: x
    jax.clear_caches()
    print(json.dumps({{"sound": sound, "no_exchange": go()}}))
""")


def test_sharded_sound_and_without_exchange():
    code = SHARDED.format(root=str(ROOT), src=str(ROOT / "src"), seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["sound"]["count"] == 4
    assert got["sound"]["correct"] is True
    assert got["no_exchange"]["correct"] is False
