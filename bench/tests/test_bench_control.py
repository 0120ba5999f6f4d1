"""The control run: incoherent caches in the plane's place come out
not correct, through the whole harness, at a size a test can hold."""

import dataclasses
import time

from bench import harness, manifest
from bench.control import IncoherentPlane


def test_control_is_not_correct():
    cell = manifest.cell("ycsb_a.l19")
    cell = dataclasses.replace(
        cell, config=dict(cell.config, recordcount=4096),
        traffic=dict(cell.traffic, warmup_batches=2, readback_records=256))
    out = harness.run(cell, seed=2**31 + 3, seconds=0.0, trace=False,
                      t0=time.perf_counter(), require_tpu=False,
                      plane_factory=IncoherentPlane, batches=40,
                      log=lambda _: None)
    assert out["correct"] is False
    assert out["checks"]["reply_mismatches"]["value"] > 0
    assert list(out)[-1] == "checks"
