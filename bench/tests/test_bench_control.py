"""The controls: the reference's store with one guarantee broken, in the
plane's place, comes out not correct through the whole harness, at a
size a test can hold.  Each cell has a control its mix can show: a
read-only mix cannot see caches that are never invalidated, but does
see cached copies held at half width."""

import dataclasses
import time

import pytest

from bench import harness, manifest
from bench.control import CONTROLS


def _run(workload: str, control: str) -> dict:
    cell = manifest.cell(workload)
    cell = dataclasses.replace(
        cell, config=dict(cell.config, recordcount=4096),
        traffic=dict(cell.traffic, warmup_batches=2, readback_records=256))
    return harness.run(cell, seed=2**31 + 3, seconds=0.0, trace=False,
                       t0=time.perf_counter(), require_tpu=False,
                       plane_factory=CONTROLS[control], batches=40,
                       log=lambda _: None)


def test_control_is_not_correct():
    out = _run("ycsb_a.l19", "incoherent")
    assert out["correct"] is False
    assert out["checks"]["reply_mismatches"]["value"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", ["ycsb_a.l19", "ycsb_c.l19"])
def test_half_width_control_is_not_correct(workload):
    out = _run(workload, "half_width")
    assert out["correct"] is False
    assert out["checks"]["reply_mismatches"]["value"] > 0


def test_incoherent_control_is_blind_to_reads_alone():
    """Why the read-only cell needs its own control: without writes the
    incoherent store answers as the reference does."""
    assert _run("ycsb_c.l19", "incoherent")["correct"] is True
