"""The trace reduction, on a hand-made trace and on a small one
recorded on a TPU v5e (two ``plane.ops`` calls of a flat plane at 4,096
lines; ``bench/tests/data/trace_v5e_small.json.gz``)."""

import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data" / "trace_v5e_small.json.gz"


def test_reduce_hand_made():
    ms = 1_000_000
    t = {"host": [["bench.ops", 0, 10 * ms], ["np.asarray(jax.Array)",
                                               8 * ms, 2 * ms],
                  ["bench.generate", 10 * ms, 1 * ms],
                  ["bench.ops", 11 * ms, 9 * ms]],
         "devices": {
             "0": [["while.1", 1 * ms, 6 * ms], ["fusion.2", 1 * ms, 2 * ms],
                   ["all-to-all.3", 3 * ms, 2 * ms],
                   ["fusion.4", 5 * ms, 2 * ms],
                   ["fusion.2", 12 * ms, 4 * ms]],
             "1": [["fusion.2", 1 * ms, 4 * ms],
                   ["all-reduce.5", 12 * ms, 2 * ms]]}}
    r = trace.reduce(t)
    assert r["window_s"] == pytest.approx(0.020)
    # chip 0 busy [1, 7) and [12, 16) ms; chip 1 [1, 5) and [12, 14)
    assert r["busy_by_chip"] == {"0": pytest.approx(0.010),
                                 "1": pytest.approx(0.006)}
    assert r["busy_s"] == pytest.approx(0.008)
    assert [c["wall_s"] for c in r["calls"]] == [0.010, 0.009]
    assert r["calls"][0]["busy_s"] == pytest.approx((0.006 + 0.004) / 2)
    # both collectives run with no other leaf operation beside them; the
    # while loop that holds chip 0's does not count
    assert r["collective_s"] == {"0": pytest.approx(0.002),
                                 "1": pytest.approx(0.002)}
    assert r["collective_exposed_s"] == {"0": pytest.approx(0.002),
                                         "1": pytest.approx(0.002)}
    ops = dict(r["device_ops"])
    # while.1's self time: 6 ms less its nested ops (2 + 2 + 2)
    assert ops["while.1"] == pytest.approx(0.0)
    assert ops["fusion.2"] == pytest.approx((0.002 + 0.004 + 0.004) / 2)
    gaps = r["idle_gaps"]
    assert gaps[0][1] == pytest.approx(0.007)        # chip 1, 5 to 12 ms
    assert gaps[0][0].startswith("bench.ops")


def test_collective_partly_hidden():
    ms = 1_000_000
    # an async all-to-all [1, 5) ms with a fusion [2, 3) ms beside it
    t = {"host": [["bench.ops", 0, 10 * ms]],
         "devices": {"0": [["all-to-all.1", 1 * ms, 4 * ms],
                           ["fusion.2", 2 * ms, 1 * ms]]}}
    r = trace.reduce(t)
    assert r["collective_exposed_s"]["0"] == pytest.approx(0.003)
    assert r["busy_s"] == pytest.approx(0.004)


def test_reduce_recorded_v5e_trace():
    t = json.loads(gzip.decompress(DATA.read_bytes()))
    r = trace.reduce(t)
    assert r["n_chips"] == 1 and len(r["calls"]) == 2
    assert 0 < r["busy_s"] < r["window_s"]
    for c in r["calls"]:
        assert 0 < c["busy_s"] < c["wall_s"]
    # every device operation ran inside a plane.ops call
    assert sum(c["busy_s"] for c in r["calls"]) == pytest.approx(
        r["busy_s"])
    assert r["collective_s"] == {"0": 0.0}
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * 1.001
    assert all(g[0].startswith("bench.") for g in r["idle_gaps"][:3])
    # the numbers this reduction gave when it was written
    assert r["busy_s"] == pytest.approx(0.025147521)
    assert r["window_s"] == pytest.approx(0.03927049)
    assert r["device_ops"][0][0] == "while.50"


def test_reduce_without_calls_or_devices():
    assert trace.reduce({"host": [], "devices": {"0": []}}) == {}
    assert trace.reduce({"host": [["bench.ops", 0, 5]],
                         "devices": {}}) == {}


def test_op_name():
    assert trace.op_name("%copy.83 = s32[8]{0} copy(s32[8]{0} %x)") \
        == "copy.83"
