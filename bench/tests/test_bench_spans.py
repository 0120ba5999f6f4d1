"""The verb-span reduction (``bench/spans.py``) on a hand-made trace and
on a small one recorded on a TPU v5e, its scope map on programs
compiled here, and the readers of the plane's own spans and counters."""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import spans
from bench.metrics import reader

DATA = Path(__file__).parent / "data" / "spans_v5e_small.json.gz"
ROUND_SCOPES = {"round/coalesce", "round/latch", "round/fetch",
                "round/boundary", "round/reply", "round/telemetry"}


def test_reduce_hand_made():
    ms = 1_000_000
    host = [["bench.ops", 0, 10 * ms], ["plane.ops", 0.5 * ms, 9 * ms],
            ["plane.dispatch", 0.5 * ms, 1 * ms],
            ["plane.sync", 1.5 * ms, 6 * ms],
            ["plane.telemetry", 7.5 * ms, 1 * ms],
            ["plane.results", 8.5 * ms, 1 * ms],
            ["np.asarray(jax.Array)", 8.6 * ms, 0.5 * ms]]
    devices = {"0": [["while.2", 1 * ms, 2 * ms],
                     ["fusion.3", 1.5 * ms, 1 * ms],
                     ["fusion.1", 3 * ms, 4 * ms],
                     ["copy.4", 8 * ms, 0.5 * ms]]}
    scopes = {"while.2": "round/latch", "fusion.3": "round/latch",
              "fusion.1": "round/boundary"}
    r = spans.reduce({"host": host, "devices": devices}, scopes)
    assert r["order"] == ["plane.dispatch", "plane.sync",
                          "plane.telemetry", "plane.results"]
    want = {"plane.dispatch": (0.001, 0.0005),
            "plane.sync": (0.006, 0.0055),
            "plane.telemetry": (0.001, 0.0005),
            "plane.results": (0.001, 0.0)}
    for k, (wall, busy) in want.items():
        assert r["spans"][k]["wall_s"] == pytest.approx(wall)
        assert r["spans"][k]["busy_s"] == pytest.approx(busy)
    assert r["unspanned"]["wall_s"] == pytest.approx(0.001)
    assert r["unspanned"]["busy_s"] == pytest.approx(0.0)
    # the children and the rest split the call's idle time exactly
    idle = sum(s["idle_s"] for s in r["spans"].values()) \
        + r["unspanned"]["idle_s"]
    assert idle == pytest.approx(0.010 - r["busy_s"])
    assert r["busy_s"] == pytest.approx(0.0065)
    assert dict(r["by_scope"]) == {
        "round/boundary": pytest.approx(0.004),
        "round/latch": pytest.approx(0.002),        # while.2 self + fusion
        spans.UNSCOPED: pytest.approx(0.0005)}


def test_reduce_without_calls_or_devices():
    assert spans.reduce({"host": [], "devices": {"0": []}}) == {}
    assert spans.reduce({"host": [["bench.ops", 0, 5]],
                         "devices": {}}) == {}


def test_scope_of():
    assert spans.scope_of("jit(run_rounds)/while/body/jit(_round_impl)/"
                          "round/boundary/reduce_sum") == "round/boundary"
    assert spans.scope_of("jit(f)/route/exchange/x/round/latch/y") \
        == "round/latch"
    assert spans.scope_of("jit(run_rounds)/while") == spans.UNSCOPED


@pytest.mark.parametrize("geometry", ["flat", "sharded"])
def test_scopes_of_compiled_verb(geometry):
    """The fused driver compiled here names every round phase in its
    instructions' metadata; the sharded one also its routing."""
    import jax
    from repro.core import rounds as rp
    i = np.zeros(8, np.int32)
    wd = np.zeros((8, 2), np.int32)
    if geometry == "flat":
        st = rp.make_state(4, 16, write_back=True, payload_width=2)
        low = rp.run_rounds.lower(st, i, i, i, wd, n_nodes=4)
        want = ROUND_SCOPES
    else:
        mesh = jax.make_mesh((1,), ("shards",))
        st = rp.make_sharded_state(4, 16, mesh, write_back=True,
                                   payload_width=2)
        low = rp.run_rounds_sharded.lower(st, i, i, i, wd, mesh=mesh,
                                          n_nodes=4)
        want = ROUND_SCOPES | {"route/bucket", "route/exchange"}
    got = set(spans.scopes_from_hlo(low.compile().as_text()).values())
    assert want <= got


def test_reduce_recorded_v5e_trace():
    """Two ``plane.ops`` calls of a flat plane at 4,096 lines on a TPU
    v5e, with the verb's scope map compiled there."""
    doc = json.loads(gzip.decompress(DATA.read_bytes()))
    r = spans.reduce(doc["trace"], doc["scopes"])
    assert len(r["calls"]) == 2
    assert r["order"] == ["plane.dispatch", "plane.sync",
                          "plane.telemetry", "plane.results"]
    for c in r["calls"]:
        assert c["order"] == r["order"]
        # the device works while the host waits in the sync
        assert c["spans"]["plane.sync"]["busy_s"] > 0.5 * c["busy_s"]
    top = dict(r["by_scope"])
    scoped = sum(v for k, v in top.items() if k != spans.UNSCOPED)
    assert scoped > 0 and set(top) <= ROUND_SCOPES | {spans.UNSCOPED}
    assert sum(top.values()) <= r["busy_s"] * 1.001
    # the numbers this reduction gave when it was written: at 4,096
    # lines the `ref` latch loop, not the boundary, takes the time
    assert r["by_scope"][0][0] == "round/latch"
    assert r["busy_s"] == pytest.approx(0.050139733)
    assert r["spans"]["plane.telemetry"]["wall_s"] == pytest.approx(
        0.0031242805)


def _result(host_s=None, events=(0, 0, 0), served=0):
    from repro.core.rounds.plane import PlaneResult
    from repro.obs import PlaneTelemetry
    tele = PlaneTelemetry.zeros(1, 4)
    tele = PlaneTelemetry.from_counters(dict(
        tele.as_dict(), served_per_home=np.asarray([served]),
        latch_requests=events[0], latch_failed=events[1],
        local_hits=events[2]))
    return PlaneResult(np.zeros(1, np.int32), np.zeros((1, 0), np.int32),
                       1, {}, tele, dict(host_s or {}))


def _ctx(results):
    return SimpleNamespace(traced_results=results, trace={}, rounds=[],
                           traced_rounds=0, traced_bytes=0, n_chips=1,
                           peak=None)


TIMES = [{"dispatch": 0.002, "sync": 0.08, "telemetry": 0.004,
          "results": 0.001},
         {"dispatch": 0.004, "sync": 0.08, "telemetry": 0.006,
          "results": 0.001}]


@pytest.mark.parametrize("name, want", [
    ("verb_dispatch_ms", 3.0),
    ("verb_readout_ms", 6.0),
    ("latch_retry_pct", 100.0 * 5 / 40),
    ("local_hit_pct", 100.0 * 100 / 512),
])
def test_plane_readers(name, want):
    res = [_result(TIMES[0], (10, 2, 60), 256),
           _result(TIMES[1], (30, 3, 40), 256)]
    assert reader(name)(_ctx(res)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["verb_dispatch_ms", "verb_readout_ms",
                                  "latch_retry_pct", "local_hit_pct"])
def test_plane_readers_find_nothing(name):
    """A program without the spans and counters, or a window without
    traced calls, has nothing to read: ``None``, never a raise."""
    old = SimpleNamespace(version=None, data=None, rounds=1, stats={},
                          telemetry=SimpleNamespace(served=256))
    assert reader(name)(_ctx([old, old])) is None
    assert reader(name)(_ctx([])) is None
