"""The plane's own instrumentation: the verb bracket's profiler spans
(``plane.<verb>`` and its fixed children, on a CPU ``jax.profiler``
trace, flat in-process and 4-shard in a subprocess) and the round
body's protocol counters (``latch_requests``, ``latch_failed``,
``local_hits``) against a hand count and between a flat and a sharded
plane on the same trace."""

import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import rounds as rp                      # noqa: E402
from repro.core.rounds import engine                     # noqa: E402

HERE = Path(__file__).resolve().parent
CHILDREN = ["plane.dispatch", "plane.sync", "plane.telemetry",
            "plane.results"]


def _i32(*xs):
    return np.asarray(xs, np.int32)


def _host_events(tdir):
    """Every host event of the profiled thread that holds ``plane.*``
    annotations: ``[name, start_ns, end_ns]``."""
    from jax.profiler import ProfileData
    path = next(os.path.join(r, f) for r, _, fs in os.walk(tdir)
                for f in fs if f.endswith(".xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                   for e in line.events]
            if any(e[0].startswith("plane.") for e in evs):
                return evs
    return []


def span_check(sharded: bool) -> None:
    """Two ``plane.ops`` calls under a CPU profiler: each is one
    ``plane.ops`` span holding the fixed children in order (with
    ``plane.pad`` first on a sharded plane), and profiling adds no jit
    trace."""
    if sharded:
        mesh = jax.make_mesh((4,), ("shards",))
        plane = rp.DevicePlane.open(
            rp.make_sharded_state(4, 16, mesh, write_back=True,
                                  payload_width=2), mesh, n_nodes=4)
    else:
        plane = rp.DevicePlane.open(
            rp.make_state(4, 16, write_back=True, payload_width=2),
            n_nodes=4)
    node, line, isw = _i32(0, 1, 2, 3, 1), _i32(3, 3, 5, 9, 3), \
        _i32(1, 0, 1, 0, 1)
    wdata = np.arange(10, dtype=np.int32).reshape(5, 2)
    plane.ops(node, line, isw, wdata)              # warm every shape
    before = dict(engine.TRACE_COUNTS)
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    try:
        res = [plane.ops(node, line, isw, wdata) for _ in range(2)]
    finally:
        jax.profiler.stop_trace()
    assert engine.TRACE_COUNTS == before, "profiling minted jit traces"
    evs = _host_events(tdir)
    verbs = sorted((e for e in evs if e[0] == "plane.ops"),
                   key=lambda e: e[1])
    assert len(verbs) == 2
    want = (["plane.pad"] if sharded else []) + CHILDREN
    for v in verbs:
        kids = sorted((e for e in evs if e[0].startswith("plane.")
                       and e is not v and v[1] <= e[1] and e[2] <= v[2]),
                      key=lambda e: e[1])
        assert [k[0] for k in kids] == want
        # children follow one another inside the bracket
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    for r in res:
        assert sorted(r.host_s) == sorted(k.split(".")[1] for k in want)
        assert all(t >= 0 for t in r.host_s.values())


@pytest.mark.parametrize("geometry", ["flat", "sharded"])
def test_verb_spans_in_cpu_profile(geometry):
    if geometry == "flat":
        span_check(sharded=False)
        return
    code = ("import sys; sys.path[:0] = ['src', %r]; "
            "import test_plane_trace as t; t.span_check(sharded=True); "
            "print('SPANS_OK')" % str(HERE))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=HERE.parent,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert "SPANS_OK" in out.stdout, out.stderr[-3000:]


def _events(res):
    t = res.telemetry
    return (int(t.latch_requests), int(t.latch_failed),
            int(t.local_hits))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_protocol_counters_hand_count(backend):
    """Each call's (latch_requests, latch_failed, local_hits) and rounds,
    counted by hand from the protocol (write-back, 4 nodes, 4 lines)."""
    plane = rp.DevicePlane.open(
        rp.make_state(4, 4, write_back=True, payload_width=1),
        n_nodes=4, backend=backend)
    steps = [
        # node 0 reads line 0 (S), node 1 writes line 1 (M): two misses,
        # both granted
        ((0, 1), (0, 1), (0, 1), (2, 0, 0), 1),
        # local hits, no request: node 0's two reads of line 0 coalesce
        # on its S copy, node 1 reads line 1 from its M copy
        ((0, 1, 0), (0, 1, 0), (0, 0, 0), (0, 0, 3), 1),
        # S->X upgrade: node 0 is line 0's sole reader, one CAS succeeds
        ((0,), (0,), (1,), (1, 0, 0), 1),
        # a CAS lost to a peer: nodes 2 and 3 both write free line 2;
        # node 3's CAS fails, kills node 2's fresh M copy at the
        # boundary, and wins next round
        ((2, 3), (2, 2), (1, 1), (3, 1, 0), 2),
        # a read blocked by a writer: node 1 holds line 1 in M; node
        # 0's FAA sees the writer, node 1 downgrades, the retry succeeds
        ((0,), (1,), (0,), (2, 1, 0), 2),
    ]
    for node, line, isw, want, rounds in steps:
        isw = np.asarray(isw, np.int32)
        wdata = np.where(isw[:, None] > 0, 7, 0).astype(np.int32)
        res = plane.ops(np.asarray(node, np.int32),
                        np.asarray(line, np.int32), isw, wdata)
        assert (_events(res), res.rounds) == (want, rounds), (node, line)
        assert res.telemetry.served == len(node)
        plane.check()


def counter_parity() -> None:
    """A seeded mixed trace through a flat plane and a 4-shard plane
    whose buckets cannot overflow: the same protocol counts per call."""
    mesh = jax.make_mesh((4,), ("shards",))
    kw = dict(write_back=True, payload_width=2)
    flat = rp.DevicePlane.open(rp.make_state(8, 32, **kw), n_nodes=8)
    shd = rp.DevicePlane.open(rp.make_sharded_state(8, 32, mesh, **kw),
                              mesh, n_nodes=8)
    rng = np.random.default_rng(7)
    total = np.zeros(3, np.int64)
    for b in range(6):
        node = rng.integers(0, 8, 24).astype(np.int32)
        line = rng.integers(0, 6, 24).astype(np.int32)   # hot: conflicts
        isw = (rng.random(24) < 0.5).astype(np.int32)
        wdata = rng.integers(0, 99, (24, 2)).astype(np.int32)
        rf = flat.ops(node, line, isw, wdata)
        rs = shd.ops(node, line, isw, wdata)
        assert rs.telemetry.deferred_total == 0
        assert rf.rounds == rs.rounds, b
        assert _events(rf) == _events(rs), b
        total += _events(rf)
    # the trace exercised every counter
    assert (total > 0).all(), total
    print("COUNTERS_OK")


def test_protocol_counters_flat_vs_sharded_subprocess():
    code = ("import sys; sys.path[:0] = ['src', %r]; "
            "import test_plane_trace as t; t.counter_parity()"
            % str(HERE))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=HERE.parent,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert "COUNTERS_OK" in out.stdout, out.stderr[-3000:]
