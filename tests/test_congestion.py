"""Congestion paths of the sharded plane: telemetry counters, dynamic
re-homing, and read-replica lines (core/rounds/{sharded,placement}.py,
DevicePlane.rehome/replicate).

In-process tests run on a 1-shard mesh (the counters, the replica
serve/invalidate cycle, the slab-row exchange and its trace count are
all real there); the migration differential — flat oracle vs a 4-shard
plane that re-homes hot lines MID-STREAM — runs in a subprocess with 4
virtual devices, asserting bit-identical version histories and payload
images (the ISSUE 9 acceptance trace).
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.rounds.placement import plan_rehome, plan_replication

jax = pytest.importorskip("jax")

from repro.core import rounds as rp                      # noqa: E402
from repro.core.rounds import engine                     # noqa: E402

# Determinism: per batch a line has either concurrent readers or exactly
# one writer (same constraint as tests/test_sharded_rounds.TRACE), so
# version histories are insensitive to how overflow splits a batch
# across rounds — which is exactly what re-homing perturbs.
TRACE = [
    [(0, 0, 0), (1, 0, 0), (2, 1, 0), (3, 2, 0)],
    [(0, 0, 1), (3, 3, 1), (2, 2, 1)],
    [(1, 0, 0), (2, 0, 0), (0, 4, 0), (2, 1, 1)],
    [(0, 0, 1), (1, 1, 1), (3, 5, 1)],
    [(1, 0, 0), (2, 2, 0), (0, 1, 0), (3, 4, 0)],
    [(2, 3, 1), (1, 5, 1), (0, 2, 1)],
    [(n, l, 0) for n, l in zip(range(4), (0, 1, 2, 3))]
    + [(0, 4, 0), (1, 5, 0)],
]
N_NODES, N_LINES = 4, 8


def _mesh1():
    return jax.make_mesh((1,), ("shards",))


def _i32(*xs):
    return np.asarray(xs, np.int32)


# ------------------------------------------------------- telemetry

def test_hot_home_overflow_reports_telemetry():
    """bucket_cap=1 under 4 ops: the fused loop defers and respins, and
    the carry-accumulated counters surface it — deferrals > 0, every op
    accounted for in served_per_home, per-line hit/write-hit counts."""
    mesh = _mesh1()
    state = rp.make_sharded_state(2, 8, mesh)
    plane = rp.DevicePlane.open(state, mesh, n_nodes=2, bucket_cap=1,
                                max_rounds=128)
    res = plane.ops(_i32(0, 1, 0, 1), _i32(0, 0, 1, 1),
                    _i32(1, 1, 1, 1))
    s = res.telemetry
    assert sorted(s) == ["deferred", "latch_failed", "latch_requests",
                         "line_hits", "line_whits", "local_hits",
                         "occupancy", "replica_served",
                         "served_per_home"]
    assert s["occupancy"].shape == s["deferred"].shape == (1, 1)
    # one bucket slot per round for 4 ops: at least 3 deferrals
    assert int(s["deferred"].sum()) >= 3
    assert int(s["occupancy"].sum()) >= 4          # every op sent once+
    assert s["served_per_home"].tolist() == [4]
    assert int(s["replica_served"].sum()) == 0     # no replica plane
    assert s["line_hits"].tolist() == [2, 2, 0, 0, 0, 0, 0, 0]
    assert s["line_whits"].tolist() == [2, 2, 0, 0, 0, 0, 0, 0]
    plane.check()
    # reads don't count as write hits
    res = plane.ops(_i32(0, 1), _i32(2, 3), _i32(0, 0))
    assert res.telemetry["line_hits"].tolist() == [0, 0, 1, 1, 0, 0, 0, 0]
    assert int(res.telemetry["line_whits"].sum()) == 0


def test_txn_batch_carries_telemetry():
    from repro.core.rounds.txn import txn_payload_width
    mesh = _mesh1()
    w = txn_payload_width(1)
    state = rp.make_sharded_state(2, 4, mesh, payload_width=w)
    plane = rp.DevicePlane.open(state, mesh, n_nodes=2)
    out = plane.txn(_i32(0, 1), np.asarray([[0], [1]], np.int32),
                    np.ones((2, 1, 1), np.int32),
                    np.ones((2, 1, 1), np.int32), _i32(1, 2),
                    algo="2pl")
    assert out.decision.all()
    assert int(out.telemetry["served_per_home"].sum()) > 0
    assert out.telemetry["line_hits"].shape == (4,)


# ------------------------------------------------------- re-homing

def test_rehome_exchange_moves_slab_rows_coherently():
    """Swapping two physical slots permutes every line-indexed leaf and
    installs the new directory; the line-major view (unstripe through
    the directory) is unchanged, so the protocol state is untouched."""
    mesh = _mesh1()
    state = rp.make_sharded_state(2, 4, mesh, payload_width=1,
                                  home_directory=True)
    plane = rp.DevicePlane.open(state, mesh, n_nodes=2)
    plane.ops(_i32(0, 0, 0, 0), _i32(0, 1, 2, 3), _i32(1, 1, 1, 1),
              np.asarray([[10], [11], [12], [13]], np.int32))
    before = {k: np.asarray(v).copy()
              for k, v in plane.flat_state().items()}
    new_home = _i32(1, 0, 2, 3)
    moved = tuple(sorted(k for k in plane.state
                         if k not in ("home",)))
    key = ("rehome", 1, 4, 2, moved, False)
    for _ in range(2):                     # same shape: ONE trace
        plane.state = rp.rehome_exchange(
            plane.state, _i32(0, 1), _i32(1, 0), new_home, mesh=mesh)
        new_home = _i32(0, 1, 2, 3)        # swap back on 2nd pass
    assert engine.TRACE_COUNTS.get(key, 0) == 1, \
        "rehome exchange must trace once per shape"
    plane.check()
    after = plane.flat_state()
    for k in before:
        np.testing.assert_array_equal(np.asarray(after[k]), before[k],
                                      err_msg=k)
    # and the protocol still runs on the migrated layout
    res = plane.ops(_i32(1, 1), _i32(0, 1), _i32(0, 0))
    assert res.data[:, 0].tolist() == [10, 11]
    plane.check()


def test_rehome_verb_guards():
    mesh = _mesh1()
    plane = rp.DevicePlane.open(rp.make_sharded_state(2, 4, mesh),
                                mesh, n_nodes=2)
    with pytest.raises(ValueError, match="home-directory"):
        plane.rehome([0], [0])
    state = rp.make_sharded_state(2, 4, mesh, home_directory=True)
    plane = rp.DevicePlane.open(state, mesh, n_nodes=2)
    with pytest.raises(ValueError, match="out of range"):
        plane.rehome([0], [3])             # 1 shard: only home 0
    assert plane.rehome([0], [0]) == 0     # already home: no-op


def test_plan_rehome_greedy_balances_load():
    n_shards = 4
    l = 16
    perm = np.arange(l)                    # identity: home = line % 4
    hits = np.zeros(l, np.int64)
    hits[[0, 4, 8]] = [90, 60, 30]         # all hot lines home on 0
    hits[[1, 5]] = [2, 1]
    lines, homes, victims = plan_rehome(hits, perm, n_shards,
                                        max_moves=8)
    assert len(lines) > 0
    assert 0 not in set(homes.tolist())    # moves go OFF the hot shard
    for a, h, v in zip(lines, homes, victims):
        assert perm[a] % n_shards == 0     # hot shard donates
        assert perm[v] % n_shards == h     # victim lives on the target
    # applying the plan strictly shrinks the max/min load gap
    home = perm % n_shards
    loads0 = np.bincount(home, weights=hits, minlength=n_shards)
    for a, h, v in zip(lines, homes, victims):
        home[a], home[v] = h, 0
    loads1 = np.bincount(home, weights=hits, minlength=n_shards)
    assert loads1.max() - loads1.min() < loads0.max() - loads0.min()
    # no gain -> empty plan
    ln, _, _ = plan_rehome(np.ones(l, np.int64), perm, n_shards)
    assert ln.size == 0


def test_plan_replication_picks_read_mostly_lines():
    hits = np.asarray([100, 80, 50, 3, 0])
    whits = np.asarray([0, 30, 1, 0, 0])
    picks = plan_replication(hits, whits, top_k=2, max_write_frac=0.05)
    assert picks.tolist() == [0, 2]        # 1 writes too much, 4 cold
    assert plan_replication(hits, whits, top_k=0).size == 0


# ---------------------------------------------------- read replicas

def test_replicated_line_serves_locally_and_invalidates_on_write():
    mesh = _mesh1()
    state = rp.make_sharded_state(3, 4, mesh, payload_width=1,
                                  replicas=True)
    plane = rp.DevicePlane.open(state, mesh, n_nodes=3)
    plane.ops(_i32(0), _i32(0), _i32(1), np.asarray([[7]], np.int32))
    plane.evict(_i32(0), _i32(0))          # drop the M holder
    plane.replicate([0])
    assert bool(np.asarray(plane.state["replica_ok"])[0])
    res = plane.ops(_i32(1, 2), _i32(0, 0), _i32(0, 0))
    assert int(res.telemetry["replica_served"].sum()) == 2
    assert res.version.tolist() == [1, 1]
    assert res.data[:, 0].tolist() == [7, 7]
    # replica-served reads never hit the home slot
    assert int(res.telemetry["line_hits"].sum()) == 0
    plane.check()
    # a granted write invalidates through the normal MSI path
    res = plane.ops(_i32(1), _i32(0), _i32(1), np.asarray([[8]],
                                                          np.int32))
    assert not bool(np.asarray(plane.state["replica_ok"])[0])
    plane.check()
    # once the writer releases, the next round's boundary refresh
    # republishes: the first read routes (and republishes), the one
    # after serves the NEW bytes locally
    plane.evict(_i32(1), _i32(0))
    res = plane.ops(_i32(2, 0), _i32(0, 0), _i32(0, 0))
    assert res.version.tolist() == [2, 2]
    assert res.data[:, 0].tolist() == [8, 8]
    assert int(res.telemetry["replica_served"].sum()) == 0
    assert bool(np.asarray(plane.state["replica_ok"])[0])
    res = plane.ops(_i32(2), _i32(0), _i32(0))
    assert res.version.tolist() == [2]
    assert res.data[:, 0].tolist() == [8]
    assert int(res.telemetry["replica_served"].sum()) == 1
    plane.check()
    # replicate(enable=False) drops the mark: reads route again
    plane.replicate([0], enable=False)
    res = plane.ops(_i32(1), _i32(0), _i32(0))
    assert int(res.telemetry["replica_served"].sum()) == 0
    assert int(res.telemetry["line_hits"].sum()) == 1
    plane.check()


def test_replicate_verb_guards():
    mesh = _mesh1()
    plane = rp.DevicePlane.open(rp.make_sharded_state(2, 4, mesh),
                                mesh, n_nodes=2)
    with pytest.raises(ValueError, match="replica-plane"):
        plane.replicate([0])


# ------------------------------- migration differential (4 devices)

def test_rehome_differential_subprocess():
    """THE acceptance test: the TRACE replayed on a flat oracle and a
    4-shard home-directory plane that migrates hot lines MID-STREAM —
    bit-identical version histories, payload bytes, and final images
    (migration moves rows, never protocol state); plus the 4-shard
    replica serve/invalidate cycle and defer-storm telemetry."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import jax, numpy as np
        from repro.core import rounds as rp

        TRACE = {TRACE!r}
        N_NODES, N_LINES = {N_NODES}, {N_LINES}
        mesh = jax.make_mesh((4,), ("shards",))

        def arrays(batch):
            return (np.asarray([b[0] for b in batch], np.int32),
                    np.asarray([b[1] for b in batch], np.int32),
                    np.asarray([b[2] for b in batch], np.int32))

        def wdata(b, batch):
            return np.asarray(
                [[b * 16 + s + 1, n] if w else [0, 0]
                 for s, (n, _, w) in enumerate(batch)], np.int32)

        for write_back in (False, True):
            flat = rp.DevicePlane.open(
                rp.make_state(N_NODES, N_LINES, write_back=write_back,
                              payload_width=2),
                n_nodes=N_NODES)
            shd = rp.DevicePlane.open(
                rp.make_sharded_state(N_NODES, N_LINES, mesh,
                                      write_back=write_back,
                                      payload_width=2,
                                      home_directory=True),
                mesh, n_nodes=N_NODES)
            hits = np.zeros(N_LINES, np.int64)
            for b, batch in enumerate(TRACE):
                node, line, isw = arrays(batch)
                wd = wdata(b, batch)
                rf = flat.ops(node, line, isw, wd, max_rounds=128)
                rs = shd.ops(node, line, isw, wd, max_rounds=128)
                assert rf.version.tolist() == rs.version.tolist(), (
                    write_back, b)
                assert rf.data.tolist() == rs.data.tolist(), (
                    write_back, b)
                hits += rs.telemetry["line_hits"].astype(np.int64)
                shd.check()
                if b == 2:
                    # migrate the observed-hottest lines mid-stream
                    perm = np.asarray(shd.state["home"])
                    lines, homes, victims = rp.plan_rehome(
                        hits, perm, 4, max_moves=4)
                    moved = shd.rehome(lines, homes, victims)
                    assert moved == len(lines)
                    shd.check()
                if b == 4:
                    # and once more without explicit victims
                    moved = shd.rehome(np.asarray([0, 3]),
                                       np.asarray([2, 1]))
                    shd.check()
            perm = np.asarray(shd.state["home"])
            assert sorted(perm.tolist()) == list(range(N_LINES))
            assert (perm != np.arange(N_LINES)).any(), \\
                "no migration happened — differential is vacuous"
            g = shd.flat_state()
            for k in flat.state:
                np.testing.assert_array_equal(
                    np.asarray(flat.state[k]), np.asarray(g[k]),
                    err_msg=f"{{write_back}}:{{k}}")

        # defer storm at 4 shards: all ops to one home, cap 1 — the
        # telemetry rows localize the congestion to that home column
        state = rp.make_sharded_state(4, 8, mesh)
        plane = rp.DevicePlane.open(state, mesh, n_nodes=4,
                                    bucket_cap=1, max_rounds=256)
        R = 16
        node = np.asarray([i % 4 for i in range(R)], np.int32)
        line = np.zeros(R, np.int32)       # all home shard 0
        res = plane.ops(node, line, np.ones(R, np.int32))
        s = res.telemetry
        assert s["deferred"].shape == (4, 4)
        assert int(s["deferred"][:, 0].sum()) > 0
        assert int(s["deferred"][:, 1:].sum()) == 0
        assert s["served_per_home"].tolist() == [R, 0, 0, 0]
        assert int(s["line_hits"][0]) == R
        plane.check()

        # 4-shard replica cycle: remote readers serve from their own
        # shard, a write kills the image, the refresh republishes
        state = rp.make_sharded_state(4, 8, mesh, payload_width=1,
                                      replicas=True,
                                      home_directory=True)
        plane = rp.DevicePlane.open(state, mesh, n_nodes=4)
        plane.ops(np.asarray([0], np.int32), np.asarray([0], np.int32),
                  np.asarray([1], np.int32),
                  np.asarray([[41]], np.int32))
        plane.evict(np.asarray([0], np.int32),
                    np.asarray([0], np.int32))
        plane.replicate([0])
        res = plane.ops(np.asarray([1, 2, 3], np.int32),
                        np.zeros(3, np.int32), np.zeros(3, np.int32))
        assert res.version.tolist() == [1, 1, 1]
        assert res.data[:, 0].tolist() == [41, 41, 41]
        assert int(res.telemetry["replica_served"].sum()) == 3
        plane.check()
        res = plane.ops(np.asarray([2], np.int32),
                        np.asarray([0], np.int32),
                        np.asarray([1], np.int32),
                        np.asarray([[42]], np.int32))
        assert not bool(np.asarray(plane.state["replica_ok"])[0])
        plane.evict(np.asarray([2], np.int32),
                    np.asarray([0], np.int32))
        res = plane.ops(np.asarray([1, 3], np.int32),
                        np.zeros(2, np.int32), np.zeros(2, np.int32))
        assert res.version.tolist() == [2, 2]
        assert res.data[:, 0].tolist() == [42, 42]
        plane.check()

        # replicated lines survive a migration: the replica plane keys
        # by LINE id, so re-homing the line keeps the image serving
        plane.rehome([0], [3])
        res = plane.ops(np.asarray([1], np.int32),
                        np.zeros(1, np.int32), np.zeros(1, np.int32))
        assert res.version.tolist() == [2]
        assert int(res.telemetry["replica_served"].sum()) == 1
        plane.check()
        print("CONGESTION_PARITY_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert "CONGESTION_PARITY_OK" in out.stdout, out.stderr[-3000:]
