"""The mesh-sharded rounds engine (core/rounds/sharded.py).

In-process tests run on a 1-shard mesh (shard_map machinery, bucket
routing, overflow deferral, trace counts, eviction — all real); the
multi-shard differential parity test runs in a subprocess with 4
virtual devices, replaying ONE concurrent mixed read/write/upgrade
trace through the single-shard engine and the 4-shard engine and
asserting identical per-line version histories in write-through AND
write-back modes (the PR's acceptance trace).
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rounds as rp
from repro.core.rounds import engine

# Same determinism constraints as tests/test_parity_rounds.py: per batch
# a line has either concurrent readers or exactly one writer; upgrades
# (sole-S and contended) and steals happen ACROSS batches.
TRACE = [
    [(0, 0, 0), (1, 0, 0), (2, 1, 0), (3, 2, 0)],          # warm S copies
    [(0, 0, 1), (3, 3, 1), (2, 2, 1)],                     # upgrades+steals
    [(1, 0, 0), (2, 0, 0), (0, 4, 0), (2, 1, 1)],          # PeerRd + sole-S
    [(0, 0, 1), (1, 1, 1), (3, 5, 1)],                     # contended upgr
    [(1, 0, 0), (2, 2, 0), (0, 1, 0), (3, 4, 0)],          # re-read all
    [(2, 3, 1), (1, 5, 1), (0, 2, 1)],                     # steal round
    [(n, l, 0) for n, l in zip(range(4), (0, 1, 2, 3))]
    + [(0, 4, 0), (1, 5, 0)],                              # final audit
]
N_NODES, N_LINES = 4, 8


def _mesh1():
    return jax.make_mesh((1,), ("shards",))


def _ops_tc(state, node, line, isw, wdata=None, **kw):
    # legacy run_ops_to_completion call shape via the DevicePlane facade
    plane = rp.DevicePlane.open(state, kw.pop("mesh", None), **kw)
    res = plane.ops(node, line, isw, wdata)
    if wdata is not None:
        return plane.state, res.version, res.rounds, res.data
    return plane.state, res.version, res.rounds


def _batch_arrays(batch):
    return (np.asarray([b[0] for b in batch], np.int32),
            np.asarray([b[1] for b in batch], np.int32),
            np.asarray([b[2] for b in batch], np.int32))


def _replay(state, *, mesh=None, **kw):
    out = []
    for batch in TRACE:
        node, line, isw = _batch_arrays(batch)
        state, vers, _ = _ops_tc(
            state, node, line, isw, n_nodes=N_NODES, mesh=mesh, **kw)
        rp.check_invariants(state)
        out.append([int(v) for v in vers])
    return out, state


def _wdata(batch_idx, batch, width=2):
    """Deterministic write payloads: lane 0 = batch*16+slot+1, lane 1 =
    the writing node (zeros for reads)."""
    return np.asarray(
        [[batch_idx * 16 + slot + 1, node] if isw else [0] * width
         for slot, (node, _, isw) in enumerate(batch)], np.int32)


def _replay_bytes(state, *, mesh=None, **kw):
    out = []
    for b, batch in enumerate(TRACE):
        node, line, isw = _batch_arrays(batch)
        state, vers, _, data = _ops_tc(
            state, node, line, isw, _wdata(b, batch), n_nodes=N_NODES,
            mesh=mesh, **kw)
        rp.check_invariants(state)
        out.append([(int(v),) + tuple(int(x) for x in d)
                    for v, d in zip(vers, data)])
    return out, state


# ------------------------------------------------------ stripe layout

def test_stripe_state_roundtrip():
    state = rp.make_state(3, 12, write_back=True)
    state["mem_version"] = jnp.arange(12, dtype=jnp.int32)
    back = rp.unstripe_state(rp.stripe_state(state, 4), 4)
    for k in state:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(state[k]), err_msg=k)


def test_stripe_layout_is_home_major():
    # global line l lands on shard l % S at local index l // S
    state = rp.make_state(2, 8)
    state["mem_version"] = jnp.arange(8, dtype=jnp.int32)
    striped = rp.stripe_state(state, 4)
    np.testing.assert_array_equal(
        np.asarray(striped["mem_version"]),
        np.asarray([0, 4, 1, 5, 2, 6, 3, 7]))


# ------------------------------------------- single-shard differential

@pytest.mark.parametrize("write_back", [False, True])
def test_single_shard_mesh_matches_flat_engine(write_back):
    """The sharded engine on a 1-shard mesh IS the flat engine: same
    per-op version history AND bit-identical final state."""
    mesh = _mesh1()
    flat, flat_state = _replay(rp.make_state(N_NODES, N_LINES,
                                             write_back=write_back))
    shd, shd_state = _replay(
        rp.make_sharded_state(N_NODES, N_LINES, mesh,
                              write_back=write_back), mesh=mesh)
    assert flat == shd
    gathered = rp.unshard_state(shd_state, mesh)
    for k in flat_state:
        np.testing.assert_array_equal(np.asarray(flat_state[k]),
                                      np.asarray(gathered[k]), err_msg=k)


@pytest.mark.parametrize("write_back", [False, True])
def test_single_shard_mesh_matches_flat_engine_bytes(write_back):
    """Byte-content differential: the payload-plane trace through the
    flat and 1-shard engines — identical (version, bytes) per op and
    bit-identical payload leaves (mem_data/cache_data included)."""
    mesh = _mesh1()
    flat, flat_state = _replay_bytes(
        rp.make_state(N_NODES, N_LINES, write_back=write_back,
                      payload_width=2))
    shd, shd_state = _replay_bytes(
        rp.make_sharded_state(N_NODES, N_LINES, mesh,
                              write_back=write_back, payload_width=2),
        mesh=mesh)
    assert flat == shd
    gathered = rp.unshard_state(shd_state, mesh)
    assert set(gathered) == set(flat_state) >= {"mem_data", "cache_data"}
    for k in flat_state:
        np.testing.assert_array_equal(np.asarray(flat_state[k]),
                                      np.asarray(gathered[k]), err_msg=k)


# -------------------------------------------------- overflow deferral

def test_bucket_overflow_defers_and_completes():
    """More requests for one home than the bucket holds: the overflow
    defers and respins INSIDE the loop (the caller never sees it), and
    the version history is complete — pre-PR the distributed plane
    punted this to the caller, with zero tests."""
    mesh = _mesh1()
    state = rp.make_sharded_state(2, 4, mesh)
    node = np.asarray([0, 1, 0, 1, 0, 1], np.int32)
    line = np.full(6, 1, np.int32)
    isw = np.ones(6, np.int32)
    state, vers, rounds = _ops_tc(
        state, node, line, isw, n_nodes=2, mesh=mesh, bucket_cap=2,
        max_rounds=64)
    assert sorted(vers.tolist()) == [1, 2, 3, 4, 5, 6]
    assert rounds > 3          # it actually had to respin
    assert int(np.asarray(state["mem_version"])[1]) == 6
    rp.check_invariants(state)


def test_bucket_overflow_defers_and_carries_payloads():
    """The defer/respin path must carry BYTES too: a deferred write's
    payload re-presents with it and lands when its CAS finally wins,
    and each op's reply bytes match its group's serialized write."""
    mesh = _mesh1()
    state = rp.make_sharded_state(2, 4, mesh, payload_width=2)
    node = np.asarray([0, 1, 0, 1, 0, 1], np.int32)
    line = np.full(6, 1, np.int32)
    isw = np.ones(6, np.int32)
    wd = np.stack([10 * np.arange(1, 7), np.arange(1, 7)],
                  axis=1).astype(np.int32)
    state, vers, rounds, data = _ops_tc(
        state, node, line, isw, wd, n_nodes=2, mesh=mesh, bucket_cap=2,
        max_rounds=64)
    assert sorted(vers.tolist()) == [1, 2, 3, 4, 5, 6]
    assert rounds > 3
    rp.check_invariants(state)
    # with cap=2 and alternating nodes, each deferred write re-presents
    # alone and serializes as its own group: its reply bytes are its OWN
    # payload, and memory ends with the last-serialized write's bytes
    for i in range(6):
        assert data[i].tolist() == wd[i].tolist(), i
    last = int(np.argmax(vers))
    assert np.asarray(state["mem_data"])[1].tolist() == wd[last].tolist()


def test_overflow_unserved_slots_report_at_bound():
    mesh = _mesh1()
    state = rp.make_sharded_state(2, 4, mesh)
    node = np.asarray([0, 1], np.int32)
    line = np.asarray([1, 1], np.int32)
    with pytest.raises(RuntimeError, match="not served"):
        _ops_tc(state, node, line, np.ones(2, np.int32),
                                 n_nodes=2, mesh=mesh, bucket_cap=1,
                                 max_rounds=1)


# ------------------------------------------------- trace-count proof

def test_sharded_loop_compiles_once_per_shape():
    mesh = _mesh1()
    state = rp.make_sharded_state(4, 16, mesh)

    def batch(seed):
        r = np.random.default_rng(seed)
        return (r.integers(0, 4, 8).astype(np.int32),
                r.integers(0, 16, 8).astype(np.int32),
                r.integers(0, 2, 8).astype(np.int32))

    state, _, rounds1 = _ops_tc(
        state, *batch(1), n_nodes=4, mesh=mesh)
    key = ("sharded", 1, 4, 16, 8, 8, 64, "ref", False, 0,
           False, False)
    baseline = dict(engine.TRACE_COUNTS)
    assert baseline.get(key, 0) == 1, \
        "sharded driver must trace once per shape"
    total = rounds1
    for seed in range(2, 8):
        state, _, r = _ops_tc(
            state, *batch(seed), n_nodes=4, mesh=mesh)
        total += r
    assert total > 7, "sweep must actually spin multiple rounds"
    assert engine.TRACE_COUNTS[key] == 1
    rp.check_invariants(state)


# ----------------------------------------------------------- eviction

def test_sharded_eviction_write_back_parity():
    mesh = _mesh1()
    flat = rp.make_state(3, 4, write_back=True)
    shd = rp.make_sharded_state(3, 4, mesh, write_back=True)
    node = np.asarray([2], np.int32)
    line = np.asarray([0], np.int32)
    isw = np.ones(1, np.int32)
    flat, _, _ = _ops_tc(flat, node, line, isw,
                                          n_nodes=3)
    shd, _, _ = _ops_tc(shd, node, line, isw,
                                         n_nodes=3, mesh=mesh)
    flat = rp.evict_lines(flat, jnp.asarray(node), jnp.asarray(line))
    shd = rp.evict_lines_sharded(shd, node, line, mesh=mesh)
    gathered = rp.unshard_state(shd, mesh)
    for k in flat:
        np.testing.assert_array_equal(np.asarray(flat[k]),
                                      np.asarray(gathered[k]), err_msg=k)
    assert int(np.asarray(gathered["mem_version"])[0]) == 1  # flushed


# ------------------------------------------ flat vs one-shard drivers

def _bump(data, line):
    return data + jnp.where(line >= 0, 1, 0)[:, None]


def _walk(data, key):
    """Descent transition over a binary tree: lanes 0/1 hold a node's
    two children (-1 at a leaf), the key's parity picks one."""
    nxt = jnp.where(key % 2 == 0, data[:, 0], data[:, 1])
    return nxt < 0, jnp.zeros(nxt.shape, bool), nxt


def _drive(verb, mesh):
    """Three contended batches of ``verb`` through its flat entry point
    (``mesh`` None) or its sharded one, the state carried along."""
    from repro.core.rounds.txn import tuple_program
    rng = np.random.default_rng(7)
    n, l, r = 4, 8, 12
    prog = tuple_program("2pl", 2, 2)
    state = rp.make_state(n, l, write_back=True,
                          payload_width=prog.width if verb == "txn" else 2)
    if verb == "descent":
        kids = np.full((l, 2), -1, np.int32)
        kids[:3] = [[1, 2], [3, 4], [5, 6]]
        state["mem_data"] = jnp.asarray(kids)
    kw = {"n_nodes": n}
    if mesh is not None:
        state = rp.shard_state(state, mesh)
        kw["mesh"] = mesh
    sh = "" if mesh is None else "_sharded"
    outs = []
    for _ in range(3):
        node = rng.integers(0, n, r).astype(np.int32)
        line = rng.integers(0, l, r).astype(np.int32)
        if verb == "ops":
            out = getattr(rp, "run_rounds" + sh)(
                state, node, line, rng.integers(0, 2, r).astype(np.int32),
                rng.integers(1, 99, (r, 2)).astype(np.int32), **kw)
        elif verb == "rmw":
            out = getattr(rp, "run_rmw" + sh)(state, node, line, (),
                                              modify=_bump, **kw)
        elif verb == "descent":
            root = np.where(rng.random(r) < 0.2, -1, 0).astype(np.int32)
            out = getattr(rp, "run_descent" + sh)(
                state, node, line, root, transition=_walk, **kw)
        else:
            b = 6
            glines = np.sort(np.stack([rng.choice(l, 2, replace=False)
                                       for _ in range(b)]), axis=1)
            batch = prog.encode(glines, rng.integers(0, 2, (b, 2, 2)),
                                rng.integers(0, 2, (b, 2, 2)),
                                rng.integers(1, 50, b))
            out = getattr(rp, "run_txn_rounds" + sh)(
                state, node[:b], *batch, program=prog,
                max_iters=4 * b + 16, **kw)
        state = out[0]
        outs.append(jax.device_get(out))
    return outs


@pytest.mark.parametrize("verb", ["ops", "rmw", "descent", "txn"])
def test_flat_and_one_shard_drivers_agree(verb):
    """Each verb's flat driver and its sharded driver on a 1-shard mesh
    run the same loop over their own round step: bit-identical results
    and state, and telemetry dicts equal in keys, shapes, dtypes and
    values."""
    flat = _drive(verb, None)
    shd = _drive(verb, _mesh1())
    at = -2 if verb == "txn" else -1             # the telemetry dict
    for f, s in zip(flat, shd):
        assert jax.tree_util.tree_structure(f) == \
            jax.tree_util.tree_structure(s)
        assert set(f[at]) == set(s[at]) == {
            "occupancy", "deferred", "served_per_home", "replica_served",
            "slot_hits", "slot_whits", "events"}
        assert f[at]["occupancy"].shape == (1, 1)
        assert f[at]["events"].shape == (1, 3)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(f)[0],
                jax.tree_util.tree_leaves(s)):
            a, b = np.asarray(a), np.asarray(b)
            where = jax.tree_util.keystr(path)
            assert a.shape == b.shape and a.dtype == b.dtype, where
            np.testing.assert_array_equal(a, b, err_msg=where)
    # the batches take latches and, but for the read-only descent,
    # contend: some request failed and was retried
    events = sum(o[at]["events"][0] for o in flat)
    assert events[0] > 0 and (events[1] > 0 or verb == "descent")


def test_bucket_overflow_keeps_capacity_requests_intact():
    """A FULL bucket plus overflow/pad slots: the first `cap` requests
    must survive bucketing untouched and the overflow must be counted.
    Non-kept slots once scattered INTO cell (0, cap-1) and could clobber
    a legitimate request whenever its bucket was exactly full."""
    from repro.core.rounds.sharded import _bucket
    cap, n_shards = 2, 2
    req = {
        "node": jnp.asarray([1, 1, 1, 1, 0], jnp.int32),
        "line": jnp.asarray([0, 2, 4, 1, -1], jnp.int32),  # 3x home0 + pad
        "isw": jnp.asarray([11, 22, 33, 44, 0], jnp.int32),
    }
    buckets, order, keep, _, dropped = _bucket(req, n_shards, cap,
                                               fields=tuple(req))
    assert int(dropped) == 1                  # line 4 overflowed home 0
    # home 0's bucket holds exactly the first two home-0 requests
    np.testing.assert_array_equal(np.asarray(buckets["line"][0]), [0, 2])
    np.testing.assert_array_equal(np.asarray(buckets["isw"][0]), [11, 22])
    np.testing.assert_array_equal(np.asarray(buckets["line"][1]),
                                  [1, -1])    # home 1: one request + pad
    # per-original-slot sent mask: line 4 and the pad were NOT sent
    sent = np.asarray(keep)[np.argsort(np.asarray(order))]
    np.testing.assert_array_equal(sent, [True, True, False, True, False])


# ------------------------------------------------------------- guards

def test_pad_ops_pads_to_shard_multiple():
    node, line, isw = rp.pad_ops([0], [1], [1], 4)
    assert line.shape[0] == 4 and (line[1:] == -1).all()
    assert node.shape == isw.shape == line.shape
    n2, l2, w2 = rp.pad_ops([0, 1], [1, 2], [1, 0], 2)
    assert l2.tolist() == [1, 2]             # already a multiple: no-op
    del n2, w2


# --------------------------------------- multi-shard (4 virtual devices)

def test_multi_shard_parity_subprocess():
    """THE acceptance test: one concurrent mixed read/write/upgrade
    trace through the single-shard engine and the 4-shard engine —
    identical per-line version histories, write-through AND write-back;
    plus hot-home overflow completion and the 4-shard trace-count
    proof."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import jax, numpy as np
        from repro.core import rounds as rp
        from repro.core.rounds import engine
        from repro.apps.workloads import (DeviceRoundsConfig,
                                          device_rounds_batches)

        TRACE = {TRACE!r}
        N_NODES, N_LINES = {N_NODES}, {N_LINES}
        mesh = jax.make_mesh((4,), ("shards",))

        def _ops_tc(state, node, line, isw, wdata=None, **kw):
            plane = rp.DevicePlane.open(state, kw.pop("mesh", None), **kw)
            res = plane.ops(node, line, isw, wdata)
            if wdata is not None:
                return plane.state, res.version, res.rounds, res.data
            return plane.state, res.version, res.rounds

        def arrays(batch):
            return (np.asarray([b[0] for b in batch], np.int32),
                    np.asarray([b[1] for b in batch], np.int32),
                    np.asarray([b[2] for b in batch], np.int32))

        def wdata(b, batch):
            return np.asarray(
                [[b * 16 + s + 1, n] if w else [0, 0]
                 for s, (n, _, w) in enumerate(batch)], np.int32)

        for write_back in (False, True):
            # version-only plane AND payload plane: flat vs 4 shards
            flat = rp.make_state(N_NODES, N_LINES, write_back=write_back)
            shd = rp.make_sharded_state(N_NODES, N_LINES, mesh,
                                        write_back=write_back)
            flat_p = rp.make_state(N_NODES, N_LINES,
                                   write_back=write_back, payload_width=2)
            shd_p = rp.make_sharded_state(N_NODES, N_LINES, mesh,
                                          write_back=write_back,
                                          payload_width=2)
            for b, batch in enumerate(TRACE):
                node, line, isw = arrays(batch)
                flat, v1, _ = _ops_tc(
                    flat, node, line, isw, n_nodes=N_NODES)
                shd, v2, _ = _ops_tc(
                    shd, node, line, isw, n_nodes=N_NODES, mesh=mesh)
                assert v1.tolist() == v2.tolist(), (
                    write_back, batch, v1.tolist(), v2.tolist())
                rp.check_invariants(shd)
                wd = wdata(b, batch)
                flat_p, v3, _, d3 = _ops_tc(
                    flat_p, node, line, isw, wd, n_nodes=N_NODES)
                shd_p, v4, _, d4 = _ops_tc(
                    shd_p, node, line, isw, wd, n_nodes=N_NODES,
                    mesh=mesh)
                # byte-content differential: (version, bytes) agree
                # between the flat and 4-shard payload planes, and the
                # payload plane serializes exactly like the bare one
                assert v3.tolist() == v1.tolist()
                assert v4.tolist() == v1.tolist()
                assert d3.tolist() == d4.tolist(), (write_back, batch)
                rp.check_invariants(shd_p)
            g = rp.unshard_state(shd, mesh)
            for k in flat:
                np.testing.assert_array_equal(
                    np.asarray(flat[k]), np.asarray(g[k]), err_msg=k)
            gp = rp.unshard_state(shd_p, mesh)
            assert "mem_data" in gp and "cache_data" in gp
            for k in flat_p:
                np.testing.assert_array_equal(
                    np.asarray(flat_p[k]), np.asarray(gp[k]), err_msg=k)

        # hot home + tiny buckets: every source shard overflows toward
        # home 0, the loop defers and respins, history stays complete
        state = rp.make_sharded_state(4, 8, mesh)
        R = 16
        node = np.asarray([i % 4 for i in range(R)], np.int32)
        line = np.zeros(R, np.int32)
        isw = np.ones(R, np.int32)
        state, vers, rounds = _ops_tc(
            state, node, line, isw, n_nodes=4, mesh=mesh,
            bucket_cap=1, max_rounds=128)
        assert sorted(vers.tolist()) == list(range(1, R + 1))
        rp.check_invariants(state)

        # same hot-home overflow storm, payload-carrying: the deferred
        # slots respin WITH their bytes, and the final memory image is
        # the payload of whichever write serialized last
        state_p = rp.make_sharded_state(4, 8, mesh, payload_width=2)
        wd = np.stack([7 * np.arange(1, R + 1), np.arange(1, R + 1)],
                      axis=1).astype(np.int32)
        state_p, vers_p, _, data_p = _ops_tc(
            state_p, node, line, isw, wd, n_nodes=4, mesh=mesh,
            bucket_cap=1, max_rounds=256)
        assert sorted(vers_p.tolist()) == list(range(1, R + 1))
        rp.check_invariants(state_p)
        # the reply of the last-serialized slot carries its group's
        # final bytes — exactly what write-through left in memory
        md = rp.unshard_state(state_p, mesh)["mem_data"]
        last = int(np.argmax(vers_p))
        assert np.asarray(md)[0].tolist() == data_p[last].tolist()

        # trace-count proof at 4 shards: shapes repeat, no retrace
        key = ("sharded", 4, 4, 8, 16, 1, 128, "ref", False, 0,
               False, False)
        assert engine.TRACE_COUNTS.get(key, 0) == 1
        state2 = rp.make_sharded_state(4, 8, mesh)
        state2, _, _ = _ops_tc(
            state2, node, line, isw, n_nodes=4, mesh=mesh,
            bucket_cap=1, max_rounds=128)
        assert engine.TRACE_COUNTS[key] == 1

        # static-shape guards need a real multi-device mesh to fire
        try:
            rp.run_rounds_sharded(
                rp.make_sharded_state(2, 8, mesh),
                np.zeros(3, np.int32), np.zeros(3, np.int32),
                np.zeros(3, np.int32), mesh=mesh, n_nodes=2)
            raise SystemExit("indivisible R accepted")
        except ValueError as e:
            assert "not divisible" in str(e)
        try:
            rp.shard_state(rp.make_state(2, 6), mesh)
            raise SystemExit("indivisible n_lines accepted")
        except ValueError as e:
            assert "not divisible" in str(e)
        assert rp.make_sharded_state(2, 6, mesh)["words"].shape[0] == 8

        # workload soup: Zipf/YCSB generator batches, invariants hold
        cfg = DeviceRoundsConfig(n_nodes=4, n_lines=16, r_slots=12,
                                 read_ratio=0.5, zipf_theta=0.9,
                                 iters=4)
        soup = rp.make_sharded_state(4, 16, mesh, write_back=True)
        for node, line, isw in device_rounds_batches(cfg, seed=5):
            soup, _, _ = _ops_tc(
                soup, node, line, isw, n_nodes=4, mesh=mesh,
                max_rounds=128)
            rp.check_invariants(soup)

        # payload soup on 4 shards: random mixed ops with random bytes,
        # data/version agreement checked on every materialized state
        cfgp = DeviceRoundsConfig(n_nodes=4, n_lines=16, r_slots=12,
                                  read_ratio=0.5, zipf_theta=0.9,
                                  iters=4, payload_width=3)
        soup_p = rp.make_sharded_state(4, 16, mesh, write_back=True,
                                       payload_width=3)
        for node, line, isw, wd in device_rounds_batches(cfgp, seed=6):
            soup_p, _, _, _ = _ops_tc(
                soup_p, node, line, isw, wd, n_nodes=4, mesh=mesh,
                max_rounds=128)
            rp.check_invariants(soup_p)

        # mesh-backed SELCCKVPool on the rounds data plane: a mixed
        # append/read trace vs a host-replayed numpy oracle — reads
        # must return the exact bytes the serialized appends left
        from repro.dsm.kvpool import KVPoolConfig, SELCCKVPool
        kcfg = KVPoolConfig(n_pages=8, page_size=4, n_kv_heads=1,
                            head_dim=8, n_replicas=4, cache_slots=4,
                            dtype="float32")
        kpool = SELCCKVPool(kcfg, mesh=mesh)
        pages = kpool.allocate(8)
        kpool.open_rounds_plane()
        ok = np.zeros((8, 4, 1, 8), np.float32)
        ov = np.zeros((8, 4, 1, 8), np.float32)
        rng = np.random.default_rng(9)
        for t in range(10):
            rep = t % 4
            pg = np.asarray([pages[t % 8], pages[(t + 3) % 8]], np.int32)
            off = np.asarray([t % 4, (t + 1) % 4], np.int32)
            kn = rng.normal(size=(2, 1, 8)).astype(np.float32)
            vn = rng.normal(size=(2, 1, 8)).astype(np.float32)
            kpool.append(pg, off, kn, vn, replica=rep)
            for i in range(2):
                ok[pg[i], off[i]] = kn[i]
                ov[pg[i], off[i]] = vn[i]
            reader = (t + 1) % 4
            rd = np.asarray([pages[t % 8], pages[(t + 5) % 8]], np.int32)
            k, v, _ = kpool.read(reader, rd)
            np.testing.assert_array_equal(np.asarray(k), ok[rd])
            np.testing.assert_array_equal(np.asarray(v), ov[rd])
        # attention consumes the same plane bytes
        q = rng.normal(size=(1, 2, 8)).astype(np.float32)
        out = kpool.attend(q, np.asarray([[pages[0], pages[1]]],
                                         np.int32),
                           np.asarray([8], np.int32))
        assert np.isfinite(np.asarray(out)).all()
        print("SHARDED_PARITY_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_PARITY_OK" in out.stdout, out.stderr[-3000:]


def test_failing_writers_on_one_local_line_subprocess():
    """Two nodes fail writes on the same line in one round, on a slab
    where that line's LOCAL index differs from its global id (line 6 is
    local line 1 of shard 2, line 2 local line 0): the homes' boundaries
    kill, flush and rebuild words exactly as the flat engine does.  One
    slot per source shard keeps the homes' slot order the flat order, so
    the latch contention resolves the same way on both planes."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import jax, numpy as np
        from repro.core import rounds as rp

        mesh = jax.make_mesh((4,), ("shards",))
        TRACE = [
            [(0, 6, 0), (1, 6, 0), (2, 6, 0), (3, 2, 0)],
            # racing upgraders on line 6 kill each other and node 2's
            # copy, while node 3 upgrades line 2 on the same slab
            [(0, 6, 1), (1, 6, 1), (3, 2, 1), (2, 3, 0)],
            # a failed write and a failed read on each of lines 6 and 2
            # kill their (dirty) M holders
            [(2, 6, 0), (3, 6, 1), (1, 2, 1), (0, 2, 0)],
        ]
        for write_back in (False, True):
            for width in (0, 2):
                flat = rp.DevicePlane.open(rp.make_state(
                    4, 8, write_back=write_back, payload_width=width))
                shd = rp.DevicePlane.open(rp.make_sharded_state(
                    4, 8, mesh, write_back=write_back,
                    payload_width=width), mesh)
                for b, batch in enumerate(TRACE):
                    node, line, isw = (np.asarray(col, np.int32)
                                       for col in zip(*batch))
                    wd = (np.stack([isw * (10 * b + np.arange(4) + 1),
                                    node], axis=1).astype(np.int32)
                          if width else None)
                    r1 = flat.ops(node, line, isw, wd)
                    r2 = shd.ops(node, line, isw, wd)
                    assert r1.version.tolist() == r2.version.tolist(), (
                        write_back, width, b)
                    if width:
                        assert r1.data.tolist() == r2.data.tolist()
                    assert (int(r1.telemetry.latch_failed)
                            == int(r2.telemetry.latch_failed))
                    if b == 1:
                        assert int(r2.telemetry.latch_failed) >= 2
                    shd.check()
                g = shd.flat_state()
                for k in flat.state:
                    np.testing.assert_array_equal(
                        np.asarray(flat.state[k]), np.asarray(g[k]),
                        err_msg=k)
        print("LOCAL_LINE_PARITY_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert "LOCAL_LINE_PARITY_OK" in out.stdout, out.stderr[-3000:]
