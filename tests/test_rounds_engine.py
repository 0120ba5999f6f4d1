"""The device-resident rounds engine: upgrades, write-back, coalescing,
the fused spin loop (trace-count proof: no per-round retrace), eviction
write-back, and the capacity guards — under both latch backends."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coherence as co
from repro.core import rounds as rp
from repro.core.rounds import engine
from repro.kernels.latch_ops.ops import OP_CAS, OP_FAA, apply_batch


def _ops(node, line, isw):
    return (np.asarray(node, np.int32), np.asarray(line, np.int32),
            np.asarray(isw, np.int32))


def _ops_tc(state, node, line, isw, wdata=None, **kw):
    # the legacy run_ops_to_completion call shape, via the DevicePlane
    # facade (the deprecated wrapper itself is covered in test_plane.py)
    plane = rp.DevicePlane.open(state, kw.pop("mesh", None), **kw)
    res = plane.ops(node, line, isw, wdata)
    if wdata is not None:
        return plane.state, res.version, res.rounds, res.data
    return plane.state, res.version, res.rounds


def _rmw_tc(state, node, line, modify, operands=(), **kw):
    plane = rp.DevicePlane.open(state, kw.pop("mesh", None), **kw)
    res = plane.rmw(node, line, modify=modify, operands=operands)
    return plane.state, res.version, res.rounds, res.data


def _run(state, node, line, isw, n_nodes, **kw):
    return _ops_tc(state, *_ops(node, line, isw), n_nodes=n_nodes, **kw)


# ------------------------------------------------------------- upgrades

@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_sole_reader_upgrades_in_place(backend):
    state = rp.make_state(4, 8)
    state, v, _ = _run(state, [2], [5], [0], 4, backend=backend)
    assert v[0] == 0
    state, v, rounds = _run(state, [2], [5], [1], 4, backend=backend)
    assert v[0] == 1 and rounds == 1          # S->X CAS, single round
    cs = np.asarray(state["cache_state"])
    assert cs[2, 5] == rp.M
    # writer byte landed in the directory word
    assert int(np.asarray(state["words"])[5, 0]) == int(
        jnp.asarray(co.writer_field_hi(2)))
    rp.check_invariants(state)


def test_upgrade_with_other_readers_evicts_then_wins():
    state = rp.make_state(4, 8)
    state, _, _ = _run(state, [0, 1, 3], [5, 5, 5], [0, 0, 0], 4)
    state, v, rounds = _run(state, [0], [5], [1], 4)
    assert v[0] == 1 and rounds == 2          # PeerUpgr round + CAS round
    cs = np.asarray(state["cache_state"])
    assert cs[0, 5] == rp.M and cs[1, 5] == rp.I and cs[3, 5] == rp.I
    rp.check_invariants(state)


def test_racing_upgraders_converge():
    # both S holders upgrade in the same call: they kill each other,
    # fall back to fresh acquisition, and serialize (Algorithm 2)
    state = rp.make_state(4, 8)
    state, _, _ = _run(state, [0, 1], [3, 3], [0, 0], 4)
    state, v, _ = _run(state, [0, 1], [3, 3], [1, 1], 4)
    assert sorted(v.tolist()) == [1, 2]
    rp.check_invariants(state)


# ----------------------------------------------------------- coalescing

@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_multi_op_per_node_coalesces(backend):
    # two writes + one read by ONE node on ONE line in a single call:
    # pre-refactor drivers had to hand-serialize these across rounds
    state = rp.make_state(2, 4)
    state, v, rounds = _run(state, [0, 0, 0], [2, 2, 2], [1, 1, 0], 2,
                            backend=backend)
    assert rounds == 1
    assert v.tolist() == [1, 2, 2]            # writes serialize; read
    assert np.asarray(state["mem_version"])[2] == 2   # sees both writes
    rp.check_invariants(state)


def test_coalesced_groups_still_contend_across_nodes():
    state = rp.make_state(3, 4)
    node = [0, 0, 1, 1, 2]
    line = [1, 1, 1, 1, 1]
    isw = [1, 1, 1, 1, 0]
    state, v, _ = _run(state, node, line, isw, 3)
    # 4 writes total, serialized in two groups of 2; the read sees some
    # complete group boundary
    assert np.asarray(state["mem_version"])[1] == 4
    assert sorted(v.tolist()[:4]) == [1, 2, 3, 4]
    assert v[4] in (0, 2, 4)
    rp.check_invariants(state)


# ----------------------------------------------------------- write-back

def test_write_back_defers_memory_and_flushes_on_downgrade():
    state = rp.make_state(3, 4, write_back=True)
    state, v1, _ = _run(state, [0], [1], [1], 3)
    state, v2, _ = _run(state, [0], [1], [1], 3)
    assert (v1[0], v2[0]) == (1, 2)
    assert np.asarray(state["mem_version"])[1] == 0       # dirty, not flushed
    assert bool(np.asarray(state["dirty"])[0, 1])
    rp.check_invariants(state)
    # a reader forces downgrade + write-back
    state, v3, _ = _run(state, [1], [1], [0], 3)
    assert v3[0] == 2
    assert np.asarray(state["mem_version"])[1] == 2
    assert not np.asarray(state["dirty"]).any()
    rp.check_invariants(state)


def test_write_back_flushes_on_invalidation():
    state = rp.make_state(3, 4, write_back=True)
    state, _, _ = _run(state, [0], [2], [1], 3)
    state, v, _ = _run(state, [1], [2], [1], 3)   # steals the latch
    assert v[0] == 2                               # saw the flushed write
    assert np.asarray(state["mem_version"])[2] >= 1
    rp.check_invariants(state)


def test_eviction_write_back():
    state = rp.make_state(3, 4, write_back=True)
    state, _, _ = _run(state, [2], [0], [1], 3)
    assert np.asarray(state["mem_version"])[0] == 0
    state = rp.evict_lines(state, jnp.asarray([2], jnp.int32),
                           jnp.asarray([0], jnp.int32))
    assert np.asarray(state["mem_version"])[0] == 1       # flushed
    assert np.asarray(state["cache_state"])[2, 0] == rp.I
    assert not np.asarray(state["dirty"]).any()
    rp.check_invariants(state)


# ------------------------------------------- fused driver: no retraces

def test_run_rounds_compiles_once_per_shape():
    state = rp.make_state(4, 16)
    rng = np.random.default_rng(0)

    def batch(seed):
        r = np.random.default_rng(seed)
        return (r.integers(0, 4, 8).astype(np.int32),
                r.integers(0, 16, 8).astype(np.int32),
                r.integers(0, 2, 8).astype(np.int32))

    state, _, rounds1 = _run(state, *batch(1), 4)
    round_key = ("round", 4, 16, 8, "ref", False, 0)
    driver_key = ("driver", 4, 8, 64, "ref", False, 0)
    baseline = dict(engine.TRACE_COUNTS)
    assert baseline.get(round_key, 0) == 1, \
        "round engine must trace once inside the while_loop body"
    assert baseline.get(driver_key, 0) == 1
    # more calls, same shapes, different data and round counts: NO retrace
    total_rounds = rounds1
    for seed in range(2, 8):
        state, _, r = _run(state, *batch(seed), 4)
        total_rounds += r
    assert total_rounds > 7, "sweep must actually spin multiple rounds"
    assert engine.TRACE_COUNTS[round_key] == baseline[round_key]
    assert engine.TRACE_COUNTS[driver_key] == baseline[driver_key]
    del rng
    rp.check_invariants(state)


def test_run_rounds_reports_unserved_on_bound():
    state = rp.make_state(2, 4)
    # two nodes fight over one line with max_rounds=1: someone is unserved
    with pytest.raises(RuntimeError, match="not served"):
        _ops_tc(state, *_ops([0, 1], [1, 1], [1, 1]),
                                 n_nodes=2, max_rounds=1)


# ---------------------------------------------------- random soup + guards

@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("write_back", [False, True])
def test_random_mixed_trace_invariants(backend, write_back):
    rng = np.random.default_rng(5)
    n_nodes, n_lines = 4, 16
    state = rp.make_state(n_nodes, n_lines, write_back=write_back)
    for _ in range(4):
        r = 12
        node = rng.integers(0, n_nodes, r).astype(np.int32)
        line = rng.integers(-1, n_lines, r).astype(np.int32)
        isw = rng.integers(0, 2, r).astype(np.int32)
        state, _, _ = _ops_tc(
            state, node, line, isw, n_nodes=n_nodes, max_rounds=128,
            backend=backend)
        rp.check_invariants(state)


# --------------------------------------------------------- payload plane

def _wd(rows):
    return np.asarray(rows, np.int32)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_payload_write_apply_and_fetch_on_grant(backend):
    state = rp.make_state(3, 4, payload_width=2)
    assert rp.payload_width(state) == 2
    # write lands bytes in the writer's cache AND (write-through) memory
    state, v, _, d = _ops_tc(
        state, *_ops([0], [1], [1]), _wd([[7, 9]]), n_nodes=3,
        backend=backend)
    assert d.tolist() == [[7, 9]]
    assert np.asarray(state["mem_data"])[1].tolist() == [7, 9]
    rp.check_invariants(state)
    # another node's read miss fetches the bytes on grant
    state, v, _, d = _ops_tc(
        state, *_ops([2], [1], [0]), _wd([[0, 0]]), n_nodes=3,
        backend=backend)
    assert d.tolist() == [[7, 9]]
    assert np.asarray(state["cache_data"])[2, 1].tolist() == [7, 9]
    rp.check_invariants(state)


def test_payload_coalesced_group_serializes_to_last_write():
    state = rp.make_state(2, 4, payload_width=1)
    # one node, two writes + one read on one line in a single call: the
    # group serializes in slot order, so slot 1's bytes are final and
    # EVERY slot's reply carries them (reads observe start+k)
    state, v, _, d = _ops_tc(
        state, *_ops([0, 0, 0], [2, 2, 2], [1, 1, 0]),
        _wd([[11], [22], [0]]), n_nodes=2)
    assert v.tolist() == [1, 2, 2]
    assert d.tolist() == [[22], [22], [22]]
    assert np.asarray(state["mem_data"])[2].tolist() == [22]
    rp.check_invariants(state)


def test_payload_write_back_flush_paths():
    state = rp.make_state(3, 4, write_back=True, payload_width=2)
    state, _, _, _ = _ops_tc(
        state, *_ops([0], [1], [1]), _wd([[5, 6]]), n_nodes=3)
    # dirty: memory bytes still stale
    assert np.asarray(state["mem_data"])[1].tolist() == [0, 0]
    rp.check_invariants(state)
    # a reader forces downgrade: bytes flush WITH the version, and the
    # reader's reply carries them
    state, v, _, d = _ops_tc(
        state, *_ops([1], [1], [0]), _wd([[0, 0]]), n_nodes=3)
    assert d.tolist() == [[5, 6]]
    assert np.asarray(state["mem_data"])[1].tolist() == [5, 6]
    rp.check_invariants(state)
    # invalidation (steal) flushes too: the stealing writer starts from
    # the flushed memory image
    state, _, _, _ = _ops_tc(
        state, *_ops([2], [1], [1]), _wd([[8, 8]]), n_nodes=3)
    rp.check_invariants(state)
    assert np.asarray(state["mem_data"])[1].tolist() == [5, 6]  # dirty again
    state = rp.evict_lines(state, jnp.asarray([2], jnp.int32),
                           jnp.asarray([1], jnp.int32))
    assert np.asarray(state["mem_data"])[1].tolist() == [8, 8]  # evict flush
    rp.check_invariants(state)


@pytest.mark.parametrize("write_back", [False, True])
def test_payload_random_soup_invariants(write_back):
    rng = np.random.default_rng(11)
    n_nodes, n_lines, width = 4, 8, 3
    state = rp.make_state(n_nodes, n_lines, write_back=write_back,
                          payload_width=width)
    for it in range(4):
        r = 10
        node = rng.integers(0, n_nodes, r).astype(np.int32)
        line = rng.integers(-1, n_lines, r).astype(np.int32)
        isw = rng.integers(0, 2, r).astype(np.int32)
        wd = rng.integers(1, 1000, (r, width)).astype(np.int32)
        state, _, _, _ = _ops_tc(
            state, node, line, isw, wd, n_nodes=n_nodes, max_rounds=128)
        rp.check_invariants(state)


def test_payload_width_rejects_negative():
    with pytest.raises(ValueError, match="payload_width"):
        rp.make_state(2, 4, payload_width=-1)


def test_payload_driver_compiles_once_per_shape():
    """The payload plane rides INSIDE the fused while_loop: same
    zero-sync driver, one trace per (shape, width) — no per-batch
    retrace, no extra host round trip for the bytes."""
    rng = np.random.default_rng(2)
    state = rp.make_state(4, 16, payload_width=8)

    def batch(seed):
        r = np.random.default_rng(seed)
        return (r.integers(0, 4, 8).astype(np.int32),
                r.integers(0, 16, 8).astype(np.int32),
                r.integers(0, 2, 8).astype(np.int32),
                r.integers(1, 99, (8, 8)).astype(np.int32))

    state, _, _, _ = _ops_tc(state, *batch(1),
                                              n_nodes=4)
    round_key = ("round", 4, 16, 8, "ref", False, 8)
    driver_key = ("driver", 4, 8, 64, "ref", False, 8)
    baseline = dict(engine.TRACE_COUNTS)
    assert baseline.get(round_key, 0) == 1
    assert baseline.get(driver_key, 0) == 1
    for seed in range(2, 6):
        state, _, _, _ = _ops_tc(state, *batch(seed),
                                                  n_nodes=4)
    assert engine.TRACE_COUNTS[round_key] == baseline[round_key]
    assert engine.TRACE_COUNTS[driver_key] == baseline[driver_key]
    del rng
    rp.check_invariants(state)


def test_unencodable_node_count_rejected():
    with pytest.raises(ValueError, match="latch word"):
        rp.make_state(co.MAX_NODES + 1, 8)
    with pytest.raises(ValueError, match="latch word"):
        rp.make_state(0, 8)
    rp.make_state(co.MAX_NODES, 2)            # the paper's limit is fine


def test_high_node_ids_use_distinct_lanes():
    # nodes 31/32/55 span the lo/hi lane boundary; pre-spec node >= 56
    # aliased — now every encodable node has a distinct directory bit
    state = rp.make_state(56, 4)
    state, _, _ = _run(state, [31, 32, 55], [1, 1, 1], [0, 0, 0], 56)
    hi, lo = np.asarray(state["words"])[1]
    assert lo == np.int32(np.uint32(1 << 31))
    assert hi == ((1 << 0) | (1 << 23))
    rp.check_invariants(state)


# ------------------------------------------------- jax_protocol shim

def test_jax_protocol_shim_warns_exactly_once_and_reexports():
    """The compat shim's finished deprecation story (mirroring
    core/latchword.py): importing emits DeprecationWarning EXACTLY once
    (cached re-imports and attribute use stay silent), points at
    core/rounds, and every re-export is the SAME object."""
    import importlib
    import sys
    import warnings

    sys.modules.pop("repro.core.jax_protocol", None)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        shim = importlib.import_module("repro.core.jax_protocol")
        importlib.import_module("repro.core.jax_protocol")  # cached
        _ = shim.make_state, shim.run_rounds                # use
    dep = [w for w in caught
           if issubclass(w.category, DeprecationWarning)
           and "rounds" in str(w.message)]
    assert len(dep) == 1, [str(w.message) for w in caught]
    for name in ("check_invariants", "coherence_round", "evict_lines",
                 "make_state", "run_rounds"):
        assert getattr(shim, name) is getattr(rp, name), name
    for name in ("I", "S", "M", "WRITER_SHIFT_HI"):
        assert getattr(shim, name) is getattr(co, name), name


def test_jax_protocol_shim_reload_rewarns():
    """A forced reload re-executes the module body, so the warning
    fires again — once-per-import is real, not a filter accident."""
    import importlib
    import warnings

    from repro.core import jax_protocol as jp
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        importlib.reload(jp)
    assert sum(issubclass(w.category, DeprecationWarning)
               for w in caught) == 1


# ---------------------------------------------------- fused RMW driver

def test_run_rmw_is_read_transform_write_in_one_call():
    """run_rmw: phase-1 bytes feed the transform, phase-2 lands the
    result through the upgrade path — and the caller's node ends the
    call as a coherent holder whose copy equals memory."""
    import jax.numpy as jnp

    def bump(data, line, amount):
        return jnp.where((line >= 0)[:, None], data + amount[:, None],
                         data)

    state = rp.make_state(3, 8, payload_width=4)
    node = np.asarray([0, 0, 0], np.int32)
    line = np.asarray([1, 5, -1], np.int32)
    state, vers, rounds, data = _rmw_tc(
        state, node, line, bump,
        (np.asarray([10, 20, 99], np.int32),), n_nodes=3)
    assert vers.tolist() == [1, 1, 0]
    assert data[0].tolist() == [10] * 4 and data[1].tolist() == [20] * 4
    assert data[2].tolist() == [0] * 4             # line=-1 untouched
    md = np.asarray(state["mem_data"])
    assert md[1].tolist() == [10] * 4 and md[5].tolist() == [20] * 4
    rp.check_invariants(state)
    # a second RMW reads its own prior write (coherent S->M round trip)
    state, vers, _, data = _rmw_tc(
        state, node, line, bump, (np.asarray([1, 2, 3], np.int32),),
        n_nodes=3)
    assert vers.tolist() == [2, 2, 0]
    assert data[0].tolist() == [11] * 4 and data[1].tolist() == [22] * 4


def test_run_rmw_atomic_against_outside_holders():
    """Peers holding S copies before the call are invalidated by the
    upgrade (PeerWr at the round boundary) and re-read the NEW bytes —
    the RMW is coherent against every op outside its call."""
    import jax.numpy as jnp

    state = rp.make_state(4, 4, payload_width=2)
    # peers 1..3 take S copies of line 2
    state, _, _ = _ops_tc(
        state, np.asarray([1, 2, 3], np.int32),
        np.asarray([2, 2, 2], np.int32), np.zeros(3, np.int32),
        n_nodes=4)

    def put(data, line, val):
        return jnp.where((line >= 0)[:, None], val[:, None], data)

    state, vers, _, _ = _rmw_tc(
        state, np.asarray([0], np.int32), np.asarray([2], np.int32),
        put, (np.asarray([7], np.int32),), n_nodes=4)
    assert vers.tolist() == [1]
    cs = np.asarray(state["cache_state"])
    assert cs[0, 2] == 2 and (cs[1:, 2] == 0).all()   # peers evicted
    state, _, _, d = _ops_tc(
        state, np.asarray([1], np.int32), np.asarray([2], np.int32),
        np.zeros(1, np.int32), np.zeros((1, 2), np.int32), n_nodes=4)
    assert d[0].tolist() == [7, 7]
    rp.check_invariants(state)


# ------------------------------------ sparse boundary vs dense reference

def _dense_round(state, node_id, line, is_write, wdata, *, n_nodes):
    """Reference round: the engine's round body with its boundary as a
    DENSE pass over every line ([N, L] masks, an [N, L, W] flush sum,
    the whole directory rebuilt).  The engine must match it bit for
    bit while touching only the lines the round named."""
    write_back = "dirty" in state
    words = state["words"]
    cstate = state["cache_state"]
    cver = state["cache_version"]
    mver = state["mem_version"]
    dirty = state.get("dirty")
    mdata = state.get("mem_data")
    cdata = state.get("cache_data")
    width = mdata.shape[1] if mdata is not None else 0
    n_lines = words.shape[0]
    r = line.shape[0]
    if wdata is None:
        wdata = jnp.zeros((r, width), jnp.int32)
    I, S, M = co.I, co.S, co.M
    # coalesce
    valid = line >= 0
    idx = jnp.maximum(line, 0)
    is_w = jnp.logical_and(is_write.astype(bool), valid)
    key = node_id * n_lines + idx
    eq = jnp.logical_and(key[:, None] == key[None, :],
                         jnp.logical_and(valid[:, None], valid[None, :]))
    first = jnp.argmax(eq, axis=1)
    is_rep = jnp.logical_and(valid, first == jnp.arange(r))
    grp_write = jnp.any(jnp.logical_and(eq, is_w[None, :]), axis=1)
    lower = jnp.tril(jnp.ones((r, r), bool), k=-1)
    in_grp_w = jnp.logical_and(eq, is_w[None, :])
    w_rank = jnp.sum(jnp.logical_and(in_grp_w, lower), axis=1) \
        .astype(jnp.int32)
    n_w_grp = jnp.sum(in_grp_w, axis=1).astype(jnp.int32)
    last_w = jnp.maximum(
        jnp.max(jnp.where(in_grp_w, jnp.arange(r), -1), axis=1), 0)
    # latch
    st = cstate[node_id, idx]
    hit_read = jnp.logical_and(~grp_write, st >= S)
    hit_write = jnp.logical_and(grp_write, st == M)
    hit = jnp.logical_and(is_rep, jnp.logical_or(hit_read, hit_write))
    miss = jnp.logical_and(is_rep, ~hit)
    upgrade = jnp.logical_and(miss, jnp.logical_and(grp_write, st == S))
    fresh_w = jnp.logical_and(miss, jnp.logical_and(grp_write, st != S))
    read_miss = jnp.logical_and(miss, ~grp_write)
    bit_hi, bit_lo = co.bit_lanes(node_id)
    wf = co.writer_field_hi(node_id)
    req = {
        "line": jnp.where(miss, line, -1).astype(jnp.int32),
        "op": jnp.where(grp_write, OP_CAS, OP_FAA).astype(jnp.int32),
        "arg_hi": jnp.where(grp_write, wf, bit_hi).astype(jnp.int32),
        "arg_lo": jnp.where(grp_write, 0, bit_lo).astype(jnp.int32),
        "cmp_hi": jnp.where(upgrade, bit_hi, 0).astype(jnp.int32),
        "cmp_lo": jnp.where(upgrade, bit_lo, 0).astype(jnp.int32),
    }
    _, old_hi, _, ok = apply_batch(words, req, backend="ref")
    ok = ok.astype(bool)
    no_writer = co.writer_of_hi(old_hi) < 0
    read_grant = jnp.logical_and(read_miss, no_writer)
    write_grant = jnp.logical_and(jnp.logical_or(upgrade, fresh_w), ok)
    granted = jnp.logical_or(read_grant, write_grant)
    served_rep = jnp.logical_or(hit, granted)
    start = jnp.where(hit, cver[node_id, idx], mver[idx])
    k = jnp.where(jnp.logical_and(served_rep, grp_write), n_w_grp, 0)
    cstate = cstate.at[jnp.where(granted, node_id, n_nodes), idx].set(
        jnp.where(read_grant, jnp.int8(S), jnp.int8(M)), mode="drop")
    cver = cver.at[jnp.where(served_rep, node_id, n_nodes), idx].set(
        start + k, mode="drop")
    wrote = jnp.logical_and(served_rep, grp_write)
    if write_back:
        dirty = dirty.at[jnp.where(wrote, node_id, n_nodes), idx].set(
            True, mode="drop")
    else:
        mver = mver.at[jnp.where(wrote, idx, n_lines)].add(k, mode="drop")
    # fetch
    if width:
        base = jnp.where(hit[:, None], cdata[node_id, idx], mdata[idx])
        gdata = jnp.where(grp_write[:, None], wdata[last_w], base)
        cdata = cdata.at[jnp.where(served_rep, node_id, n_nodes),
                         idx].set(gdata, mode="drop")
        if not write_back:
            mdata = mdata.at[jnp.where(wrote, idx, n_lines)].set(
                gdata, mode="drop")
    # boundary, dense
    fail_w = jnp.logical_and(jnp.logical_or(upgrade, fresh_w), ~ok)
    fail_r = jnp.logical_and(read_miss, ~no_writer)
    wr_cnt = jnp.zeros((n_lines,), jnp.int32).at[
        jnp.where(fail_w, idx, n_lines)].add(1, mode="drop")
    rd_fail = jnp.zeros((n_lines,), bool).at[
        jnp.where(fail_r, idx, n_lines)].set(True, mode="drop")
    self_wr_fail = jnp.zeros((n_nodes, n_lines), jnp.int32).at[
        jnp.where(fail_w, node_id, n_nodes), idx].set(1, mode="drop")
    other_fail = (wr_cnt[None, :] - self_wr_fail) > 0
    kill = jnp.logical_and(other_fail, cstate >= S)
    m_mask = cstate == M
    dg_line = jnp.logical_and(jnp.logical_and(rd_fail, wr_cnt == 0),
                              jnp.any(m_mask, axis=0))
    dg_mask = jnp.logical_and(dg_line[None, :], m_mask)
    if write_back:
        flush = jnp.logical_and(jnp.logical_or(kill, dg_mask),
                                jnp.logical_and(m_mask, dirty))
        flush_ver = jnp.max(jnp.where(flush, cver, 0), axis=0)
        mver = jnp.where(jnp.any(flush, axis=0), flush_ver, mver)
        if width:
            flush_data = jnp.sum(
                jnp.where(flush[:, :, None], cdata, 0), axis=0)
            mdata = jnp.where(jnp.any(flush, axis=0)[:, None],
                              flush_data, mdata)
        dirty = jnp.logical_and(dirty, ~jnp.logical_or(kill, dg_mask))
    cstate = jnp.where(kill, jnp.int8(I), cstate)
    cstate = jnp.where(dg_mask, jnp.int8(S), cstate)
    words = co.directory_from_state(cstate)
    # replies
    served = jnp.where(valid, served_rep[first], False)
    slot_start = start[first]
    version = jnp.where(
        served, jnp.where(is_w, slot_start + w_rank + 1,
                          slot_start + n_w_grp), 0).astype(jnp.int32)
    data = (jnp.where(served[:, None], gdata[first], 0) if width
            else jnp.zeros((r, 0), jnp.int32))
    new_state = dict(state)
    new_state.update({"words": words, "cache_state": cstate,
                      "cache_version": cver, "mem_version": mver})
    if write_back:
        new_state["dirty"] = dirty
    if width:
        new_state["mem_data"] = mdata
        new_state["cache_data"] = cdata
    events = jnp.stack([
        jnp.sum(miss.astype(jnp.int32)),
        jnp.sum(jnp.logical_or(fail_w, fail_r).astype(jnp.int32)),
        jnp.sum(jnp.logical_and(valid, hit[first]).astype(jnp.int32))])
    return new_state, served, version, data, events


_dense_round_jit = jax.jit(_dense_round, static_argnames="n_nodes")

# Scripted rounds of (node, line, is_write) slots, padded with line = -1
# slots, on 4 nodes x 8 lines.  Each round names the boundary case it
# drives.
_BOUNDARY_SCRIPT = [
    [(0, 2, 0), (1, 2, 0), (2, 2, 0), (3, 5, 0)],   # S copies
    # racing upgraders kill each other and node 2's copy; node 3's
    # sole-S upgrade wins
    [(0, 2, 1), (1, 2, 1), (3, 5, 1)],
    # a read-fail downgrade of a dirty M holder; three nodes (and a
    # duplicate slot) name line 5
    [(3, 5, 1), (2, 5, 0), (1, 5, 0), (2, 5, 0)],
    [(0, 5, 0), (1, 5, 0)],
    # two failing writers on line 5, an upgrader and a fresh writer,
    # kill its three S holders
    [(0, 5, 1), (2, 5, 1), (2, 2, 1)],
    [(0, 6, 1), (0, 6, 1), (3, 7, 1)],
    # a failed writer kills a dirty M holder while a failed reader of
    # the same line waits: no downgrade
    [(1, 6, 1), (2, 6, 0), (3, 7, 0)],
    [(1, 6, 1), (2, 6, 0), (0, 7, 1), (1, 7, 0)],
]


def _boundary_rounds(scenario, n_nodes, r, width):
    """The (node, line, is_write, wdata) operands of each round."""
    rng = np.random.default_rng(17)
    if scenario == "scripted":
        batches = [(np.asarray([s[0] for s in b] + [0] * (r - len(b))),
                    np.asarray([s[1] for s in b] + [-1] * (r - len(b))),
                    np.asarray([s[2] for s in b] + [0] * (r - len(b))))
                   for b in _BOUNDARY_SCRIPT]
    else:
        # contended: few hot lines, half writes, a quarter empty slots
        batches = [(rng.integers(0, n_nodes, r),
                    np.where(rng.random(r) < 0.25, -1,
                             rng.integers(0, 4, r)),
                    rng.integers(0, 2, r)) for _ in range(40)]
    for node, line, isw in batches:
        wd = (jnp.asarray(rng.integers(1, 1 << 20, (r, width)), jnp.int32)
              if width else None)
        yield (jnp.asarray(node, jnp.int32), jnp.asarray(line, jnp.int32),
               jnp.asarray(isw, jnp.int32), wd)


@pytest.mark.parametrize("scenario", ["scripted", "random"])
@pytest.mark.parametrize("write_back,width",
                         [(False, 0), (False, 4), (True, 0), (True, 4)])
def test_sparse_boundary_matches_dense_reference(scenario, write_back,
                                                 width):
    """Round by round, the engine's sparse boundary gives the same
    state leaves, replies and events as the dense reference, and every
    word equals its directory."""
    n_nodes, n_lines, r = 4, 8, 12
    state = rp.make_state(n_nodes, n_lines, write_back=write_back,
                          payload_width=width)
    ref = state
    for ops in _boundary_rounds(scenario, n_nodes, r, width):
        out = engine._round_jit(state, *ops, n_nodes=n_nodes)
        want = _dense_round_jit(ref, *ops, n_nodes=n_nodes)
        for got_x, want_x in zip(out[1:], want[1:]):
            np.testing.assert_array_equal(np.asarray(got_x),
                                          np.asarray(want_x))
        state, ref = out[0], want[0]
        assert set(state) == set(ref)
        for key in ref:
            np.testing.assert_array_equal(np.asarray(state[key]),
                                          np.asarray(ref[key]),
                                          err_msg=key)
        rp.check_invariants(state)
    if scenario == "scripted" and write_back:
        # the dirty holders of lines 5 and 6 flushed at the boundary
        mv = np.asarray(state["mem_version"])
        assert mv[5] > 0 and mv[6] > 0


_INDEXING = ("gather", "scatter", "dynamic_slice", "dynamic_update_slice")


def _boundary_eqns(jaxpr, inside=False):
    """(primitive, operand shapes) of every equation in the
    ``round/boundary`` scope, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        here = inside or "round/boundary" in str(eqn.source_info.name_stack)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _boundary_eqns(sub, here)
        if here:
            out.append((eqn.primitive.name,
                        [tuple(getattr(v.aval, "shape", ()))
                         for v in eqn.invars]))
    return out


def test_round_boundary_touches_only_named_lines():
    """No equation of the boundary passes over a whole [N, L] or
    [N, L, W] leaf: its work scales with the round's R slots, not with
    the plane's L lines.  Gathers and scatters are the boundary's way in
    and out of the carried leaves, so they are left out; one-axis [L]
    vectors are allowed."""
    n_nodes, n_lines, width, r = 8, 4096, 8, 32
    state = rp.make_state(n_nodes, n_lines, write_back=True,
                          payload_width=width)
    slots = jnp.zeros((r,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda st, node, line, isw, wd: engine._round_impl(
            st, node, line, isw, wd, n_nodes=n_nodes))(
        state, slots, slots, slots, jnp.zeros((r, width), jnp.int32))
    eqns = _boundary_eqns(jaxpr.jaxpr)
    assert len(eqns) > 10
    dense = [(prim, shapes) for prim, shapes in eqns
             if not prim.startswith(_INDEXING)
             and any(len(s) >= 2 and n_lines in s for s in shapes)]
    assert dense == []
