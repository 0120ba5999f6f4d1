"""DevicePlane facade contract (core/rounds/plane.py).

The facade owns ``state + mesh + n_nodes + write_back`` and exposes the
three verbs (``ops`` / ``rmw`` / ``descent``) with one keyword surface
and ONE result type; these tests pin that contract — PlaneResult shape,
in-place state ownership, flat/sharded uniformity, the
``SELCCLayer.as_plane`` bridge, bound-hit errors.  (The legacy
``run_*_to_completion`` dispatchers served their one deprecation
release and are gone — the facade is the only host-facing surface.)
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import ClusterConfig, SELCCLayer

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402,F401

from eager_telemetry import assert_same, eager_of, spy   # noqa: E402

from repro.core import rounds as rp                      # noqa: E402


def _i32(*xs):
    return np.asarray(xs, np.int32)


# ------------------------------------------------------------ facade

def test_ops_returns_plane_result_and_owns_state():
    plane = rp.DevicePlane.open(rp.make_state(2, 4))
    assert plane.n_nodes == 2            # inferred from cache_state
    assert not plane.sharded and plane.n_shards == 1
    assert plane.n_lines == 4 and plane.payload_width == 0
    assert not plane.write_back

    res = plane.ops(_i32(0, 1), _i32(0, 1), _i32(1, 0))
    assert isinstance(res, rp.PlaneResult)
    assert res.version.shape == (2,)
    assert res.data.shape == (2, 0)      # version-only plane: W == 0
    assert res.rounds >= 1 and res.stats == {}
    # flat verbs carry typed telemetry now (no sharded-only guard)
    assert isinstance(res.telemetry, rp.PlaneTelemetry)
    assert res.telemetry.n_shards == 1
    assert res.telemetry.served == 2
    assert res.telemetry.line_hits.tolist() == [1, 1, 0, 0]
    assert res.telemetry.line_whits.tolist() == [1, 0, 0, 0]
    assert res.version.tolist() == [1, 0]
    plane.check()
    assert "flat" in repr(plane)


def test_payload_ops_and_rmw_roundtrip():
    plane = rp.DevicePlane.open(rp.make_state(2, 4, payload_width=2))
    plane.ops(_i32(0), _i32(1), _i32(1), np.asarray([[7, 9]], np.int32))
    res = plane.ops(_i32(1), _i32(1), _i32(0))
    assert res.data.tolist() == [[7, 9]]

    def _store(data, line, val):
        return jnp.where((line >= 0)[:, None], val, data)

    res = plane.rmw(_i32(1), _i32(1), modify=_store,
                    operands=(np.asarray([[3, 4]], np.int32),))
    assert res.data.shape == (1, 2)
    res = plane.ops(_i32(0), _i32(1), _i32(0))
    assert res.data.tolist() == [[3, 4]]
    plane.check()


def test_one_shard_mesh_matches_flat():
    mesh = jax.make_mesh((1,), ("shards",))
    flat = rp.DevicePlane.open(rp.make_state(2, 4, payload_width=1))
    shd = rp.DevicePlane.open(
        rp.make_sharded_state(2, 4, mesh, payload_width=1), mesh)
    assert shd.sharded and shd.n_shards == 1
    trace = [(_i32(0, 1), _i32(0, 1), _i32(1, 1),
              np.asarray([[5], [6]], np.int32)),
             (_i32(1, 0), _i32(0, 1), _i32(0, 0), None)]
    for node, line, isw, wd in trace:
        r1 = flat.ops(node, line, isw, wd)
        r2 = shd.ops(node, line, isw, wd)
        assert r1.version.tolist() == r2.version.tolist()
        assert r1.data.tolist() == r2.data.tolist()
    for k, v in flat.flat_state().items():
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(shd.flat_state()[k]),
                                      err_msg=k)


def test_descent_verb_reports_stats():
    # two-line chain: line 0 points at line 1, which is the leaf
    plane = rp.DevicePlane.open(rp.make_state(1, 2, payload_width=2))
    plane.state = dict(plane.state,
                       mem_data=jnp.asarray([[0, 1], [1, 0]], jnp.int32))

    def _chain(data, key):
        at_leaf = data[:, 0] == 1
        return at_leaf, jnp.zeros(data.shape[0], bool), data[:, 1]

    res = plane.descent(_i32(0), _i32(0), _i32(0), transition=_chain)
    assert res.version is None
    assert res.data.tolist() == [[1, 0]]
    assert res.stats["line"].tolist() == [1]
    assert res.stats["levels"].tolist() == [1]
    assert res.stats["hops"].tolist() == [0]
    assert res.rounds >= 1


def test_as_plane_bridges_the_des_layer():
    layer = SELCCLayer(ClusterConfig(n_compute=3, n_memory=2))
    plane = layer.as_plane(8, payload_width=2)
    assert isinstance(plane, rp.DevicePlane)
    assert plane.n_nodes == 3 and plane.payload_width == 2
    assert not plane.sharded
    res = plane.ops(_i32(2), _i32(5), _i32(1),
                    np.asarray([[1, 2]], np.int32))
    assert res.version.tolist() == [1]

    mesh = jax.make_mesh((1,), ("shards",))
    shp = layer.as_plane(8, mesh=mesh)
    assert shp.sharded and shp.mesh is mesh and shp.n_nodes == 3


def test_bound_hit_raises_runtime_error():
    plane = rp.DevicePlane.open(rp.make_state(2, 4), max_rounds=1)
    with pytest.raises(RuntimeError, match="not served"):
        plane.ops(_i32(0, 1), _i32(1, 1), _i32(1, 1))


# ------------------------------------------------------- lazy telemetry

def _trace_verbs(plane):
    """Writes, reads and an RMW over an 8-line payload plane."""
    def _store(data, line, val):
        return jnp.where((line >= 0)[:, None], val, data)

    wd = np.asarray([[5, 1], [6, 2], [7, 3]], np.int32)
    return [plane.ops(_i32(0, 1, 1), _i32(0, 3, 5), _i32(1, 1, 0), wd),
            plane.ops(_i32(1, 0, 1, 0), _i32(0, 0, 3, 7), _i32(0, 0, 1, 0),
                      np.zeros((4, 2), np.int32)),
            plane.rmw(_i32(1, 0), _i32(5, 6), modify=_store,
                      operands=(np.asarray([[8, 8], [9, 9]], np.int32),)),
            plane.ops(_i32(0, 1), _i32(6, 5), _i32(0, 1), wd[:2])]


@pytest.mark.parametrize("shards", [0, 1])
def test_lazy_telemetry_matches_eager(monkeypatch, shards):
    """Every verb's lazy record equals, field for field, the eager copy
    of the same raw leaves — on a flat plane and on a one-shard mesh
    (the sharded remap path)."""
    seen, wrapped = spy(rp.DevicePlane._telemetry)
    monkeypatch.setattr(rp.DevicePlane, "_telemetry", wrapped)
    if shards:
        mesh = jax.make_mesh((shards,), ("shards",))
        state = rp.make_sharded_state(2, 8, mesh, payload_width=2,
                                      home_directory=True)
    else:
        mesh, state = None, rp.make_state(2, 8, payload_width=2)
    plane = rp.DevicePlane.open(state, mesh)
    results = _trace_verbs(plane)
    assert len(seen) == len(results)
    for res, item in zip(results, seen):
        assert_same(res.telemetry, eager_of(item))
    assert results[1].telemetry.line_hits.tolist() == [2, 0, 0, 1,
                                                       0, 0, 0, 1]


def test_unread_results_copy_no_telemetry():
    """A loop that never reads its results' telemetry copies none of
    it; reading ``served`` copies the small counters alone, reading a
    per-line counter the per-line pair alone, each once."""
    plane = rp.DevicePlane.open(rp.make_state(2, 8, payload_width=2))
    before = dict(rp.TELEMETRY_READS)
    results = [plane.ops(_i32(0, 1), _i32(k, 7 - k), _i32(1, 0),
                         np.full((2, 2), k, np.int32))
               for k in range(4)]
    plane.rmw(_i32(0), _i32(2), modify=lambda d, line, v: d + v,
              operands=(np.ones((1, 2), np.int32),))
    assert rp.TELEMETRY_READS == before

    tele = results[-1].telemetry
    assert tele.served == 2 and tele.deferred_total == 0
    assert int(tele.latch_requests) >= 1
    assert rp.TELEMETRY_READS == dict(before,
                                      counters=before["counters"] + 1)
    assert tele.line_hits.tolist() == [0, 0, 0, 1, 1, 0, 0, 0]
    assert tele.line_whits.tolist() == [0, 0, 0, 1, 0, 0, 0, 0]
    assert rp.TELEMETRY_READS == {"counters": before["counters"] + 1,
                                  "lines": before["lines"] + 1}


def test_lazy_telemetry_sums_match_eager(monkeypatch):
    """``sum()`` and ``+`` over lazy records give the eager sums."""
    seen, wrapped = spy(rp.DevicePlane._telemetry)
    monkeypatch.setattr(rp.DevicePlane, "_telemetry", wrapped)
    plane = rp.DevicePlane.open(rp.make_state(2, 8, payload_width=2))
    lazy = [r.telemetry for r in _trace_verbs(plane)]
    eager = [eager_of(item) for item in seen]
    assert_same(sum(lazy), sum(eager))
    assert_same(sum(lazy, rp.PlaneTelemetry.zeros(1, 8)), sum(eager))
    assert_same(lazy[0] + lazy[1], eager[0] + eager[1])
    assert_same(lazy[2] + eager[3], eager[2] + eager[3])
    assert sum(lazy).served == sum(r.served for r in eager) == 13


_FOUR_SHARDS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path[:0] = ["src", "tests"]
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import rounds as rp
    from eager_telemetry import assert_same, eager_of, spy

    seen, wrapped = spy(rp.DevicePlane._telemetry)
    rp.DevicePlane._telemetry = wrapped
    mesh = jax.make_mesh((4,), ("shards",))
    plane = rp.DevicePlane.open(
        rp.make_sharded_state(4, 16, mesh, payload_width=1,
                              home_directory=True), mesh)
    i32 = lambda *xs: np.asarray(xs, np.int32)
""")


def _four_shards(body: str, ok: str):
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_SHARDS + textwrap.dedent(body)],
        cwd=".", capture_output=True, text=True, timeout=600)
    assert ok in out.stdout, out.stderr[-3000:]


def test_lazy_telemetry_matches_eager_four_shards_subprocess():
    """On a 4-shard plane whose home directory moves between verbs,
    each lazy record, read after the moves, equals the eager copy made
    as its verb returned; so do their sums."""
    _four_shards("""
        def store(data, line, val):
            return jnp.where((line >= 0)[:, None], val, data)

        results = []
        for b in range(6):
            line = i32(b, 2 * b + 1, 15 - b, b, 7)
            isw = i32(1, 0, b % 2, 0, 1)
            results.append(plane.ops(i32(0, 1, 2, 3, b % 4), line, isw,
                                     (10 * b + line[:, None]) * isw[:, None],
                                     max_rounds=128))
            results.append(plane.rmw(i32(b % 4), i32(b + 8), modify=store,
                                     operands=(i32(b)[:, None],),
                                     max_rounds=128))
            assert plane.rehome(i32(b, 15 - b), i32((b + 1) % 4,
                                                    (b + 2) % 4)) > 0
        eager = [eager_of(item) for item in seen]
        for res, ref in zip(results, eager):
            assert_same(res.telemetry, ref)
        assert_same(sum(r.telemetry for r in results), sum(eager))
        assert sum(eager).served == 42
        plane.check()
        print("LAZY_FOUR_SHARDS_OK")
    """, "LAZY_FOUR_SHARDS_OK")


def test_rehome_keeps_earlier_line_hits_subprocess():
    """A ``rehome`` after an ``ops`` call does not change that call's
    ``line_hits``: the record remaps through the directory its verb
    left, although the new directory would remap it differently."""
    _four_shards("""
        res = plane.ops(i32(0, 1, 2, 3, 0), i32(1, 1, 1, 2, 6),
                        i32(0, 0, 0, 1, 0), max_rounds=128)
        before = eager_of(seen[-1])
        assert plane.rehome(i32(1, 2), i32(3, 0)) == 2
        after = eager_of(seen[-1], home=np.asarray(plane.state["home"]))
        assert before.line_hits.tolist() == [0, 3, 1, 0, 0, 0, 1] + [0] * 9
        assert after.line_hits.tolist() != before.line_hits.tolist()
        assert_same(res.telemetry, before)
        print("REHOME_KEEPS_OK")
    """, "REHOME_KEEPS_OK")
