"""Observability plane (src/repro/obs/): the StreamingHistogram sketch
against numpy.percentile, the EWMA closed form, the FlightRecorder span
ring (wraparound, zero added jit traces), the Prometheus exporter
(parse-back), placement driven purely off recorder heat, the
serving loop's queue-wait / TPOT histograms — and THE acceptance
differential: a mixed-verb trace on a flat plane vs a 4-shard plane
must produce bit-identical per-line hit/write-hit telemetry (runs in a
subprocess with 4 virtual devices, like test_congestion's).
"""

import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.rounds.placement import plan_rehome
from repro.obs import (EwmaHeat, FlightRecorder, MetricsRegistry,
                       PlaneTelemetry, StreamingHistogram)

jax = pytest.importorskip("jax")

import jax.numpy as jnp                                  # noqa: E402

from repro.core import rounds as rp                      # noqa: E402
from repro.core.rounds import engine                     # noqa: E402


def _i32(*xs):
    return np.asarray(xs, np.int32)


def _tele(line_hits, line_whits=None, n_shards=4):
    """Hand-built PlaneTelemetry for recorder/placement unit tests."""
    hits = np.asarray(line_hits, np.int64)
    served = np.zeros(n_shards, np.int64)
    served[0] = hits.sum()
    return PlaneTelemetry.from_counters({
        "occupancy": np.zeros((n_shards, n_shards), np.int64),
        "deferred": np.zeros((n_shards, n_shards), np.int64),
        "served_per_home": served,
        "replica_served": np.zeros(n_shards, np.int64),
        "line_hits": hits,
        "line_whits": (np.zeros_like(hits) if line_whits is None
                       else np.asarray(line_whits, np.int64)),
        "latch_requests": 0, "latch_failed": 0, "local_hits": 0,
    })


# ----------------------------------------------------------- histogram

def test_histogram_tracks_numpy_percentile():
    """The sketch's bounded relative error, checked on a fixed heavy-
    tailed draw: p50/p90/p99 within a few percent of the exact sorted-
    sample answer, ends exact."""
    rng = np.random.default_rng(42)
    xs = rng.lognormal(mean=-6.0, sigma=1.5, size=5000)
    h = StreamingHistogram()
    for x in xs:
        h.observe(x)
    assert h.count == len(xs)
    assert h.min == xs.min() and h.max == xs.max()
    assert h.mean == pytest.approx(xs.mean())
    for q in (0.50, 0.90, 0.99):
        exact = float(np.percentile(xs, q * 100))
        assert h.quantile(q) == pytest.approx(exact, rel=0.05), q
    assert h.quantile(0.0) == xs.min()
    assert h.quantile(1.0) == xs.max()
    assert h.percentile(50) == h.quantile(0.50)


def test_histogram_edges_and_merge():
    h = StreamingHistogram()
    assert h.quantile(0.5) == 0.0 and h.snapshot()["count"] == 0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        StreamingHistogram(growth=1.0)
    a, b = StreamingHistogram(), StreamingHistogram()
    for x in (1.0, 2.0, 3.0):
        a.observe(x)
    b.observe(10.0)
    a.merge(b)
    assert a.count == 4 and a.max == 10.0
    assert a.total == pytest.approx(16.0)
    with pytest.raises(ValueError, match="geometry"):
        a.merge(StreamingHistogram(growth=2.0))


# ---------------------------------------------------------------- EWMA

def test_ewma_closed_form():
    """k updates with constant counts c from zero must equal the closed
    form c * (1 - (1-alpha)^k) exactly (float64 arithmetic)."""
    alpha, k = 0.3, 6
    c = np.asarray([5.0, 2.0, 0.0, 7.0])
    heat = EwmaHeat(4, alpha=alpha)
    for _ in range(k):
        heat.update(c)
    np.testing.assert_allclose(heat.values,
                               c * (1 - (1 - alpha) ** k),
                               rtol=1e-12)
    assert heat.updates == k
    assert heat.top(2).tolist() == [3, 0]
    with pytest.raises(ValueError, match="shape"):
        heat.update(np.zeros(3))
    with pytest.raises(ValueError, match="alpha"):
        EwmaHeat(4, alpha=0.0)


# ------------------------------------------------------- recorder ring

def test_recorder_ring_wraparound():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record("ops", duration=1e-4, batch=(8,), rounds=i)
    assert len(rec) == 4 and rec.total == 10 and rec.dropped == 6
    spans = rec.spans()
    assert [s.index for s in spans] == [6, 7, 8, 9]   # oldest first
    assert [s.rounds for s in spans] == [6, 7, 8, 9]
    # counters saw EVERY span, not just the retained window
    c = rec.registry.counter("plane_dispatches_total",
                             labels={"verb": "ops"})
    assert c.value == 10
    assert rec.snapshot()["dropped"] == 6
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_recorder_heat_drives_plan_rehome():
    """The ISSUE acceptance: placement planned PURELY off the
    recorder's EWMA heat — no raw telemetry plumbing.  Constant skewed
    hits -> heat is a positive scalar multiple of the hit vector, so
    the greedy plan matches the raw-counter plan exactly."""
    l, s = 16, 4
    hits = np.zeros(l, np.int64)
    hits[[0, 4, 8]] = [90, 60, 30]         # identity perm: all on shard 0
    hits[[1, 5]] = [2, 1]
    rec = FlightRecorder(capacity=16)
    for _ in range(3):
        rec.record("ops", duration=1e-4, batch=(8,), rounds=2,
                   telemetry=_tele(hits, n_shards=s))
    heat = rec.line_heat
    assert heat is not None and heat.shape == (l,)
    assert rec.home_heat is not None and rec.home_heat.shape == (s,)
    perm = np.arange(l)
    lines, homes, victims = plan_rehome(heat, perm, s, max_moves=8,
                                        min_gain=0.5)
    ref = plan_rehome(hits, perm, s, max_moves=8)
    assert lines.tolist() == ref[0].tolist()
    assert homes.tolist() == ref[1].tolist()
    assert 0 not in set(homes.tolist())
    # and plan_rehome takes the typed telemetry itself (duck-typed)
    lines2, _, _ = plan_rehome(_tele(hits, n_shards=s), perm, s,
                               max_moves=8)
    assert lines2.tolist() == ref[0].tolist()


# ------------------------------------------------------------ exporters

_PROM_LINE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$")


def _parse_prom(text):
    """Minimal Prometheus text-format parser: sample lines back into
    {(name, labelstr): float}; validates every non-comment line."""
    out = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE "))
            continue
        m = _PROM_LINE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        out[(m.group(1), m.group(2) or "")] = float(m.group(3))
    return out


def test_prometheus_render_parses_back():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests", {"verb": "ops"}).inc(7)
    reg.gauge("depth").set(3.5)
    h = reg.histogram("lat_seconds", "latency")
    for x in (0.001, 0.002, 0.004, 0.008):
        h.observe(x)
    samples = _parse_prom(reg.render_prom())
    assert samples[("reqs_total", '{verb="ops"}')] == 7.0
    assert samples[("depth", "")] == 3.5
    assert samples[("lat_seconds_count", "")] == 4.0
    assert samples[("lat_seconds_sum", "")] == pytest.approx(0.015)
    buckets = [(k, v) for k, v in samples.items()
               if k[0] == "lat_seconds_bucket"]
    assert len(buckets) == 5               # 4 occupied + +Inf
    cums = [v for _, v in buckets]
    assert cums == sorted(cums)            # cumulative, monotone
    assert any('le="+Inf"' in k[1] and v == 4.0 for k, v in buckets)
    # kind conflicts are an error, not silent re-registration
    with pytest.raises(ValueError, match="registered"):
        reg.gauge("reqs_total")


# --------------------------------------------- plane integration (jax)

def test_plane_spans_add_zero_jit_traces():
    """Warm every shape recorder-OFF, snapshot TRACE_COUNTS, re-run the
    same mixed verbs recorder-ON: the trace-key set must not grow and
    every span's compile delta must be 0 — the recorder is host-side
    by construction."""
    plane = rp.DevicePlane.open(rp.make_state(2, 4, payload_width=1),
                                n_nodes=2)

    def _store(data, line, val):
        return jnp.where((line >= 0)[:, None], val, data)

    def drive():
        plane.ops(_i32(0, 1), _i32(0, 1), _i32(1, 0),
                  np.asarray([[5], [0]], np.int32))
        plane.rmw(_i32(1), _i32(0), modify=_store,
                  operands=(np.asarray([[9]], np.int32),))
        plane.evict(_i32(1), _i32(0))

    drive()                                # recorder off: warm traces
    keys_before = set(engine.TRACE_COUNTS)
    rec = FlightRecorder(capacity=16)
    plane.attach_recorder(rec)
    drive()
    assert set(engine.TRACE_COUNTS) == keys_before, \
        "attaching the recorder minted new jit traces"
    assert rec.total == 3
    ops_s, rmw_s, evict_s = rec.spans()
    assert (ops_s.verb, rmw_s.verb, evict_s.verb) == \
        ("ops", "rmw", "evict")
    assert all(s.compiled == 0 for s in rec.spans())
    assert ops_s.served == 2 and ops_s.batch == (2,)
    assert rmw_s.served == 2               # 1 op, read phase + write phase
    assert evict_s.served == 0             # no telemetry on evict
    assert rec.line_heat is not None and rec.line_heat.shape == (4,)
    assert rec.line_heat[0] > rec.line_heat[2]
    reg = rec.registry
    assert reg.counter("plane_dispatches_total",
                       labels={"verb": "ops"}).value == 1
    assert reg.counter("plane_compile_events_total").value == 0
    assert "plane_dispatch_seconds_bucket" in reg.render_prom()
    plane.check()


def test_serve_loop_histograms():
    """Satellite (f): ServeStats carries queue-wait and TPOT histogram
    snapshots, and the loop's registry renders them as Prometheus."""
    from repro.dsm.kvpool import KVPoolConfig, SELCCKVPool
    from repro.serve import ServeLoop, ToyLM
    cfg = KVPoolConfig(n_pages=24, page_size=4, n_kv_heads=2,
                       head_dim=4, n_replicas=2, dtype="float32")
    pool = SELCCKVPool(cfg)
    pool.open_rounds_plane()
    rec = FlightRecorder(capacity=64)
    loop = ServeLoop(pool, ToyLM(cfg), n_slots=2, max_pages=4,
                     queue_capacity=8, recorder=rec)
    reqs = [loop.submit([1, 2], 3) for _ in range(3)]
    assert loop.drain(timeout=120)
    assert all(r.generated for r in reqs)
    st = loop.stats()
    assert st.queue_wait is not None and st.queue_wait["count"] == 3
    assert st.queue_wait["max"] >= st.queue_wait["min"] >= 0.0
    # 3 reqs x 3 tokens: 2 inter-token gaps each
    assert st.tpot is not None and st.tpot["count"] == 6
    assert st.tpot["p99"] >= st.tpot["p50"] > 0.0
    prom = loop.render_prom()
    assert "serve_queue_wait_seconds_count 3" in prom
    assert "serve_tpot_seconds_count 6" in prom
    assert rec.total > 0                   # plane spans flowed too
    assert {"rmw"} <= set(rec.snapshot()["verbs"])


# ------------------------------ parity differential (4 devices)

def test_telemetry_parity_flat_vs_sharded_subprocess():
    """THE acceptance test: a mixed-verb trace (ops reads+writes, RMW)
    on a flat plane and on a 4-shard plane yields BIT-IDENTICAL
    per-line hit/write-hit telemetry — the counters are protocol
    facts, not geometry artifacts."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import rounds as rp

        N_NODES, N_LINES = 4, 8
        mesh = jax.make_mesh((4,), ("shards",))
        flat = rp.DevicePlane.open(
            rp.make_state(N_NODES, N_LINES, payload_width=1),
            n_nodes=N_NODES)
        shd = rp.DevicePlane.open(
            rp.make_sharded_state(N_NODES, N_LINES, mesh,
                                  payload_width=1),
            mesh, n_nodes=N_NODES)

        def _store(data, line, val):
            return jnp.where((line >= 0)[:, None], val, data)

        TRACE = [
            ("ops", [0, 1, 2, 3], [0, 1, 2, 3], [1, 1, 1, 1]),
            ("ops", [0, 1, 2, 3], [0, 0, 4, 4], [0, 0, 0, 0]),
            ("rmw", [1, 2], [1, 5], None, None),
            ("ops", [3, 0, 1], [5, 2, 7], [0, 1, 0]),
            ("rmw", [0, 3], [0, 3], None, None),
            ("ops", [2, 3, 0, 1], [6, 5, 1, 4], [1, 0, 0, 1]),
        ]
        agg = {"flat": 0, "shd": 0}
        for b, batch in enumerate(TRACE):
            if batch[0] == "ops":
                _, node, line, isw = batch
                node, line, isw = (np.asarray(node, np.int32),
                                   np.asarray(line, np.int32),
                                   np.asarray(isw, np.int32))
                wd = np.where(isw[:, None] > 0, b * 8 + line[:, None],
                              0).astype(np.int32)
                rf = flat.ops(node, line, isw, wd, max_rounds=128)
                rs = shd.ops(node, line, isw, wd, max_rounds=128)
            else:
                _, node, line = batch[:3]
                node, line = (np.asarray(node, np.int32),
                              np.asarray(line, np.int32))
                val = (100 + b * 8 + line[:, None]).astype(np.int32)
                rf = flat.rmw(node, line, modify=_store,
                              operands=(val,), max_rounds=128)
                rs = shd.rmw(node, line, modify=_store,
                             operands=(val,), max_rounds=128)
            assert rf.version.tolist() == rs.version.tolist(), b
            assert rf.data.tolist() == rs.data.tolist(), b
            tf, ts = rf.telemetry, rs.telemetry
            assert tf.n_shards == 1 and ts.n_shards == 4
            assert tf.line_hits.tolist() == ts.line_hits.tolist(), b
            assert tf.line_whits.tolist() == ts.line_whits.tolist(), b
            assert tf.served == ts.served, b
            assert int(ts.served_per_home.sum()) == ts.served
            agg["flat"] += tf.line_hits.sum()
            agg["shd"] += ts.line_hits.sum()
            flat.check(); shd.check()
        assert agg["flat"] == agg["shd"] > 0
        print("OBS_PARITY_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert "OBS_PARITY_OK" in out.stdout, out.stderr[-3000:]
