"""One run of one cell: set-up, warm-up, the measured window, the check.

The window is a closed loop with one batch in flight: each batch of
``R`` ops (one per client slot) is handed to ``DevicePlane.ops``, and
the clock stops when ``ops`` returns, which is after its results are on
the host.  Set-up (``setup_s``) runs from process start to the start of
the window: the table is made on the device from the seed in one
jitted call, the node caches start empty, and a fixed number of batches
of the cell's own traffic warm the caches and compile the one program
shape the window uses.

After the window the plain reference (:mod:`bench.reference`) checks
every reply of every batch, and a seeded sample of the records written
is read back through the plane from a node other than its last writer.
With ``trace`` a short steady part of the window is profiled, and the
per-layer metrics are read from that trace and the program's counters.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

import numpy as np

from . import trace as trace_mod
from .metrics import reader
from .peaks import peak
from .reference import Reference, Served
from .ycsb import Batches, init_lanes, seed32

TRACE_BATCHES = 8          # batches profiled in a --trace 1 run
TRACE_AFTER = 0.25         # ...starting this far into the window
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclass
class Readings:
    """What the per-layer readers read (see ``bench/metrics``)."""

    trace: dict
    rounds: list
    traced_rounds: int
    traced_ops: int
    width: int
    n_chips: int
    peak: dict
    traced_results: list        # PlaneResult of each traced batch


class CompileCounter:
    """Counts XLA compiles and persistent-cache hits from JAX's events."""

    def __init__(self):
        import jax
        self.n = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.n += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def chips_for(n: int, require_tpu: bool = True) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if require_tpu and len(devs) < n:
        raise NoChip(f"needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def open_plane(config: dict, seed: int, devices: list):
    """A ``DevicePlane`` over a fresh state made on the device in one
    jitted call: every record holds its seed-derived version-0 bytes,
    the node caches are empty.  One chip holds a flat plane; more chips
    hold the plane striped over them by ``make_sharded_state``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core.rounds import DevicePlane, make_sharded_state, make_state

    n, l, w = (int(config["n_nodes"]), int(config["recordcount"]),
               int(config["payload_width"]))
    kw = dict(write_back=bool(config["write_back"]), payload_width=w)
    s32 = jnp.uint32(seed32(seed))
    if len(devices) == 1:
        def build(s):
            st = dict(make_state(n, l, **kw))
            st["mem_data"] = init_lanes(s, jnp.arange(l), w)
            return st
        state = jax.jit(build)(jax.device_put(s32, devices[0]))
        return DevicePlane.open(state, n_nodes=n)
    mesh = Mesh(np.array(devices), ("shards",),
                axis_types=(AxisType.Auto,))
    shards = len(devices)
    shapes = jax.eval_shape(lambda: make_sharded_state(n, l, mesh, **kw))
    # every leaf is striped over its line axis: the one of length L
    outs = {k: NamedSharding(mesh, P(*[
        "shards" if d == s.shape.index(l) else None
        for d in range(len(s.shape))])) for k, s in shapes.items()}

    def build(s):
        st = dict(make_sharded_state(n, l, mesh, **kw))
        # row r of the stripe holds line (r % (L/S)) * S + r // (L/S)
        r = jnp.arange(l)
        rows = (r % (l // shards)) * shards + r // (l // shards)
        st["mem_data"] = init_lanes(s, rows, w)
        return st
    state = jax.jit(build, out_shardings=outs)(s32)
    return DevicePlane.open(state, mesh, n_nodes=n)


def _serve(plane, node, line, isw, wdata):
    """One ``plane.ops`` call: (result or None if it was refused)."""
    try:
        return plane.ops(node, line, isw, wdata)
    except RuntimeError as e:
        if "not served" not in str(e):
            raise
        return None


def _percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile (the value at or below which ``q``% of
    the samples lie)."""
    v = np.sort(values)
    return float(v[max(0, int(np.ceil(q / 100.0 * v.size)) - 1)])


def _memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell, *, seed: int, seconds: float, trace: bool, t0: float,
        require_tpu: bool = True, plane_factory=open_plane,
        batches: int | None = None, log=print) -> dict:
    """Run ``cell`` (a :class:`bench.manifest.Cell`) once; returns the
    result line as a dict.  ``t0`` is the process start on the
    ``perf_counter`` clock.  Raises :class:`NoChip` without a TPU unless
    ``require_tpu`` is off (tests, the control).  ``plane_factory``
    builds what serves the ops (the control puts its own store there);
    ``batches``, when given, ends the window after that many batches
    instead of after ``seconds``."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core.rounds import TRACE_COUNTS

    compiles = CompileCounter()
    devices = chips_for(cell.chips, require_tpu)
    t_chips = time.perf_counter()
    cfg, mix = cell.config, cell.traffic
    width = int(cfg["payload_width"])
    n_nodes = int(cfg["n_nodes"])
    gen = Batches(mix, n_records=int(cfg["recordcount"]), n_nodes=n_nodes,
                  clients_per_node=int(cfg["clients_per_node"]),
                  width=width, seed=seed)
    chip_peak = peak(devices[0].device_kind) if trace else None
    plane = plane_factory(cfg, seed, devices)
    jax.block_until_ready(getattr(plane, "state", None))
    t_built = time.perf_counter()
    served: list = []
    traced_results: list = []

    def one(keep=False):
        with TraceAnnotation("bench.generate"):
            node, line, isw, wdata, tags = gen.next()
        with TraceAnnotation("bench.ops"):
            t1 = time.perf_counter()
            res = _serve(plane, node, line, isw, wdata)
            t2 = time.perf_counter()
        with TraceAnnotation("bench.record"):
            if res is None:
                served.append(Served(node, line, isw, tags, failed=True))
            else:
                served.append(Served(node, line, isw, tags, res.version,
                                     res.data, rounds=res.rounds))
                if keep:
                    traced_results.append(res)
        return t2 - t1

    for _ in range(int(mix["warmup_batches"])):
        one()
    n_warm = len(served)
    traces0 = sum(TRACE_COUNTS.values())
    compiles0 = compiles.n

    # ------------------------------------------------------- the window
    lat: list = []
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    traced = [None, None]            # window batch indices [start, stop)
    w0 = time.perf_counter()
    setup_s = w0 - t0
    try:
        while True:
            now = time.perf_counter() - w0
            k = len(lat)
            if tdir and traced[0] is None and k >= 2 \
                    and now >= TRACE_AFTER * seconds:
                from jax.profiler import ProfileOptions
                opts = ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(tdir, profiler_options=opts)
                traced[0] = k
            if traced[0] is not None and traced[1] is None \
                    and k - traced[0] >= TRACE_BATCHES:
                jax.profiler.stop_trace()
                traced[1] = k
            over = k >= batches if batches is not None else now >= seconds
            if over and (not tdir or traced[1] is not None):
                break
            lat.append(one(keep=traced[0] is not None
                           and traced[1] is None))
    finally:
        if traced[0] is not None and traced[1] is None:
            jax.profiler.stop_trace()
            traced[1] = len(lat)
    window_s = time.perf_counter() - w0
    in_window = served[n_warm:]
    rounds = [b.rounds for b in in_window if not b.failed]
    window_traces = sum(TRACE_COUNTS.values()) - traces0
    window_compiles = compiles.n - compiles0
    mem_peak = _memory_peak(devices)

    r = gen.r
    attempted = r * len(in_window)
    failed = r * sum(b.failed for b in in_window)
    log(json.dumps({"window_batches": len(in_window),
                    "warmup_batches": n_warm, "window_traces": window_traces,
                    "window_compiles": window_compiles,
                    "setup_compiles": compiles0,
                    "setup_cache_hits": compiles.cache_hits,
                    "setup_s": setup_s, "to_chips_s": t_chips - t0,
                    "build_s": t_built - t_chips,
                    "warmup_s": w0 - t_built,
                    "batch_ms_by_quarter": [
                        1e3 * float(np.mean(q)) if len(q) else None
                        for q in np.array_split(np.asarray(lat), 4)]}))

    # ------------------------------------------ the reference, read-back
    ref = Reference(int(cfg["recordcount"]), width, seed)
    reply_bad = sum(ref.check_batch(b) for b in served)
    rng = np.random.default_rng([int(seed) % (1 << 64), 1])
    lines, nodes = ref.readback_plan(rng, int(mix["readback_records"]),
                                     n_nodes)
    rb_ver, rb_data = _read_back(plane, lines, nodes, r, width)
    del plane
    readback_bad = ref.check_readback(lines, rb_ver, rb_data)
    compared = r * sum(not b.failed for b in served)
    checks = {"reply_mismatches": {"value": reply_bad, "limit": 0},
              "readback_mismatches": {"value": readback_bad, "limit": 0}}
    correct = compared > 0 and all(c["value"] <= c["limit"]
                                   for c in checks.values())

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed}
    if not trace:
        done = np.asarray([t for t, b in zip(lat, in_window)
                           if not b.failed])
        all_ops = np.repeat(np.asarray(lat), r)
        values = {"ops_per_s": r * done.size / window_s,
                  "op_p95_ms": 1e3 * _percentile(all_ops, 95.0),
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    else:
        try:
            reduced = trace_mod.reduce(trace_mod.extract(_xplane(tdir)))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        lo, hi = traced
        t_batches = in_window[lo:hi]
        ctx = Readings(
            trace=reduced, rounds=rounds,
            traced_rounds=int(sum(b.rounds for b in t_batches)),
            traced_ops=int(sum(int((b.line >= 0).sum())
                               for b in t_batches if not b.failed)),
            width=width, n_chips=len(devices), peak=chip_peak,
            traced_results=traced_results)
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = reduced.get("busy_s", 0.0)
        device["window_s"] = reduced.get("window_s", 0.0)
        out["breakdown"] = {"device_ops": reduced.get("device_ops", []),
                            "idle_gaps": reduced.get("idle_gaps", [])}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    return out


def _xplane(tdir: str) -> str:
    for root, _, files in os.walk(tdir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    raise FileNotFoundError(f"no .xplane.pb under {tdir}")


def _read_back(plane, lines, nodes, r: int, width: int):
    """Read ``lines[i]`` from node ``nodes[i]`` through ``plane.ops``, in
    batches of the window's own shape."""
    vers, datas = [], []
    zeros_w = np.zeros((r, width), np.int32)
    zeros_i = np.zeros(r, np.int32)
    for s in range(0, lines.size, r):
        ln = np.full(r, -1, np.int32)
        nd = np.zeros(r, np.int32)
        k = min(r, lines.size - s)
        ln[:k], nd[:k] = lines[s:s + k], nodes[s:s + k]
        res = _serve(plane, nd, ln, zeros_i, zeros_w)
        if res is None:
            vers.append(np.full(k, -1, np.int32))
            datas.append(np.zeros((k, width), np.int32))
        else:
            vers.append(res.version[:k])
            datas.append(res.data[:k])
    if not vers:
        return np.zeros(0, np.int32), np.zeros((0, width), np.int32)
    return np.concatenate(vers), np.concatenate(datas)


def print_checks(checks: dict) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
