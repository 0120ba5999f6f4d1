"""One run of one cell: set-up, warm-up, the measured window, the check.

The window is a closed loop with one batch in flight: each batch of
``R`` ops is handed to the cell's driver (:mod:`bench.drivers`), and the
clock stops when its ``serve`` returns, which is after the results are
on the host.  Set-up (``setup_s``) runs from process start to the start
of the window: the driver builds the system on the device from the
seed, and a fixed number of batches of the cell's own traffic warm its
caches and compile the program shapes the window uses.

After the window the driver compares every batch with its plain
reference and reads back what it must.  With ``trace`` a short steady
part of the window is profiled, and the per-layer metrics are read from
that trace and the program's counters.
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
    traced_bytes: int           # least HBM bytes of the traced batches
    n_chips: int
    peak: dict
    traced_results: list        # the driver's result of each traced batch


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
        require_tpu: bool = True, plane_factory=None,
        batches: int | None = None, log=print) -> dict:
    """Run ``cell`` (a :class:`bench.manifest.Cell`) once; returns the
    result line as a dict.  ``t0`` is the process start on the
    ``perf_counter`` clock.  Raises :class:`NoChip` without a TPU unless
    ``require_tpu`` is off (tests, the control).  ``plane_factory``
    builds what serves the batches in place of the driver's
    ``open_plane`` (the control puts its own store there); ``batches``,
    when given, ends the window after that many batches instead of
    after ``seconds``."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core.rounds import TRACE_COUNTS

    compiles = CompileCounter()
    devices = chips_for(cell.chips, require_tpu)
    t_chips = time.perf_counter()
    drv, cfg, mix = cell.driver, cell.config, cell.traffic
    gen = drv.traffic(cfg, mix, seed)
    chip_peak = peak(devices[0].device_kind) if trace else None
    plane = (plane_factory or drv.open_plane)(cfg, seed, devices)
    jax.block_until_ready(getattr(plane, "state", None))
    t_built = time.perf_counter()
    records: list = []
    traced_results: list = []

    def one(keep=False):
        with TraceAnnotation("bench.generate"):
            batch = gen.next()
        with TraceAnnotation("bench.ops"):
            t1 = time.perf_counter()
            res = drv.serve(plane, batch)
            t2 = time.perf_counter()
        with TraceAnnotation("bench.record"):
            records.append(drv.record(batch, res))
            if res is not None and keep:
                traced_results.append(res)
        return t2 - t1

    for _ in range(int(mix["warmup_batches"])):
        one()
    n_warm = len(records)
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
    in_window = records[n_warm:]
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
    checks, compared = drv.check(cfg, mix, seed, records, plane)
    del plane
    correct = compared > 0 and all(c["value"] <= c["limit"]
                                   for c in checks.values())

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed}
    if not trace:
        all_ops = np.repeat(np.asarray(lat), r)
        values = {"ops_per_s": sum(b.ops for b in in_window) / window_s,
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
            traced_bytes=drv.least_bytes(cfg, t_batches),
            n_chips=len(devices), peak=chip_peak,
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


def print_checks(checks: dict) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
