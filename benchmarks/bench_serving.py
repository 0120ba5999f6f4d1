"""bench_serving — continuous batching vs. synchronous gang batching
on the device coherence plane.

The serving tentpole's headline number: the SAME request trace (mixed
prompt lengths, heterogeneous ``max_new`` budgets — the workload shape
continuous batching exists for) served twice over identical
rounds-plane KV pools at equal slot count:

* ``engine`` — ``serve.ServeLoop``: streaming FCFS admission into the
  slot grid, ONE fused ``run_rmw`` append + ONE fused paged attend per
  tick, completed slots evicted and refilled immediately;
* ``sync``   — ``serve.SyncBatchServer``: static FCFS gangs, a finished
  sequence's slot idles until the whole gang drains, and every KV
  append is the pre-fuse two-phase host path (read plane call -> numpy
  splice -> write plane call: two device dispatches + a host sync where
  the engine spends one fused call).

Both run the deterministic :class:`~repro.serve.model.ToyLM`, so the
bench first asserts token-identical outputs (the differential test's
invariant, re-checked on the benchmark trace) and then measures:
steady-state requests/sec, emitted-token throughput, and per-request
p50/p99 completion latency from submission — quantiles from the obs
``StreamingHistogram`` sketch, not a sorted sample.  The gated
``engine_sync_speedup`` row (>= 1.5x, within-run and therefore
machine-independent) is the acceptance bar; ``tok_mops`` rides the
regular max-regress trajectory gate.

Observability: the engine legs run with a ``FlightRecorder`` attached
to the pool's plane, so the artifact carries the full flight record —
``meta.telemetry`` embeds the recorder snapshot (spans/rounds/serve
totals, hottest lines) plus the engine's queue-wait and time-per-
output-token histogram snapshots.  For a timeline, profile the run
with ``jax.profiler``: every plane dispatch is a ``plane.<verb>``
annotation there, on the device's clock.
"""

from __future__ import annotations

import time

from .common import emit, write_bench_json

N_SLOTS = 8
PAGE = 8
N_PAGES = 64
MAX_PAGES = 4          # per-slot window: prompt<=4 + max_new<=16 -> 19 kv
PREFILL_CHUNK = 4
PROMPT_MAX = 4
GEN_MIN, GEN_MAX = 2, 16


def _workload(n_req: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    work = []
    for _ in range(n_req):
        plen = int(rng.integers(1, PROMPT_MAX + 1))
        prompt = tuple(int(t) for t in rng.integers(0, 97, plen))
        work.append((prompt, int(rng.integers(GEN_MIN, GEN_MAX + 1))))
    return work


def _pool():
    from repro.dsm.kvpool import KVPoolConfig, SELCCKVPool
    pool = SELCCKVPool(KVPoolConfig(
        n_pages=N_PAGES, page_size=PAGE, n_kv_heads=2, head_dim=8,
        n_replicas=2, dtype="float32"))
    pool.open_rounds_plane()
    return pool


def _run_engine(work):
    """-> (wall_s, completion latencies, ServeStats, tokens, recorder)."""
    from repro.obs import FlightRecorder
    from repro.serve import ServeLoop, ToyLM
    pool = _pool()
    loop_t0 = 0.0
    lats = []

    def _done(req, slot):
        lats.append(time.perf_counter() - loop_t0)

    rec = FlightRecorder(capacity=4096)
    loop = ServeLoop(pool, ToyLM(pool.cfg), n_slots=N_SLOTS,
                     max_pages=MAX_PAGES, prefill_chunk=PREFILL_CHUNK,
                     queue_capacity=len(work), on_complete=_done,
                     recorder=rec)
    loop_t0 = time.perf_counter()
    reqs = [loop.submit(p, m) for p, m in work]
    loop.start()
    if not loop.drain(timeout=600):
        raise RuntimeError("engine failed to drain the benchmark trace")
    loop.stop()
    wall = time.perf_counter() - loop_t0
    st = loop.stats()
    assert st.completed == len(work) and st.pages_in_use == 0
    assert rec.total > 0, "recorder saw no plane dispatches"
    return wall, lats, st, [r.generated for r in reqs], rec


def _run_sync(work):
    from repro.serve import ServeRequest, SyncBatchServer, ToyLM
    pool = _pool()
    sync_t0 = 0.0
    lats = []

    def _done(req, slot):
        lats.append(time.perf_counter() - sync_t0)

    srv = SyncBatchServer(pool, ToyLM(pool.cfg), n_slots=N_SLOTS,
                          max_pages=MAX_PAGES, on_complete=_done)
    reqs = [ServeRequest(prompt=p, max_new=m) for p, m in work]
    sync_t0 = time.perf_counter()
    srv.serve(reqs)
    wall = time.perf_counter() - sync_t0
    assert pool.pages_in_use == 0
    return wall, sorted(lats), srv, [r.generated for r in reqs]


def _hist(lats):
    from repro.obs import StreamingHistogram
    h = StreamingHistogram()
    for x in lats:
        h.observe(x)
    return h


def main(quick: bool = False, smoke: bool = False) -> list:
    n_req = 24 if (smoke or quick) else 48
    n_meas = 2 if (smoke or quick) else 3
    work = _workload(n_req, seed=17)
    tokens = sum(m for _, m in work)

    # warmup run of each server traces every jit shape (fused append,
    # two-phase read/write, attend); fresh pools below reuse the traces
    _, _, _, toks_e, _ = _run_engine(work)
    _, _, _, toks_s = _run_sync(work)
    assert toks_e == toks_s, \
        "engine and sync baseline diverged on the benchmark trace"

    runs_e = [_run_engine(work) for _ in range(n_meas)]
    runs_s = [_run_sync(work) for _ in range(n_meas)]
    wall_e = sorted(r[0] for r in runs_e)[n_meas // 2]
    wall_s = sorted(r[0] for r in runs_s)[n_meas // 2]
    hist_e = _hist(x for r in runs_e for x in r[1])
    hist_s = _hist(x for r in runs_s for x in r[1])
    st = runs_e[-1][2]
    srv = runs_s[-1][2]
    rec = runs_e[-1][4]

    rows: list = []
    for series, wall, hist in (("engine", wall_e, hist_e),
                               ("sync", wall_s, hist_s)):
        emit("serving", series, N_SLOTS, "reqs_per_s", n_req / wall,
             rows=rows)
        emit("serving", series, N_SLOTS, "p50_ms",
             hist.quantile(0.50) * 1e3, rows=rows)
        emit("serving", series, N_SLOTS, "p99_ms",
             hist.quantile(0.99) * 1e3, rows=rows)
    # emitted-token throughput rides the cross-commit trajectory gate
    emit("serving", "engine", N_SLOTS, "tok_mops", tokens / wall_e / 1e6,
         rows=rows)
    # the acceptance bar: continuous batching + the fused append must
    # beat gang scheduling + two-phase host appends >= 1.5x at equal
    # slot count (gated via the "speedup" metric floor)
    emit("serving", "engine", N_SLOTS, "engine_sync_speedup",
         wall_s / wall_e, rows=rows)
    # engine counters for the trajectory record (ungated diagnostics)
    emit("serving", "engine", N_SLOTS, "ticks", st.tick, rows=rows)
    emit("serving", "engine", N_SLOTS, "coherence_rounds",
         st.rounds_total, rows=rows)
    emit("serving", "engine", N_SLOTS, "appended_tokens",
         st.appended_tokens, rows=rows)
    emit("serving", "sync", N_SLOTS, "plane_calls", srv.plane_calls,
         rows=rows)
    emit("serving", "sync", N_SLOTS, "steps", srv.steps, rows=rows)
    # engine-only latency breakdown from the loop's own histograms
    # (ungated diagnostics: scheduling quality, not raw speed)
    if st.queue_wait is not None:
        emit("serving", "engine", N_SLOTS, "queue_wait_p50_ms",
             st.queue_wait["p50"] * 1e3, rows=rows)
        emit("serving", "engine", N_SLOTS, "queue_wait_p99_ms",
             st.queue_wait["p99"] * 1e3, rows=rows)
    if st.tpot is not None:
        emit("serving", "engine", N_SLOTS, "tpot_p50_ms",
             st.tpot["p50"] * 1e3, rows=rows)
        emit("serving", "engine", N_SLOTS, "tpot_p99_ms",
             st.tpot["p99"] * 1e3, rows=rows)

    # gate_max_regress 0.6: a serve tick is a few SMALL dispatches
    # (fused append + attend) plus host-side bookkeeping, jittery under
    # container CPU contention like fig10's descent loop; the within-run
    # engine_sync_speedup stays the sharp, machine-independent check
    write_bench_json("serving", rows,
                     meta={"payload": True, "gate_max_regress": 0.6,
                           "n_slots": N_SLOTS, "n_requests": n_req,
                           "n_pages": N_PAGES, "page_size": PAGE,
                           "max_pages": MAX_PAGES,
                           "prefill_chunk": PREFILL_CHUNK,
                           "gen_range": [GEN_MIN, GEN_MAX],
                           "tokens": tokens, "runs": n_meas,
                           "smoke": smoke, "quick": quick,
                           "telemetry": rec.snapshot()})
    return rows


if __name__ == "__main__":
    main()
