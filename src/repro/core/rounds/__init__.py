"""The device-resident coherence engine (bulk-synchronous rounds plane).

One SELCC spec (core/coherence.py), two planes: the DES models the
asynchronous RPC protocol; this package runs the SAME state machine as
deterministic rounds on device — S->X upgrades, write-back with dirty
bits and eviction write-back, multi-op coalescing, and a fully-jitted
spin loop (:func:`run_rounds`) with zero host syncs per round.

    state  = make_state(n_nodes, n_lines[, write_back=True]
                        [, payload_width=W])
    state, versions, data, rounds, ok, tele = run_rounds(
        state, nodes, lines, is_wr[, wdata], n_nodes=n_nodes)

``payload_width=W`` attaches the GCL data plane: ops carry [R, W] write
payloads and every served slot's read payload comes back in ``data`` —
reads return bytes, not just versions.

Mesh scale-out (rounds/sharded.py): the SAME engine across a shard_map
mesh (home = the physical-slot directory, the ``line % n_shards``
stripe by default), requests routed home and replies routed back by
two all_to_alls per round (payload lanes ride the same collectives),
still one fused loop.  BOTH planes accumulate telemetry in the loop
carry (the trailing ``tele`` counter dict — same keys flat and
sharded, so the two geometries diff bit-for-bit); the facade types it
as :class:`~repro.obs.PlaneTelemetry`:

    state  = make_sharded_state(n_nodes, n_lines, mesh[, write_back=..]
                                [, payload_width=W]
                                [, home_directory=True][, replicas=True])
    state, versions, data, rounds, ok, tele = run_rounds_sharded(
        state, nodes, lines, is_wr[, wdata], mesh=mesh, n_nodes=n_nodes)

Host-facing callers should use the :class:`DevicePlane` facade
(rounds/plane.py) — ONE object owning state + mesh + n_nodes that
exposes ``plane.ops`` / ``plane.rmw`` / ``plane.descent`` /
``plane.txn`` (plus the placement verbs ``plane.rehome`` /
``plane.replicate``, fed by :mod:`.placement` policies over the
telemetry) and returns normalized :class:`PlaneResult`s.  Attach an
``obs.FlightRecorder`` (``DevicePlane.open(..., recorder=rec)``) to
get per-dispatch spans, Prometheus metrics, Chrome-trace export and
the EWMA line/home heat the placement policies consume online.
"""

from ...obs import TELEMETRY_READS, FlightRecorder, PlaneTelemetry
from ..coherence import I, M, S
from .descent import run_descent
from .driver import run_rmw, run_rounds
from .engine import TRACE_COUNTS, coherence_round, evict_lines
from .placement import plan_rehome, plan_replication
from .plane import DevicePlane, PlaneResult
from .sharded import (coherence_round_sharded, evict_lines_sharded,
                      make_sharded_state, pad_ops, rehome_exchange,
                      run_descent_sharded, run_rmw_sharded,
                      run_rounds_sharded, shard_state, unshard_state)
from .state import (check_invariants, is_write_back, make_state,
                    payload_width, stripe_state, unstripe_state)
from .txn import (TxnBatchResult, TxnProgram, run_txn_rounds,
                  run_txn_rounds_sharded)

__all__ = [
    "I", "S", "M", "DevicePlane", "FlightRecorder", "PlaneResult",
    "PlaneTelemetry", "TELEMETRY_READS", "TRACE_COUNTS",
    "TxnBatchResult", "TxnProgram", "check_invariants", "coherence_round",
    "coherence_round_sharded", "evict_lines", "evict_lines_sharded",
    "is_write_back", "make_sharded_state", "make_state", "pad_ops",
    "payload_width", "plan_rehome", "plan_replication",
    "rehome_exchange", "run_descent", "run_descent_sharded", "run_rmw",
    "run_rmw_sharded", "run_rounds",
    "run_rounds_sharded", "run_txn_rounds",
    "run_txn_rounds_sharded",
    "shard_state", "stripe_state", "unshard_state", "unstripe_state",
]
