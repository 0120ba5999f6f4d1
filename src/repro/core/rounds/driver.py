"""The fused on-device spin loop: run ops to completion in ONE jit call.

Pre-refactor, ``run_ops_to_completion`` was a host-side Python loop that
synced served/pending masks back to the host after EVERY round — exactly
the per-op round-trip overhead MIND (arXiv 2107.00164) shows dominating
disaggregated-memory latency.  :func:`run_rounds` replaces it with a
``jax.lax.while_loop`` whose carry (state, pending lines, versions,
round counter) never leaves the device: unserved ops re-present
themselves round after round (the protocol's spin) with zero host↔device
syncs inside the loop, and the while_loop body traces the round engine
exactly once per shape (engine.TRACE_COUNTS proves it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .engine import _note_trace, _round_jit
from .state import payload_width


def zero_flat_tele(n_lines: int) -> dict:
    """Zeroed FLAT telemetry accumulator — the same counter keys the
    sharded drivers return, shaped for S = 1 home (``occupancy`` /
    ``deferred`` [1, 1], ``served_per_home`` / ``replica_served`` [1],
    per-line ``slot_hits`` / ``slot_whits`` [L]; the flat engine is
    line-major, so slot == line; ``events`` [1, 3], the round body's
    protocol events in ``engine.EVENT_KEYS`` order).  Rides the fused
    loops' carries — accumulating costs two scatter-adds per round,
    zero host syncs."""
    z1 = jnp.zeros((1,), jnp.int32)
    return {"occupancy": jnp.zeros((1, 1), jnp.int32),
            "deferred": jnp.zeros((1, 1), jnp.int32),
            "served_per_home": z1, "replica_served": z1,
            "slot_hits": jnp.zeros((n_lines,), jnp.int32),
            "slot_whits": jnp.zeros((n_lines,), jnp.int32),
            "events": jnp.zeros((1, 3), jnp.int32)}


def add_tele(a: dict, b: dict) -> dict:
    """Key-wise telemetry-dict sum (accumulation across phases/spins)."""
    return {k: a[k] + b[k] for k in a}


@jax.named_scope("round/telemetry")
def _tele_round(tele: dict, pending, served, is_write, events,
                n_lines: int) -> dict:
    """Fold one round's serve results and protocol ``events`` into a
    flat telemetry carry: ``pending`` is the PRE-round line per slot
    (-1 = done/pad)."""
    valid = pending >= 0
    hit = jnp.logical_and(served, valid)
    hit_line = jnp.where(hit, pending, n_lines)      # n_lines = dropped
    occ = tele["occupancy"] + jnp.sum(valid.astype(jnp.int32))
    srv = tele["served_per_home"] + jnp.sum(hit.astype(jnp.int32))
    hits = tele["slot_hits"].at[hit_line].add(1, mode="drop")
    whits = tele["slot_whits"].at[
        jnp.where(is_write.astype(bool), hit_line, n_lines)].add(
        1, mode="drop")
    return {"occupancy": occ, "deferred": tele["deferred"],
            "served_per_home": srv,
            "replica_served": tele["replica_served"],
            "slot_hits": hits, "slot_whits": whits,
            "events": tele["events"] + events[None, :]}


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "max_rounds", "backend"))
def run_rounds(state, node_id, line, is_write, wdata=None, *,
               n_nodes: int, max_rounds: int = 64, backend: str = "ref"):
    """Drive op slots (node_id, line, is_write) int32 [R] to completion.

    ``wdata`` [R, W] carries per-op write payloads on a payload-plane
    state (``None`` = zeros; ignored on version-only states).

    Returns ``(state', versions[R], data[R, W], rounds_used,
    all_served, telemetry)`` — all device values; the only sync is
    whatever the CALLER materializes.  ``data`` holds each op's read
    payload (its group's final bytes; W = 0 on version-only states),
    produced INSIDE the fused loop — no extra host round trip buys the
    bytes.  ``telemetry`` is the flat counter dict (same keys as the
    sharded drivers', S = 1 — see :func:`zero_flat_tele`), accumulated
    in the loop carry; its per-line hit counters are bit-identical to a
    sharded plane's on the same op trace.  ``max_rounds`` bounds the
    loop (static); ``all_served`` is False if the bound was hit with
    ops still pending."""
    node_id = jnp.asarray(node_id, jnp.int32)
    line = jnp.asarray(line, jnp.int32)
    is_write = jnp.asarray(is_write, jnp.int32)
    width = payload_width(state)
    n_lines = state["words"].shape[0]
    if wdata is None:
        wdata = jnp.zeros((line.shape[0], width), jnp.int32)
    else:
        wdata = jnp.asarray(wdata, jnp.int32)
    write_back = "dirty" in state
    _note_trace(("driver", n_nodes, line.shape[0], max_rounds, backend,
                 write_back, width))

    def cond(carry):
        _, pending, _, _, rounds, _ = carry
        return jnp.logical_and(jnp.any(pending >= 0), rounds < max_rounds)

    def body(carry):
        st, pending, versions, data, rounds, tele = carry
        st, served, ver, rdata, events = _round_jit(
            st, node_id, pending, is_write, wdata, n_nodes=n_nodes,
            backend=backend)
        tele = _tele_round(tele, pending, served, is_write, events,
                           n_lines)
        versions = jnp.where(served, ver, versions)
        data = jnp.where(served[:, None], rdata, data)
        pending = jnp.where(served, jnp.int32(-1), pending)
        return st, pending, versions, data, rounds + 1, tele

    init = (state, line, jnp.zeros_like(line),
            jnp.zeros((line.shape[0], width), jnp.int32), jnp.int32(0),
            zero_flat_tele(n_lines))
    state, pending, versions, data, rounds, tele = jax.lax.while_loop(
        cond, body, init)
    return state, versions, data, rounds, jnp.all(pending < 0), tele


@functools.partial(jax.jit,
                   static_argnames=("modify", "n_nodes", "max_rounds",
                                    "backend"))
def run_rmw(state, node_id, line, operands=(), *, modify, n_nodes: int,
            max_rounds: int = 64, backend: str = "ref"):
    """Fused coherent read-modify-write — ONE jit call, zero host syncs.

    Two :func:`run_rounds` phases with the caller's transform in
    between, all inside one trace:

    1. READ phase — every slot presents a read op; the grant registers
       the node's S copy and returns the line's current payload bytes;
    2. ``modify(data, line, *operands)`` computes the new payload
       ``[R, W]`` from the freshly-read bytes (pure jnp — it runs on
       device between the phases; ``line`` is passed so the transform
       can mask padded ``line = -1`` rows);
    3. WRITE phase — every slot presents a write op carrying the new
       bytes, which lands through the engine's S->X upgrade path (the
       node holds S from phase 1, so an uncontended upgrade is a
       single CAS).

    This is the device-side form of the DES read-modify-write idiom
    (``xlocked`` + ``h.value`` + ``h.store``): pre-refactor callers
    (kvpool append, and any index wanting in-place node edits) ran the
    two phases as separate host-synced calls with the splice on the
    host in between — two dispatches and a full host round trip per
    batch.  ``modify`` must be a STATIC callable (pass the same
    function object per shape — e.g. an ``lru_cache``-kept closure —
    or every call retraces).

    Atomicity is per CALL: the RMW is coherent against every op outside
    this call (phase 2's upgrade fails if a peer intervened, and the
    spin re-acquires — but ``modify`` is not re-run, so slots of
    DIFFERENT nodes targeting the SAME line within one call would each
    write bytes derived from the shared phase-1 read, last writer
    winning.  Present cross-node conflicts as separate calls (the DES
    analogue: one latch scope per client RMW); duplicate (node, line)
    slots within a call must carry group-total bytes on every slot
    (write coalescing serializes to the LAST slot's payload — see
    kvpool's token splice).

    Returns ``(state', versions[R], data[R, W], rounds_used,
    all_served, telemetry)`` where ``versions``/``data`` are the WRITE
    phase's replies (the bytes the final versions name) and
    ``telemetry`` sums both phases' flat counter dicts."""
    node_id = jnp.asarray(node_id, jnp.int32)
    line = jnp.asarray(line, jnp.int32)
    # modify is a static arg: a fresh callable per call retraces, so it
    # belongs in the trace key or the TRACE_COUNTS guard tests go blind
    _note_trace(("rmw", modify, n_nodes, line.shape[0], max_rounds,
                 backend, "dirty" in state, payload_width(state)))
    state, _, data, r1, ok1, t1 = run_rounds(
        state, node_id, line, jnp.zeros_like(line), None,
        n_nodes=n_nodes, max_rounds=max_rounds, backend=backend)
    new_data = jnp.asarray(modify(data, line, *operands), jnp.int32)
    state, versions, data2, r2, ok2, t2 = run_rounds(
        state, node_id, line, jnp.ones_like(line), new_data,
        n_nodes=n_nodes, max_rounds=max_rounds, backend=backend)
    return (state, versions, data2, r1 + r2,
            jnp.logical_and(ok1, ok2), add_tele(t1, t2))
