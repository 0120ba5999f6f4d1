"""The fused on-device loops: run a verb to completion in ONE jit call.

Pre-refactor, ``run_ops_to_completion`` was a host-side Python loop that
synced served/pending masks back to the host after EVERY round — exactly
the per-op round-trip overhead MIND (arXiv 2107.00164) shows dominating
disaggregated-memory latency.  :func:`run_rounds` replaces it with a
``jax.lax.while_loop`` whose carry (state, pending lines, versions,
round counter) never leaves the device: unserved ops re-present
themselves round after round (the protocol's spin) with zero host↔device
syncs inside the loop, and the while_loop body traces the round engine
exactly once per shape (engine.TRACE_COUNTS proves it).

Each loop is written once, over a round STEP::

    step(state, node, pending, is_write, wdata)
        -> (state', served, version, data, tele_delta)

and a COUNT lift that turns a local count into the loop's global one.
One chip plugs in :func:`flat_step` (one engine round plus the flat
telemetry fold) and the identity; a mesh plugs in
``sharded._route_round`` (the round routed to each line's home shard)
and a ``psum`` over the mesh axis, inside its ``shard_map``.  The spin
(:func:`spin`) serves ``run_rounds``, ``run_rounds_sharded`` and both
transaction spins; the descent (``descent.descend``) serves ``run_descent``
and ``run_descent_sharded``.

Telemetry has one layout: the tuple of :data:`TELE_KEYS`, a delta per
round summed in the carry (:func:`_zero_tele`, :func:`_add_tele`).  A
jitted entry point turns it into the public dict where it returns
(:func:`_tele_rows` then :func:`tele_dict`): ``occupancy`` / ``deferred``
[S, S], ``served_per_home`` / ``replica_served`` [S], ``slot_hits`` /
``slot_whits`` [L] and ``events`` [S, 3], with S = 1 on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .engine import _note_trace, _round_jit
from .state import payload_width

TELE_KEYS = ("occupancy", "deferred", "served_per_home", "replica_served",
             "slot_hits", "slot_whits", "events")


def _zero_tele(n_shards: int, l_local: int):
    """Zeroed telemetry accumulator of one shard (the whole plane on one
    chip): (occupancy[S], deferred[S], served_at_home, replica_served,
    slot_hits[L_local], slot_whits[L_local], events[3]), ``events`` in
    ``engine.EVENT_KEYS`` order."""
    z = jnp.zeros((n_shards,), jnp.int32)
    zl = jnp.zeros((l_local,), jnp.int32)
    return (z, z, jnp.int32(0), jnp.int32(0), zl, zl,
            jnp.zeros((3,), jnp.int32))


def _add_tele(a, b):
    return tuple(x + y for x, y in zip(a, b))


def _tele_rows(tele):
    """A shard's accumulated telemetry as one row per home of every
    scalar and [S]-vector counter, the slab's per-slot counters as they
    are (``sharded._tele_specs`` stacks the rows across the mesh)."""
    occ, dfr, srv, rsrv, hits, whits, events = tele
    return (occ[None, :], dfr[None, :], srv[None], rsrv[None], hits,
            whits, events[None, :])


def tele_dict(rows) -> dict:
    """The public telemetry dict of :func:`_tele_rows`' rows."""
    return dict(zip(TELE_KEYS, rows))


def _same(x):
    """The count lift of one chip: a local count is the global one."""
    return x


def flat_step(n_nodes: int, backend: str):
    """The one-chip round step: one engine round, with its telemetry
    delta folded from the PRE-round line per slot (``pending``, -1 =
    done/pad).  The flat engine presents ops by line id, so its slot
    counters are line-indexed, and its occupancy is the count of valid
    pending slots."""
    def step(state, node, pending, is_write, wdata):
        state, served, ver, data, events = _round_jit(
            state, node, pending, is_write, wdata, n_nodes=n_nodes,
            backend=backend)
        with jax.named_scope("round/telemetry"):
            n_lines = state["words"].shape[0]
            valid = pending >= 0
            hit = jnp.logical_and(served, valid)
            hit_line = jnp.where(hit, pending, n_lines)   # n_lines = drop
            zl = jnp.zeros((n_lines,), jnp.int32)
            tele = (jnp.sum(valid.astype(jnp.int32))[None],
                    jnp.zeros((1,), jnp.int32),
                    jnp.sum(hit.astype(jnp.int32)), jnp.int32(0),
                    zl.at[hit_line].add(1, mode="drop"),
                    zl.at[jnp.where(is_write.astype(bool), hit_line,
                                    n_lines)].add(1, mode="drop"),
                    events)
        return state, served, ver, data, tele
    return step


def spin(step, count, state, node, line, is_write, wdata, *,
         max_rounds: int, tele0):
    """Serve op slots [R] round after round until none is pending (the
    protocol's spin) or ``max_rounds`` is hit.  Returns ``(state',
    versions[R], data[R, W], rounds, all_served, tele)``."""
    def left(pending):
        return count(jnp.sum((pending >= 0).astype(jnp.int32)))

    def cond(c):
        return jnp.logical_and(c[6] > 0, c[4] < max_rounds)

    def body(c):
        st, pending, versions, data, rounds, tele, _ = c
        st, served, ver, rdata, dtele = step(st, node, pending, is_write,
                                             wdata)
        versions = jnp.where(served, ver, versions)
        data = jnp.where(served[:, None], rdata, data)
        pending = jnp.where(served, jnp.int32(-1), pending)
        return (st, pending, versions, data, rounds + 1,
                _add_tele(tele, dtele), left(pending))

    init = (state, line, jnp.zeros_like(line),
            jnp.zeros((line.shape[0], wdata.shape[1]), jnp.int32),
            jnp.int32(0), tele0, left(line))
    state, _, versions, data, rounds, tele, n = jax.lax.while_loop(
        cond, body, init)
    return state, versions, data, rounds, n == 0, tele


def op_arrays(state, node_id, line, is_write, wdata):
    """Op slots as int32 arrays; ``wdata`` all-zero [R, W] when None."""
    line = jnp.asarray(line, jnp.int32)
    wdata = (jnp.zeros((line.shape[0], payload_width(state)), jnp.int32)
             if wdata is None else jnp.asarray(wdata, jnp.int32))
    return (jnp.asarray(node_id, jnp.int32), line,
            jnp.asarray(is_write, jnp.int32), wdata)


def two_phase(run, state, node_id, line, operands, modify):
    """The coherent read-modify-write over an ops driver ``run(state,
    node, line, is_write, wdata)``: a read phase, ``modify`` on the
    read bytes, a write phase of the new bytes (see :func:`run_rmw`)."""
    state, _, data, r1, ok1, t1 = run(state, node_id, line,
                                      jnp.zeros_like(line), None)
    new_data = jnp.asarray(modify(data, line, *operands), jnp.int32)
    state, versions, data2, r2, ok2, t2 = run(
        state, node_id, line, jnp.ones_like(line), new_data)
    return (state, versions, data2, r1 + r2, jnp.logical_and(ok1, ok2),
            {k: t1[k] + t2[k] for k in t1})


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
    bytes.  ``telemetry`` is the counter dict of the module docstring
    at S = 1, accumulated in the loop carry; its per-line hit counters
    are bit-identical to a sharded plane's on the same op trace.
    ``max_rounds`` bounds the loop (static); ``all_served`` is False if
    the bound was hit with ops still pending."""
    ops = op_arrays(state, node_id, line, is_write, wdata)
    _note_trace(("driver", n_nodes, ops[1].shape[0], max_rounds, backend,
                 "dirty" in state, payload_width(state)))
    *out, tele = spin(flat_step(n_nodes, backend), _same, state, *ops,
                      max_rounds=max_rounds,
                      tele0=_zero_tele(1, state["words"].shape[0]))
    return (*out, tele_dict(_tele_rows(tele)))


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
    ``telemetry`` sums both phases' counter dicts."""
    node_id = jnp.asarray(node_id, jnp.int32)
    line = jnp.asarray(line, jnp.int32)
    # modify is a static arg: a fresh callable per call retraces, so it
    # belongs in the trace key or the TRACE_COUNTS guard tests go blind
    _note_trace(("rmw", modify, n_nodes, line.shape[0], max_rounds,
                 backend, "dirty" in state, payload_width(state)))
    return two_phase(
        functools.partial(run_rounds, n_nodes=n_nodes,
                          max_rounds=max_rounds, backend=backend),
        state, node_id, line, operands, modify)
