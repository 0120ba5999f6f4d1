"""Fused index descent: a whole root-to-leaf walk in ONE jit call.

``run_rounds`` fused the protocol spin, but an index descent still had
to ladder DOWN the tree from the host: one fused dispatch per level
(plus one per right-link hop), so descent cost scaled with tree height
in *dispatch latency* — exactly the many-small-dispatches overhead that
one-sided RDMA indexes (Sherman) avoid by chaining their reads on the
NIC, and that MIND pushes off the critical path.

:func:`run_descent` nests the per-level coherence rounds inside an
outer ``lax.while_loop``: each iteration presents the batched S-latch
reads for every undone key's current line, runs ONE coherence round
(``engine._round_impl`` — grants, payload fetch, boundary
invalidations), decodes the returned node lanes with a caller-supplied
jittable ``transition`` (for the B-link tree:
``index.codec.descend_step(fanout)`` — child index, right-link hop,
at-leaf), advances each served key on device, and re-presents keys
whose read lost a latch race.  Keys at different depths advance
independently — the walk is a wavefront, not a level barrier — and the
carry (state, per-key line, per-level path buffer, level/hop counters)
never leaves the device.  An entire ``lookup_batch`` descent is ONE
dispatch with zero host syncs REGARDLESS OF TREE HEIGHT; the trace key
does not mention the height, so growing the tree never retraces
(``engine.TRACE_COUNTS`` proves it).

The ``transition`` contract (static callable, cache it per geometry or
every call retraces — see ``codec.descend_step``)::

    at_leaf[B], hop[B], nxt[B] = transition(data[B, W], key[B])

* ``at_leaf`` — the slot rests on its target node: record the lanes,
  stop presenting ops;
* ``hop`` — the slot re-presents at ``nxt`` WITHOUT counting a level
  (a B-link right-link hop; counted separately);
* otherwise the slot descends to ``nxt`` (one level).

The per-slot path buffer ``paths [B, path_cap]`` records the lines a
slot DESCENDED through (hops and the final leaf excluded) — the
insert-split path, produced inside the loop instead of by host
bookkeeping.  ``path_cap`` is static and height-independent (callers
pass a generous constant, e.g. the tree's link-hop bound).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .driver import _tele_round, zero_flat_tele
from .engine import _note_trace, _round_impl
from .state import payload_width

@functools.partial(jax.jit,
                   static_argnames=("transition", "n_nodes", "max_steps",
                                    "backend", "path_cap"))
def run_descent(state, node_id, key, root, *, transition, n_nodes: int,
                max_steps: int = 64, backend: str = "ref",
                path_cap: int = 16):
    """Drive descent slots (node_id, key, start line) int32 [B] to
    their leaves in ONE jit call.  ``root[i] = -1`` marks an inactive
    pad slot.  Requires a payload-plane state (the transition decodes
    real node bytes).

    Returns ``(state', line[B], lanes[B, W], levels[B], hops[B],
    paths[B, path_cap], path_len[B], steps_used, all_done,
    telemetry)`` — all device values: each slot's final line and its
    node lanes, how many levels it descended and right links it hopped,
    the internal lines it descended through, whether every slot settled
    within ``max_steps`` outer iterations (each costs one coherence
    round), and the flat telemetry counter dict accumulated in the
    carry (``driver.zero_flat_tele`` keys; descents are pure reads, so
    ``slot_whits`` stays zero)."""
    node_id = jnp.asarray(node_id, jnp.int32)
    key = jnp.asarray(key, jnp.int32)
    root = jnp.asarray(root, jnp.int32)
    b = root.shape[0]
    width = payload_width(state)
    n_lines = state["words"].shape[0]
    write_back = "dirty" in state
    _note_trace(("descent", transition, n_nodes, b, max_steps, backend,
                 write_back, width, path_cap))
    no_write = jnp.zeros((b,), jnp.int32)
    no_bytes = jnp.zeros((b, width), jnp.int32)

    def cond(carry):
        _, _, done, _, _, _, _, _, steps, _ = carry
        return jnp.logical_and(jnp.any(~done), steps < max_steps)

    def body(carry):
        st, cur, done, lanes, levels, hops, paths, plen, steps, tele \
            = carry
        line = jnp.where(done, jnp.int32(-1), cur)
        st, served, _, d, events = _round_impl(
            st, node_id, line, no_write, no_bytes, n_nodes=n_nodes,
            backend=backend)
        tele = _tele_round(tele, line, served, no_write, events, n_lines)
        at_leaf, hop, nxt = transition(d, key)
        move = jnp.logical_and(served, ~done)
        hop = jnp.logical_and(move, hop)
        at_leaf = jnp.logical_and(move, at_leaf)
        desc = jnp.logical_and(
            move, jnp.logical_and(~hop, ~at_leaf))
        lanes = jnp.where(at_leaf[:, None], d, lanes)
        # path buffer: record the line a slot descends FROM (drop rows
        # that stay put; a slot deeper than path_cap overwrites its
        # last entry — callers size path_cap past any reachable height)
        row = jnp.where(desc, jnp.arange(b), b)
        paths = paths.at[row, jnp.minimum(plen, path_cap - 1)].set(
            cur, mode="drop")
        plen = plen + desc.astype(jnp.int32)
        levels = levels + desc.astype(jnp.int32)
        hops = hops + hop.astype(jnp.int32)
        done = jnp.logical_or(done, at_leaf)
        advance = jnp.logical_and(move, ~at_leaf)
        cur = jnp.where(advance, nxt, cur)
        return (st, cur, done, lanes, levels, hops, paths, plen,
                steps + 1, tele)

    init = (state, root, root < 0,
            jnp.zeros((b, width), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.full((b, path_cap), -1, jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.int32(0),
            zero_flat_tele(n_lines))
    state, cur, done, lanes, levels, hops, paths, plen, steps, tele = \
        jax.lax.while_loop(cond, body, init)
    return (state, cur, lanes, levels, hops, paths, plen, steps,
            jnp.all(done), tele)

