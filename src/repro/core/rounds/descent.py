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

from .driver import (_add_tele, _same, _tele_rows, _zero_tele, flat_step,
                     tele_dict)
from .engine import _note_trace
from .state import payload_width


def descend(step, count, state, node, key, root, *, transition,
            max_steps: int, path_cap: int, tele0):
    """Walk descent slots (node, key, start line) [B] to their leaves:
    each iteration presents every undone slot's S-latch read in one
    round, decodes the served lanes with ``transition`` and advances the
    slot (the module docstring).  Returns ``(state', line[B], lanes[B, W],
    levels[B], hops[B], paths[B, path_cap], path_len[B], steps,
    all_done, tele)``."""
    b = root.shape[0]
    width = payload_width(state)
    no_write = jnp.zeros((b,), jnp.int32)
    no_bytes = jnp.zeros((b, width), jnp.int32)

    def left(done):
        return count(jnp.sum((~done).astype(jnp.int32)))

    def cond(c):
        return jnp.logical_and(c[10] > 0, c[8] < max_steps)

    def body(c):
        st, cur, done, lanes, levels, hops, paths, plen, steps, tele, _ = c
        line = jnp.where(done, jnp.int32(-1), cur)
        st, served, _, d, dtele = step(st, node, line, no_write, no_bytes)
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
                steps + 1, _add_tele(tele, dtele), left(done))

    done0 = root < 0
    init = (state, root, done0,
            jnp.zeros((b, width), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.full((b, path_cap), -1, jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.int32(0), tele0, left(done0))
    (state, cur, _, lanes, levels, hops, paths, plen, steps, tele,
     n) = jax.lax.while_loop(cond, body, init)
    return (state, cur, lanes, levels, hops, paths, plen, steps, n == 0,
            tele)


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
    round), and the telemetry dict (``driver.TELE_KEYS``) accumulated in
    the carry (descents are pure reads, so ``slot_whits`` stays
    zero)."""
    node_id = jnp.asarray(node_id, jnp.int32)
    key = jnp.asarray(key, jnp.int32)
    root = jnp.asarray(root, jnp.int32)
    _note_trace(("descent", transition, n_nodes, root.shape[0], max_steps,
                 backend, "dirty" in state, payload_width(state),
                 path_cap))
    *out, tele = descend(
        flat_step(n_nodes, backend), _same, state, node_id, key, root,
        transition=transition, max_steps=max_steps, path_cap=path_cap,
        tele0=_zero_tele(1, state["words"].shape[0]))
    return (*out, tele_dict(_tele_rows(tele)))
