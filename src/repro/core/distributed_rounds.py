"""Distributed coherence rounds: the latch plane at mesh scale.

`core/jax_protocol.py` runs the bulk-synchronous protocol against one
latch-word array; THIS module shards that array across the mesh (lines
striped by `home = line % n_shards` by default — dsm/address.home_of —
or by a caller-supplied home-directory lookup, see `_bucket`) and
routes each round's requests to their home shards with ONE all_to_all,
applies them there with the `latch_ops` kernel (per-word serialization =
the NIC atomic unit), and routes the old-word replies back with a second
all_to_all — the paper's one-sided verbs expressed as two collectives per
round, with ZERO control logic on the home side.

Shapes are static: each shard presents R request slots per round; buckets
pad to capacity R (line = -1 marks empty).  Requests that overflow a
bucket are deferred to the next round by the caller (spin semantics) —
this module is one round of the LATCH plane only.  The full sharded MSI
engine (upgrades, write-back, coalescing, in-loop overflow deferral)
lives in :mod:`repro.core.rounds.sharded`, which reuses :func:`_bucket`
for its request routing — passing home-directory lookups as the
``home`` override when line placement is dynamic (``state["home"]``).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels.latch_ops.ops import apply_batch

FIELDS = ("line", "op", "arg_hi", "arg_lo", "cmp_hi", "cmp_lo")


def make_sharded_words(n_lines: int, mesh, axis: str = "model"):
    n = mesh.shape[axis]
    assert n_lines % n == 0
    words = jnp.zeros((n_lines, 2), jnp.int32)
    from .rounds.sharded import auto_mesh
    return jax.device_put(
        words, jax.sharding.NamedSharding(auto_mesh(mesh), P(axis, None)))


def _bucket(requests, n_shards: int, cap: int, fields=FIELDS, home=None):
    """Sort each shard's local requests into per-home buckets [S, cap].

    ``fields`` selects which request leaves ride along (the latch plane
    routes the six kernel fields; the full sharded engine —
    rounds/sharded.py — routes (node, line, isw) plus, on payload-plane
    states, a [R, W] ``wdata`` lane — any field may carry trailing
    dimensions and buckets to [S, cap, \\*rest]).  ``home`` is the
    per-slot destination shard ([R] int32, ``n_shards`` = pad/no-send);
    when omitted it defaults to the static stripe placement ``home =
    line % n_shards`` derived from ``requests["line"]`` (the sharded MSI
    engine passes home-directory lookups instead).  Requests past a
    bucket's capacity are NOT silently sent: they show up in the
    returned ``keep`` mask (False in sorted order; ``keep[argsort(
    order)]`` is the per-original-slot sent mask) and the ``dropped``
    count, so callers either respin them (sharded engine, in-loop) or
    surface the count (this module's single-round API)."""
    line = requests["line"]
    if home is None:
        home = jnp.where(line >= 0, line % n_shards, n_shards)  # pad bucket
    order = jnp.argsort(home)                                # stable
    sorted_reqs = {k: requests[k][order] for k in fields}
    home_sorted = home[order]
    # slot within bucket
    onehot = jax.nn.one_hot(home_sorted, n_shards + 1, dtype=jnp.int32)
    slot = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                               home_sorted[:, None], 1)[:, 0]
    keep = jnp.logical_and(home_sorted < n_shards, slot < cap)
    # non-kept slots (pads, overflow) scatter OUT OF BOUNDS and drop —
    # routing them to a real bucket cell (the pre-fix (0, cap-1)) let a
    # pad/overflow slot clobber a legitimate request whenever its bucket
    # was exactly full (scatter order is unspecified)
    b_idx = jnp.where(keep, home_sorted, n_shards)
    s_idx = jnp.where(keep, slot, 0)
    out = {}
    for k in fields:
        v = sorted_reqs[k]
        init = jnp.full((n_shards, cap) + v.shape[1:],
                        -1 if k == "line" else 0, jnp.int32)
        out[k] = init.at[b_idx, s_idx].set(v, mode="drop")
    dropped = jnp.sum(jnp.logical_and(home_sorted < n_shards,
                                      ~keep).astype(jnp.int32))
    return out, order, keep, (b_idx, s_idx), dropped


def distributed_latch_round(words, requests, *, mesh, axis: str = "model",
                            backend: str = "ref"):
    """words: [n_lines, 2] sharded P(axis, None) (striped by line%S after
    a caller-side permutation — see `stripe`/`unstripe`); requests: dict of
    [R] int32 per shard, GLOBAL line ids, sharded P(axis).

    Returns (new_words, old_hi [R], old_lo [R], ok [R], dropped_count)."""
    n = mesh.shape[axis]
    r = requests["line"].shape[0] // n      # per-shard slots (global R = n*r)
    cap = r                                  # bucket capacity

    def body(words_local, req_local):
        req_local = {k: v for k, v in req_local.items()}
        buckets, order, keep, scatter_idx, dropped = _bucket(
            {k: req_local[k] for k in FIELDS}, n, cap)
        # exchange request buckets: [S, cap] -> recv [S, cap]
        recv = {k: jax.lax.all_to_all(buckets[k], axis, 0, 0, tiled=False)
                for k in FIELDS}
        flat = {k: recv[k].reshape(-1) for k in FIELDS}
        # global line -> local slab index (stripe layout: local = line // n)
        loc = jnp.where(flat["line"] >= 0, flat["line"] // n, -1)
        new_words, old_hi, old_lo, ok = apply_batch(
            words_local, dict(flat, line=loc.astype(jnp.int32)),
            backend=backend)
        # route replies back to the requesting shards
        def back(x):
            return jax.lax.all_to_all(x.reshape(n, cap), axis, 0, 0,
                                      tiled=False)
        r_hi, r_lo, r_ok = back(old_hi), back(old_lo), back(ok)
        # un-bucket into the original request order
        b_idx, s_idx = scatter_idx
        inv = jnp.argsort(order)

        def unbucket(bucketed):
            gathered = bucketed[b_idx, s_idx]
            gathered = jnp.where(keep, gathered, 0)
            return gathered[inv]
        return (new_words, unbucket(r_hi), unbucket(r_lo),
                unbucket(r_ok.astype(jnp.int32)),
                jax.lax.psum(dropped, axis))

    from .rounds.sharded import auto_mesh
    spec_req = {k: P(axis) for k in FIELDS}
    return jax.shard_map(
        body, mesh=auto_mesh(mesh),
        in_specs=(P(axis, None), spec_req),
        out_specs=(P(axis, None), P(axis), P(axis), P(axis), P()),
        check_vma=False,
    )(words, requests)


def stripe(words_flat, n_shards: int):
    """[L,2] line-major -> stripe-major layout (home-contiguous).
    Thin alias of ``rounds.state.stripe_lines`` so the latch plane and
    the full sharded engine share ONE permutation (lazy import: this
    module is imported by rounds/sharded.py)."""
    from .rounds.state import stripe_lines
    return stripe_lines(words_flat, n_shards, 0)


def unstripe(words_striped, n_shards: int):
    from .rounds.state import unstripe_lines
    return unstripe_lines(words_striped, n_shards, 0)
