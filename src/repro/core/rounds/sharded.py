"""Mesh-sharded device rounds: the FULL MSI engine, striped across shards.

`core/distributed_rounds.py` shards only the bare latch plane — one
latch-kernel application per round, overflow deferral punted to the
caller.  This module scales the complete PR-2 rounds engine — S->X
upgrade via CAS, structural write-back with dirty-bit flush,
per-(node, line) coalescing, eviction — across a ``shard_map`` mesh:

* every line-indexed leaf of the round state lives in PHYSICAL-SLOT
  layout: line ``l`` occupies slot ``p`` — ``p = state["home"][l]``
  when the state carries a home directory, else the identity — homing
  on shard ``p % n_shards`` at local index ``p // n_shards``, sharded
  over the line axis so each shard owns one contiguous slab.  Without a
  directory this is exactly the static stripe ``home = line %
  n_shards`` (``dsm/address.home_of``); WITH one, placement is dynamic:
  ``DevicePlane.rehome`` migrates hot lines by swapping slab rows
  across the mesh (:func:`rehome_exchange`) and installing the updated
  permutation, and the router consults the directory for every bucket
  and local-index computation;
* each round, every shard buckets its pending op slots by home and the
  buckets cross the mesh in ONE ``all_to_all``; the home shard runs the
  complete round body (`engine._round_impl`) against its local slab —
  all requests for a line meet at its home, so coalescing and latch
  contention are exact — and the (served, version, payload) replies
  return by a second ``all_to_all``: the paper's one-sided verbs as two
  collectives per round, zero control logic anywhere else.  On
  payload-plane states the request bucket entries widen from (node,
  line, isw) to carry a [W] ``wdata`` lane and the reply routes the
  read bytes back — the data plane rides the SAME two collectives as
  the latch traffic, no separate host-mediated copy channel;
* the whole spin lives in ONE jitted ``lax.while_loop``: the carry
  (sharded state, pending lines, versions, a psum'd done flag) never
  leaves the devices — zero host<->device syncs per round, and
  ``engine.TRACE_COUNTS`` proves one trace per shape;
* at every round boundary each home rebuilds the latch words of the
  lines its round named from their local MSI states
  (``coherence.directory_from_state`` inside ``_round_impl``), so the
  word<->directory invariant holds PER SHARD by construction;
* a request that overflows its (source, home) bucket — ``bucket_cap``
  models the NIC queue depth; the default ``cap = r`` can never
  overflow — is NOT dropped and NOT punted to the caller: it stays
  pending in the loop carry and re-presents next round, exactly like a
  latch-contention miss (defer-and-respin inside the fused loop);
* the loop carry also accumulates CONGESTION TELEMETRY — per-(source,
  home) bucket occupancy and defer counts, per-home served ops,
  per-slot hit counters, replica-served counts — surfaced as the last
  element of every fused driver's return tuple and, host-side, through
  ``PlaneResult.stats``.  The placement policy
  (:mod:`repro.core.rounds.placement`) turns it into re-homing and
  replication decisions;
* a state with a read-replica plane (``make_state(...,
  replicas=True)``) serves S-latch reads of replicated lines from the
  requester's OWN shard when the replica image is valid
  (``replica_ok``), skipping both collectives; each round boundary the
  homes republish the image via a psum (valid only where no exclusive
  holder exists), so a write to a replicated line invalidates its
  replicas through the normal MSI path.

Memory-side compute stays ZERO (the paper's scalability argument,
Sec. 4 / Fig. 7): a home shard only applies one-sided latch atomics and
slab scatters; there is no per-home message handler, queue, or thread.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .. import coherence as co
from ..distributed_rounds import _bucket
from . import state as st
from .engine import _evict_impl, _note_trace, _round_impl

OP_FIELDS = ("node", "line", "isw")


# --------------------------------------------------------------- state I/O

def auto_mesh(mesh):
    """``mesh`` with every axis in ``Auto`` mode.  ``jax.make_mesh``
    builds ``Explicit`` axes by default, which make the sharding part of
    each array's type; the stripe permutations (reshapes across the
    sharded line axis) are then refused.  The plane places and runs its
    state on the ``Auto`` form, so it accepts either kind of mesh."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _line_spec(name: str, ndim: int, axis: str) -> P:
    if name in st.GLOBAL_LEAVES:
        # global-line-indexed maps (home directory, replica plane) are
        # replicated across the mesh, never striped
        return P(*([None] * ndim))
    la = st.LINE_AXIS[name]
    return P(*[axis if d == la else None for d in range(ndim)])


def _state_specs(state, axis: str):
    return {k: _line_spec(k, v.ndim, axis) for k, v in state.items()}


def shard_state(state, mesh, axis: str = "shards"):
    """Flat (line-major) round state -> stripe layout, device_put across
    ``mesh[axis]``.  n_lines must divide evenly by the shard count."""
    n_shards = mesh.shape[axis]
    n_lines = state["words"].shape[0]
    if n_lines % n_shards:
        raise ValueError(
            f"n_lines={n_lines} not divisible by n_shards={n_shards}")
    striped = st.stripe_state(state, n_shards)
    mesh = auto_mesh(mesh)
    return {k: jax.device_put(
        v, NamedSharding(mesh, _line_spec(k, v.ndim, axis)))
        for k, v in striped.items()}


def unshard_state(state, mesh=None, axis: str = "shards", *,
                  n_shards: int | None = None):
    """Sharded stripe-layout state -> flat line-major state (host-side:
    gathers).  Accepts either the mesh or an explicit shard count."""
    if n_shards is None:
        n_shards = mesh.shape[axis]
    return st.unstripe_state({k: jnp.asarray(v) for k, v in state.items()},
                             n_shards)


def make_sharded_state(n_nodes: int, n_lines: int, mesh,
                       axis: str = "shards", *, write_back: bool = False,
                       payload_width: int = 0,
                       home_directory: bool = False,
                       replicas: bool = False):
    """Fresh sharded round state: ``make_state`` striped over the mesh.
    ``n_lines`` is rounded UP to a multiple of the shard count (the
    extra lines are ordinary cold lines no op needs to touch).
    ``payload_width=W`` stripes the GCL data plane (``mem_data`` /
    ``cache_data``) alongside the latch words; ``home_directory`` /
    ``replicas`` attach the (replicated) dynamic-placement and
    read-replica leaves."""
    n_shards = mesh.shape[axis]
    n_lines = ((n_lines + n_shards - 1) // n_shards) * n_shards
    return shard_state(st.make_state(n_nodes, n_lines,
                                     write_back=write_back,
                                     payload_width=payload_width,
                                     home_directory=home_directory,
                                     replicas=replicas),
                       mesh, axis)


def pad_ops(node_id, line, is_write, n_shards: int, wdata=None):
    """Pad op slots with empty (line = -1) entries so the slot count
    divides evenly across shards (each shard presents R/S slots).
    With ``wdata`` [R, W], pads it with zero payloads too and returns a
    4-tuple."""
    node_id = np.asarray(node_id, np.int32)
    line = np.asarray(line, np.int32)
    is_write = np.asarray(is_write, np.int32)
    pad = (-line.shape[0]) % n_shards
    if pad:
        node_id = np.concatenate([node_id, np.zeros(pad, np.int32)])
        line = np.concatenate([line, np.full(pad, -1, np.int32)])
        is_write = np.concatenate([is_write, np.zeros(pad, np.int32)])
    if wdata is None:
        return node_id, line, is_write
    wdata = np.asarray(wdata, np.int32)
    if pad:
        wdata = np.concatenate(
            [wdata, np.zeros((pad,) + wdata.shape[1:], np.int32)])
    return node_id, line, is_write, wdata


# ------------------------------------------------------------ one round

TELE_KEYS = ("occupancy", "deferred", "served_per_home", "replica_served",
             "slot_hits", "slot_whits", "events")


def _zero_tele(n_shards: int, l_local: int):
    """Zeroed telemetry accumulator — matches `_route_round`'s
    per-round deltas: (occupancy[S], deferred[S], served_at_home,
    replica_served, slot_hits[L_local], slot_whits[L_local],
    events[3]), ``events`` in ``engine.EVENT_KEYS`` order."""
    z = jnp.zeros((n_shards,), jnp.int32)
    zl = jnp.zeros((l_local,), jnp.int32)
    return (z, z, jnp.int32(0), jnp.int32(0), zl, zl,
            jnp.zeros((3,), jnp.int32))


def _add_tele(a, b):
    return tuple(x + y for x, y in zip(a, b))


def _tele_rows(tele):
    """A shard's accumulated telemetry tuple as ``shard_map`` outputs
    under :func:`_tele_specs`: a per-home row of every scalar and
    [S]-vector counter, the slab's per-slot counters as they are."""
    occ, dfr, srv, rsrv, hits, whits, events = tele
    return (occ[None, :], dfr[None, :], srv[None], rsrv[None], hits,
            whits, events[None, :])


def _tele_specs(axis: str):
    return (P(axis, None), P(axis, None), P(axis), P(axis), P(axis),
            P(axis), P(axis, None))


def _replica_refresh(state_l, *, n_shards: int, axis: str):
    """Republish the read-replica image at the round boundary: each home
    contributes version/bytes for its OWNED replicated lines where no
    exclusive holder exists (no M holder => the memory image is
    current), and a psum broadcasts the contributions to every shard.
    A write granted M at its home therefore drops ``replica_ok``
    everywhere at the very next boundary — replica invalidation rides
    the normal MSI write path, no extra protocol."""
    rep = state_l["replica"]
    l_total = rep.shape[0]
    perm = state_l.get("home")
    slot = (perm if perm is not None
            else jnp.arange(l_total, dtype=jnp.int32))
    my = jax.lax.axis_index(axis)
    owned = (slot % n_shards) == my
    loc = slot // n_shards
    no_m = ~jnp.any(state_l["cache_state"] == co.M, axis=0)  # [L_local]
    okc = jnp.logical_and(jnp.logical_and(rep, owned), no_m[loc])
    ok = jax.lax.psum(okc.astype(jnp.int32), axis) > 0
    ver = jax.lax.psum(
        jnp.where(okc, state_l["mem_version"][loc], 0), axis)
    out = dict(state_l)
    out["replica_ok"] = ok
    out["replica_version"] = jnp.where(ok, ver,
                                       state_l["replica_version"])
    if "replica_data" in state_l:
        data = jax.lax.psum(
            jnp.where(okc[:, None], state_l["mem_data"][loc], 0), axis)
        out["replica_data"] = jnp.where(ok[:, None], data,
                                        state_l["replica_data"])
    return out


def _route_round(state_l, node_l, pending_l, isw_l, wdata_l, *,
                 n_shards: int, axis: str, n_nodes: int, cap: int,
                 backend: str):
    """One sharded round, executing INSIDE shard_map on each shard's
    local slab: serve replica reads locally, bucket the remaining
    pending slots by home (through the home directory when present),
    all_to_all the buckets, run the full round body at the homes,
    all_to_all the replies back, then republish the replica image.  On
    payload-plane states the bucket entries widen from (node, line,
    isw) to carry a [W] ``wdata`` lane, and the reply all_to_all routes
    each served slot's read payload back the same way.  Returns
    (state_l', served[r] bool, version[r], data[r, W], tele) in local
    slot order — ``tele`` is this round's telemetry delta (see
    :func:`_zero_tele`); a slot that overflowed its bucket simply comes
    back unserved (its payload re-presents with it next round)."""
    width = wdata_l.shape[1]
    l_local = state_l["words"].shape[0]
    with jax.named_scope("route/bucket"):
        valid = pending_l >= 0
        idx = jnp.maximum(pending_l, 0)
        # replica serve: a pure read of a replicated line with a valid
        # boundary-snapshot image never leaves its source shard
        if "replica" in state_l:
            rserve = jnp.logical_and(
                jnp.logical_and(valid, isw_l == 0),
                jnp.logical_and(state_l["replica"][idx],
                                state_l["replica_ok"][idx]))
            route = jnp.where(rserve, jnp.int32(-1), pending_l)
            # serve from the PRE-round image: the local serve logically
            # precedes this round's writes (a boundary-snapshot read)
            rserve_ver = state_l["replica_version"][idx]
            rserve_data = (state_l["replica_data"][idx]
                           if "replica_data" in state_l else None)
        else:
            rserve = jnp.zeros_like(valid)
            route = pending_l
        # destination shard per slot: home directory when present, static
        # stripe otherwise (pads/replica-served slots -> bucket S = dropped)
        if "home" in state_l:
            perm = state_l["home"]
            home = jnp.where(route >= 0, perm[jnp.maximum(route, 0)]
                             % n_shards, n_shards)
        else:
            home = jnp.where(route >= 0, route % n_shards, n_shards)
        fields = OP_FIELDS + ("wdata",) if width else OP_FIELDS
        reqs = {"node": node_l, "line": route, "isw": isw_l}
        if width:
            reqs["wdata"] = wdata_l
        buckets, order, keep, (b_idx, s_idx), _ = _bucket(
            reqs, n_shards, cap, fields=fields, home=home)
    with jax.named_scope("route/exchange"):
        recv = {k: jax.lax.all_to_all(buckets[k], axis, 0, 0, tiled=False)
                for k in fields}
        flat = {k: v.reshape((n_shards * cap,) + v.shape[2:])
                for k, v in recv.items()}                       # [S*cap]
    with jax.named_scope("route/bucket"):
        # global line -> local slab index: directory slot // S when the
        # placement is dynamic, stripe layout's line // S otherwise
        if "home" in state_l:
            loc = jnp.where(flat["line"] >= 0,
                            perm[jnp.maximum(flat["line"], 0)] // n_shards,
                            -1).astype(jnp.int32)
        else:
            loc = jnp.where(flat["line"] >= 0, flat["line"] // n_shards,
                            -1).astype(jnp.int32)
    state_l, served_h, ver_h, data_h, events = _round_impl(
        state_l, flat["node"], loc, flat["isw"], flat.get("wdata"),
        n_nodes=n_nodes, backend=backend)
    if "replica" in state_l:
        with jax.named_scope("route/replica"):
            state_l = _replica_refresh(state_l, n_shards=n_shards,
                                       axis=axis)

    with jax.named_scope("route/exchange"):
        def back(x):
            return jax.lax.all_to_all(
                x.reshape((n_shards, cap) + x.shape[1:]), axis, 0, 0,
                tiled=False)
        r_served = back(served_h.astype(jnp.int32))
        r_ver = back(ver_h)
        inv = jnp.argsort(order)

        def unbucket(bucketed):
            gathered = bucketed[b_idx, s_idx]
            mask = keep.reshape((-1,) + (1,) * (gathered.ndim - 1))
            gathered = jnp.where(mask, gathered, 0)
            return gathered[inv]
        served = jnp.logical_or(unbucket(r_served).astype(bool), rserve)
        version = unbucket(r_ver)
        if width:
            r_data = unbucket(back(data_h))
        else:
            r_data = jnp.zeros((pending_l.shape[0], 0), jnp.int32)
        if "replica" in state_l:
            version = jnp.where(rserve, rserve_ver, version)
            if width and rserve_data is not None:
                r_data = jnp.where(rserve[:, None], rserve_data, r_data)
    # congestion telemetry (this round's delta, all source-local or
    # home-local): bucket occupancy / defers per destination home, ops
    # served at THIS home, replica-served reads, per-local-slot hits,
    # and the home's protocol events
    with jax.named_scope("round/telemetry"):
        sent = keep[inv]
        occ = jnp.zeros((n_shards,), jnp.int32).at[
            jnp.where(sent, home, n_shards)].add(1, mode="drop")
        dfr = jnp.zeros((n_shards,), jnp.int32).at[
            jnp.where(jnp.logical_and(route >= 0, ~sent), home,
                      n_shards)].add(1, mode="drop")
        served_at_home = jnp.sum(served_h.astype(jnp.int32))
        hit_slot = jnp.where(served_h, loc, l_local)
        hits = jnp.zeros((l_local,), jnp.int32).at[hit_slot].add(
            1, mode="drop")
        whits = jnp.zeros((l_local,), jnp.int32).at[
            jnp.where(flat["isw"].astype(bool), hit_slot, l_local)].add(
            1, mode="drop")
        tele = (occ, dfr, served_at_home,
                jnp.sum(rserve.astype(jnp.int32)), hits, whits, events)
    return state_l, served, version, r_data, tele


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "n_nodes", "bucket_cap",
                              "backend"))
def coherence_round_sharded(state, node_id, line, is_write, wdata=None,
                            *, mesh, axis: str = "shards", n_nodes: int,
                            bucket_cap: int | None = None,
                            backend: str = "ref"):
    """One sharded round over GLOBAL op slots [R] (R divisible by the
    shard count; line = -1 empty).  ``wdata`` [R, W] carries write
    payloads on a payload-plane state.  Returns (state', served[R],
    version[R], data[R, W]) — the sharded mirror of
    :func:`engine.coherence_round`, and the building block of the
    host-synced baseline loop that `benchmarks/fig7_rounds.py` measures
    the fused driver against.  Overflowed slots return unserved (the
    caller respins them, payload included)."""
    co.check_node_capacity(n_nodes)
    n_shards = mesh.shape[axis]
    node_id = jnp.asarray(node_id, jnp.int32)
    line = jnp.asarray(line, jnp.int32)
    is_write = jnp.asarray(is_write, jnp.int32)
    r_total = line.shape[0]
    if r_total % n_shards:
        raise ValueError(f"R={r_total} not divisible by "
                         f"n_shards={n_shards} (use pad_ops)")
    r = r_total // n_shards
    cap = bucket_cap if bucket_cap is not None else r
    width = st.payload_width(state)
    if wdata is None:
        wdata = jnp.zeros((r_total, width), jnp.int32)
    else:
        wdata = jnp.asarray(wdata, jnp.int32)
    write_back = "dirty" in state
    _note_trace(("sharded_round", n_shards, n_nodes,
                 state["words"].shape[0], r_total, cap, backend,
                 write_back, width, "home" in state, "replica" in state))
    specs = _state_specs(state, axis)

    def spmd(state_l, node_l, line_l, isw_l, wdata_l):
        state_l, served, ver, data, _ = _route_round(
            state_l, node_l, line_l, isw_l, wdata_l,
            n_shards=n_shards, axis=axis, n_nodes=n_nodes,
            cap=cap, backend=backend)
        return state_l, served, ver, data

    return jax.shard_map(
        spmd, mesh=auto_mesh(mesh),
        in_specs=(specs, P(axis), P(axis), P(axis), P(axis)),
        out_specs=(specs, P(axis), P(axis), P(axis)),
        check_vma=False,
    )(state, node_id, line, is_write, wdata)


# ------------------------------------------------------- the fused driver

@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "n_nodes", "max_rounds",
                              "bucket_cap", "backend"))
def run_rounds_sharded(state, node_id, line, is_write, wdata=None, *,
                       mesh, axis: str = "shards", n_nodes: int,
                       max_rounds: int = 64,
                       bucket_cap: int | None = None,
                       backend: str = "ref"):
    """Drive GLOBAL op slots [R] to completion across the mesh in ONE
    jit call — the sharded mirror of :func:`driver.run_rounds`.

    ``wdata`` [R, W] carries per-op write payloads on a payload-plane
    state; returns ``(state', versions[R], data[R, W], rounds_used,
    all_served, telemetry)``, all device values, where ``data`` holds
    each op's read payload routed back through the reply all_to_all and
    ``telemetry`` is the congestion-counter dict accumulated in the
    loop carry: ``occupancy``/``deferred`` [S, S] (row = source shard,
    col = destination home: bucket entries sent / deferred-by-
    overflow), ``served_per_home`` [S], ``replica_served`` [S] (per
    SOURCE shard), per-physical-slot ``slot_hits``/``slot_whits``
    [L] in slab-concatenation order (``DevicePlane`` remaps them to
    line ids through the directory), and ``events`` [S, 3], the round
    body's protocol events at each home (``engine.EVENT_KEYS``).
    Unserved slots (latch contention
    OR bucket overflow) re-present themselves — bytes included — round
    after round inside the fused ``lax.while_loop``; the done flag is a
    psum across shards, so the loop runs lockstep until every shard's
    slots are served or ``max_rounds`` is hit."""
    co.check_node_capacity(n_nodes)
    n_shards = mesh.shape[axis]
    node_id = jnp.asarray(node_id, jnp.int32)
    line = jnp.asarray(line, jnp.int32)
    is_write = jnp.asarray(is_write, jnp.int32)
    r_total = line.shape[0]
    if r_total % n_shards:
        raise ValueError(f"R={r_total} not divisible by "
                         f"n_shards={n_shards} (use pad_ops)")
    r = r_total // n_shards
    cap = bucket_cap if bucket_cap is not None else r
    width = st.payload_width(state)
    if wdata is None:
        wdata = jnp.zeros((r_total, width), jnp.int32)
    else:
        wdata = jnp.asarray(wdata, jnp.int32)
    write_back = "dirty" in state
    _note_trace(("sharded", n_shards, n_nodes, state["words"].shape[0],
                 r_total, cap, max_rounds, backend, write_back, width,
                 "home" in state, "replica" in state))
    specs = _state_specs(state, axis)
    l_local = state["words"].shape[0] // n_shards

    def spmd(state_l, node_l, line_l, isw_l, wdata_l):
        def n_pending(pending):
            return jax.lax.psum(
                jnp.sum((pending >= 0).astype(jnp.int32)), axis)

        def cond(carry):
            _, pending, _, _, rounds, _, done = carry
            return jnp.logical_and(~done, rounds < max_rounds)

        def body(carry):
            stt, pending, versions, data, rounds, tele, _ = carry
            stt, served, ver, rdata, dtele = _route_round(
                stt, node_l, pending, isw_l, wdata_l, n_shards=n_shards,
                axis=axis, n_nodes=n_nodes, cap=cap, backend=backend)
            versions = jnp.where(served, ver, versions)
            data = jnp.where(served[:, None], rdata, data)
            pending = jnp.where(served, jnp.int32(-1), pending)
            return (stt, pending, versions, data, rounds + 1,
                    _add_tele(tele, dtele), n_pending(pending) == 0)

        init = (state_l, line_l, jnp.zeros_like(line_l),
                jnp.zeros((line_l.shape[0], width), jnp.int32),
                jnp.int32(0), _zero_tele(n_shards, l_local),
                n_pending(line_l) == 0)
        state_l, pending, versions, data, rounds, tele, done = \
            jax.lax.while_loop(cond, body, init)
        return (state_l, versions, data, rounds, done) + _tele_rows(tele)

    out = jax.shard_map(
        spmd, mesh=auto_mesh(mesh),
        in_specs=(specs, P(axis), P(axis), P(axis), P(axis)),
        out_specs=(specs, P(axis), P(axis), P(), P()) + _tele_specs(axis),
        check_vma=False,
    )(state, node_id, line, is_write, wdata)
    return out[:5] + (dict(zip(TELE_KEYS, out[5:])),)


@functools.partial(
    jax.jit, static_argnames=("modify", "mesh", "axis", "n_nodes",
                              "max_rounds", "bucket_cap", "backend"))
def run_rmw_sharded(state, node_id, line, operands=(), *, modify, mesh,
                    axis: str = "shards", n_nodes: int,
                    max_rounds: int = 64, bucket_cap: int | None = None,
                    backend: str = "ref"):
    """Sharded mirror of :func:`repro.core.rounds.driver.run_rmw`: the
    coherent read-modify-write's two fused spin loops (S-grant read,
    ``modify``, S->X upgrade write) run back to back inside ONE jit
    call, each crossing the mesh through the usual two all_to_alls per
    round.  ``modify(data, line, *operands)`` runs replicated between
    the phases on the gathered ``[R, W]`` reply bytes.  Same return
    contract as :func:`run_rounds_sharded` (telemetry summed over both
    phases), with the write phase's versions/bytes."""
    node_id = jnp.asarray(node_id, jnp.int32)
    line = jnp.asarray(line, jnp.int32)
    _note_trace(("rmw_sharded", modify, mesh.shape[axis], n_nodes,
                 state["words"].shape[0], line.shape[0], bucket_cap,
                 backend, "dirty" in state, st.payload_width(state),
                 "home" in state, "replica" in state))
    state, _, data, r1, ok1, t1 = run_rounds_sharded(
        state, node_id, line, jnp.zeros_like(line), None, mesh=mesh,
        axis=axis, n_nodes=n_nodes, max_rounds=max_rounds,
        bucket_cap=bucket_cap, backend=backend)
    new_data = jnp.asarray(modify(data, line, *operands), jnp.int32)
    state, versions, data2, r2, ok2, t2 = run_rounds_sharded(
        state, node_id, line, jnp.ones_like(line), new_data, mesh=mesh,
        axis=axis, n_nodes=n_nodes, max_rounds=max_rounds,
        bucket_cap=bucket_cap, backend=backend)
    return (state, versions, data2, r1 + r2,
            jnp.logical_and(ok1, ok2), {k: t1[k] + t2[k] for k in t1})


@functools.partial(
    jax.jit, static_argnames=("transition", "mesh", "axis", "n_nodes",
                              "max_steps", "bucket_cap", "backend",
                              "path_cap"))
def run_descent_sharded(state, node_id, key, root, *, transition, mesh,
                        axis: str = "shards", n_nodes: int,
                        max_steps: int = 64,
                        bucket_cap: int | None = None,
                        backend: str = "ref", path_cap: int = 16):
    """Sharded mirror of :func:`repro.core.rounds.descent.run_descent`:
    the whole root-to-leaf wavefront runs inside ONE jit call on the
    mesh.  Each outer iteration routes every undone slot's S-latch read
    to its line's home shard through the usual two all_to_alls
    (`_route_round`), then applies the caller's ``transition`` to the
    replies LOCALLY on the slot's own shard — slots never migrate, only
    their requests do, so the per-slot carry (current line, path
    buffer, level/hop counters) stays put and the done flag is the one
    psum.  A slot whose read lost a latch race OR overflowed its
    routing bucket simply re-presents next iteration.  Same return
    contract as ``run_descent`` (slots in global order, ``steps`` and
    ``all_done`` replicated) plus a trailing telemetry dict (the
    :func:`run_rounds_sharded` congestion counters, accumulated over
    every descent step)."""
    co.check_node_capacity(n_nodes)
    n_shards = mesh.shape[axis]
    node_id = jnp.asarray(node_id, jnp.int32)
    key = jnp.asarray(key, jnp.int32)
    root = jnp.asarray(root, jnp.int32)
    r_total = root.shape[0]
    if r_total % n_shards:
        raise ValueError(f"B={r_total} not divisible by "
                         f"n_shards={n_shards} (use pad_ops)")
    r = r_total // n_shards
    cap = bucket_cap if bucket_cap is not None else r
    width = st.payload_width(state)
    if not width:
        raise ValueError("run_descent_sharded needs a payload-plane "
                         "state (the transition decodes node bytes)")
    write_back = "dirty" in state
    _note_trace(("descent_sharded", transition, n_shards, n_nodes,
                 state["words"].shape[0], r_total, cap, max_steps,
                 backend, write_back, width, path_cap,
                 "home" in state, "replica" in state))
    specs = _state_specs(state, axis)
    l_local = state["words"].shape[0] // n_shards

    def spmd(state_l, node_l, key_l, root_l):
        b = root_l.shape[0]
        no_write = jnp.zeros((b,), jnp.int32)
        no_bytes = jnp.zeros((b, width), jnp.int32)

        def n_undone(done):
            return jax.lax.psum(jnp.sum((~done).astype(jnp.int32)),
                                axis)

        def cond(carry):
            _, _, _, _, _, _, _, _, steps, _, gdone = carry
            return jnp.logical_and(~gdone, steps < max_steps)

        def body(carry):
            (stt, cur, done, lanes, levels, hops, paths, plen, steps,
             tele, _) = carry
            line = jnp.where(done, jnp.int32(-1), cur)
            stt, served, _, d, dtele = _route_round(
                stt, node_l, line, no_write, no_bytes,
                n_shards=n_shards, axis=axis, n_nodes=n_nodes, cap=cap,
                backend=backend)
            at_leaf, hop, nxt = transition(d, key_l)
            move = jnp.logical_and(served, ~done)
            hop = jnp.logical_and(move, hop)
            at_leaf = jnp.logical_and(move, at_leaf)
            desc = jnp.logical_and(
                move, jnp.logical_and(~hop, ~at_leaf))
            lanes = jnp.where(at_leaf[:, None], d, lanes)
            row = jnp.where(desc, jnp.arange(b), b)
            paths = paths.at[row, jnp.minimum(plen, path_cap - 1)].set(
                cur, mode="drop")
            plen = plen + desc.astype(jnp.int32)
            levels = levels + desc.astype(jnp.int32)
            hops = hops + hop.astype(jnp.int32)
            done = jnp.logical_or(done, at_leaf)
            advance = jnp.logical_and(move, ~at_leaf)
            cur = jnp.where(advance, nxt, cur)
            return (stt, cur, done, lanes, levels, hops, paths, plen,
                    steps + 1, _add_tele(tele, dtele),
                    n_undone(done) == 0)

        done0 = root_l < 0
        init = (state_l, root_l, done0,
                jnp.zeros((b, width), jnp.int32),
                jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.full((b, path_cap), -1, jnp.int32),
                jnp.zeros((b,), jnp.int32), jnp.int32(0),
                _zero_tele(n_shards, l_local), n_undone(done0) == 0)
        (state_l, cur, _, lanes, levels, hops, paths, plen, steps,
         tele, gdone) = jax.lax.while_loop(cond, body, init)
        return (state_l, cur, lanes, levels, hops, paths, plen, steps,
                gdone) + _tele_rows(tele)

    out = jax.shard_map(
        spmd, mesh=auto_mesh(mesh),
        in_specs=(specs, P(axis), P(axis), P(axis)),
        out_specs=(specs, P(axis), P(axis), P(axis), P(axis), P(axis),
                   P(axis), P(), P()) + _tele_specs(axis),
        check_vma=False,
    )(state, node_id, key, root)
    return out[:9] + (dict(zip(TELE_KEYS, out[9:])),)


# --------------------------------------------------------------- eviction

@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "bucket_cap"))
def evict_lines_sharded(state, node_id, line, *, mesh,
                        axis: str = "shards",
                        bucket_cap: int | None = None):
    """Sharded :func:`engine.evict_lines`: eviction slots [R] are routed
    to their home shards (same bucket + all_to_all machinery, overflow
    defers and respins) and applied to the local slabs — releasing the
    holder's latch and flushing dirty exclusive copies first in
    write-back states.  Returns the new sharded state."""
    n_shards = mesh.shape[axis]
    node_id = jnp.asarray(node_id, jnp.int32)
    line = jnp.asarray(line, jnp.int32)
    r_total = line.shape[0]
    if r_total % n_shards:
        raise ValueError(f"R={r_total} not divisible by "
                         f"n_shards={n_shards} (use pad_ops)")
    r = r_total // n_shards
    cap = bucket_cap if bucket_cap is not None else r
    # evictions always land once routed: ceil(r / cap) rounds suffice
    max_iters = (r + cap - 1) // cap
    specs = _state_specs(state, axis)

    def spmd(state_l, node_l, line_l):
        def body(i, carry):
            stt, pending = carry
            reqs = {"node": node_l, "line": pending}
            if "home" in stt:
                perm = stt["home"]
                home = jnp.where(pending >= 0,
                                 perm[jnp.maximum(pending, 0)]
                                 % n_shards, n_shards)
            else:
                home = None
            buckets, order, keep, _, _ = _bucket(
                reqs, n_shards, cap, fields=("node", "line"),
                home=home)
            recv = {k: jax.lax.all_to_all(buckets[k], axis, 0, 0,
                                          tiled=False)
                    for k in ("node", "line")}
            flat = {k: v.reshape(-1) for k, v in recv.items()}
            if "home" in stt:
                loc = jnp.where(flat["line"] >= 0,
                                perm[jnp.maximum(flat["line"], 0)]
                                // n_shards, -1).astype(jnp.int32)
            else:
                loc = jnp.where(flat["line"] >= 0,
                                flat["line"] // n_shards, -1) \
                    .astype(jnp.int32)
            stt = _evict_impl(stt, flat["node"], loc)
            sent = keep[jnp.argsort(order)]        # per-original slot
            pending = jnp.where(sent, jnp.int32(-1), pending)
            return stt, pending
        state_l, _ = jax.lax.fori_loop(0, max_iters, body,
                                       (state_l, line_l))
        if "replica" in state_l:
            # eviction flushes can advance memory: invalidate the
            # replica image of every evicted line mesh-wide (psum'd
            # union of the per-shard request slots); the next round's
            # boundary refresh republishes it
            l_total = state_l["replica"].shape[0]
            emask = jnp.zeros((l_total,), jnp.int32).at[
                jnp.where(line_l >= 0, line_l, l_total)].add(
                1, mode="drop")
            emask = jax.lax.psum(emask, axis) > 0
            state_l = dict(state_l)
            state_l["replica_ok"] = jnp.logical_and(
                state_l["replica_ok"], ~emask)
        return state_l

    return jax.shard_map(
        spmd, mesh=auto_mesh(mesh),
        in_specs=(specs, P(axis), P(axis)),
        out_specs=specs,
        check_vma=False,
    )(state, node_id, line)


# ----------------------------------------------------------- re-homing

@functools.partial(
    jax.jit, static_argnames=("mesh", "axis"))
def rehome_exchange(state, src_slot, dst_slot, new_home, *, mesh,
                    axis: str = "shards"):
    """Migrate slab rows between physical slots and install a new home
    directory — the device half of :meth:`DevicePlane.rehome`.

    ``src_slot``/``dst_slot`` [M] int32 (replicated; -1 = empty slot)
    describe row moves in PHYSICAL slot ids: the row currently at slot
    ``src_slot[i]`` (shard ``src % S``, local index ``src // S``) moves
    to slot ``dst_slot[i]``.  The move set must be a permutation of the
    touched slots (every destination is also some move's source —
    ``plane.rehome`` builds pairwise swaps), otherwise rows are lost;
    ``new_home`` [L] int32 is the post-exchange directory, installed
    replicated.  Legal only at op-quiescent boundaries: the exchange
    moves EVERY line-indexed leaf (latch words, MSI states, versions,
    payloads, dirty bits) as one bucketed all_to_all — the same
    machinery as request routing, with the slab row riding as the
    bucket payload — so in-flight ops would race the migration.
    Global-line-indexed leaves (the replica plane) key by line id, not
    slot, and pass through unchanged."""
    if "home" not in state:
        raise ValueError("rehome_exchange needs a home-directory state "
                         "(make_state(..., home_directory=True))")
    n_shards = mesh.shape[axis]
    src_slot = jnp.asarray(src_slot, jnp.int32)
    dst_slot = jnp.asarray(dst_slot, jnp.int32)
    new_home = jnp.asarray(new_home, jnp.int32)
    m = src_slot.shape[0]
    l_total = state["words"].shape[0]
    l_local = l_total // n_shards
    moved = tuple(sorted(k for k in state
                         if k not in st.GLOBAL_LEAVES))
    _note_trace(("rehome", n_shards, l_total, m, moved,
                 "replica" in state))
    specs = _state_specs(state, axis)

    def spmd(state_l, src, dst, perm_new):
        my = jax.lax.axis_index(axis)
        mine = jnp.logical_and(src >= 0, src % n_shards == my)
        sloc = jnp.where(mine, src // n_shards, 0)
        reqs = {"line": jnp.where(mine, dst, -1),
                "dloc": jnp.where(mine, dst // n_shards, 0)}
        rows = {}
        for k in moved:
            v = jnp.moveaxis(state_l[k], st.LINE_AXIS[k], 0)
            rows["row_" + k] = v[sloc].astype(jnp.int32)
        reqs.update(rows)
        home = jnp.where(mine, dst % n_shards, n_shards)
        # cap = m: at most m sends exist mesh-wide, so no bucket can
        # overflow and one exchange always completes
        buckets, _, _, _, _ = _bucket(
            reqs, n_shards, m, fields=tuple(reqs), home=home)
        recv = {k: jax.lax.all_to_all(buckets[k], axis, 0, 0,
                                      tiled=False)
                for k in reqs}
        flat = {k: v.reshape((n_shards * m,) + v.shape[2:])
                for k, v in recv.items()}
        ok = flat["line"] >= 0
        dloc = jnp.where(ok, flat["dloc"], l_local)  # OOB drop for pads
        out = dict(state_l)
        for k in moved:
            v = jnp.moveaxis(state_l[k], st.LINE_AXIS[k], 0)
            v = v.at[dloc].set(flat["row_" + k].astype(v.dtype),
                               mode="drop")
            out[k] = jnp.moveaxis(v, 0, st.LINE_AXIS[k])
        out["home"] = perm_new
        return out

    return jax.shard_map(
        spmd, mesh=auto_mesh(mesh),
        in_specs=(specs, P(), P(), P()),
        out_specs=specs,
        check_vma=False,
    )(state, src_slot, dst_slot, new_home)
