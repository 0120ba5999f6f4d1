"""One bulk-synchronous coherence round — the full SELCC state machine.

TPU SPMD has no asynchronous RPC, so the protocol's message plane is
reshaped into deterministic ROUNDS (DESIGN.md Sec. 2).  One round:

  1. op slots are COALESCED per (node, line): a real node funnels its
     local ops through the local latch first (Sec. 5.2), so the engine
     groups duplicate (node, line) slots and issues ONE effective
     protocol op per group (a write if any member writes) — drivers no
     longer hand-enforce "one op per line per node";
  2. local cache hits are served (lazy latches: prior grants persist);
  3. misses become latch requests, applied by the latch_ops kernel
     (serialized per word — the NIC atomic unit's role in the paper):
     reads are FAA(+reader bit), fresh writes are CAS(FREE -> writer
     field), and an S holder's write is the paper's S->X UPGRADE —
     CAS(my reader bit -> writer field), which succeeds iff the holder
     is the sole reader (Algorithm 2 lines 8-13);
  4. a FAILED request's returned old word IS the embedded directory
     (Fig. 3) and becomes an invalidation applied at the ROUND BOUNDARY
     (the deterministic stand-in for the async RPC handlers): PeerWr /
     PeerUpgr -> every *other* holder releases (an upgrader never kills
     itself — two racing upgraders kill each other, drop to I, and one
     wins the fresh CAS next round, exactly Algorithm 2's release+
     reacquire fallback); PeerRd -> the writer downgrades M -> S.  The
     boundary transitions follow coherence.MSI_ON_PEER — the same table
     the DES handlers consume.

After the boundary the latch words are REBUILT from the cache states
(`coherence.directory_from_state`), so word and directory cannot drift
and failed readers' transient bits vanish without a second kernel pass.

Data plane: write-through by default (memory version current once the
latch moves).  A state built with ``make_state(..., write_back=True)``
carries per-copy dirty bits: write hits bump only the local version;
memory catches up when the holder downgrades, is invalidated, or is
evicted (:func:`evict_lines`) — the DES's write-back semantics, on
device.

Payload plane: a state built with ``make_state(..., payload_width=W)``
carries REAL GCL bytes (``mem_data`` [L, W] int32 + per-node
``cache_data`` copies).  Ops then take a ``wdata`` [R, W] operand:

* fetch-on-grant — an S/X grant copies ``mem_data[line]`` into the
  acquiring node's ``cache_data`` (the paper's combined latch+read
  round trip; on the pallas backend the gather reuses the ``gcl_fetch``
  kernel);
* write-apply — a granted write lands its group's final ``wdata`` in
  ``cache_data`` and, in write-through, ``mem_data``;
* dirty-flush-with-bytes — when a dirty M holder downgrades, is
  invalidated, or is evicted, its ``cache_data`` bytes flush to
  ``mem_data`` alongside the version;
* every served slot's reply carries the group's final payload bytes —
  reads return BYTES whose freshness the protocol guarantees, not just
  versions.

Versions under coalescing: a group's k writes serialize in slot order —
write slot j returns ``start + rank_j + 1`` and read slots in the group
return ``start + k`` (reads observe the node's fully-applied local
writes, as they would through the local latch).

Cache states per (node, line): 0=I 1=S 2=M (coherence.I/S/M).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import coherence as co
from ...kernels.gcl_fetch.ops import fetch as gcl_fetch_op
from ...kernels.latch_ops.ops import OP_CAS, OP_FAA, apply_batch

I, S, M = co.I, co.S, co.M

# Python-side trace bookkeeping: the body below executes once per jit
# TRACE (never per round — the while_loop body traces once), so tests
# can prove the fused driver compiles once per shape.
TRACE_COUNTS: dict = {}


# Protocol events a round counts, in the order of ``_round_impl``'s
# ``events`` vector: latch requests issued by group representatives (a
# CAS or FAA on the word), the requests that failed (they retry next
# round), and served slots whose group hit the node's own cached copy
# with no request at all (SELCC's lazy latch).
EVENT_KEYS = ("latch_requests", "latch_failed", "local_hits")


def _note_trace(key) -> None:
    TRACE_COUNTS[key] = TRACE_COUNTS.get(key, 0) + 1


def _round_impl(state, node_id, line, is_write, wdata=None, *,
                n_nodes: int, backend: str = "ref"):
    """Unjitted round body — :func:`coherence_round` is its jitted public
    face; the sharded plane (rounds/sharded.py) inlines it per home shard
    inside its own fused loop, where the state leaves are each shard's
    LOCAL slab and ``line`` carries local (striped) indices.

    ``wdata`` [R, W] carries write payloads on a payload-plane state
    (``None`` = all-zero payloads); returns a 5-tuple ``(state', served,
    version, data, events)`` where ``data`` [R, W] holds each served
    slot's read payload (W = 0 on version-only states) and ``events``
    int32 [3] counts this round's protocol events in
    :data:`EVENT_KEYS` order.

    Each phase runs under a ``jax.named_scope`` (``round/coalesce``,
    ``round/latch``, ``round/fetch``, ``round/boundary``,
    ``round/reply``, ``round/replica``, ``round/telemetry``), so a
    profile attributes device time to a phase by the ops' HLO metadata
    rather than by fusion numbers."""
    co.check_node_capacity(n_nodes)
    write_back = "dirty" in state
    words = state["words"]
    cstate = state["cache_state"]
    cver = state["cache_version"]
    mver = state["mem_version"]
    dirty = state.get("dirty")
    mdata = state.get("mem_data")
    cdata = state.get("cache_data")
    width = mdata.shape[1] if mdata is not None else 0
    n_lines = words.shape[0]
    r = line.shape[0]
    if wdata is None:
        wdata = jnp.zeros((r, width), jnp.int32)
    _note_trace(("round", n_nodes, n_lines, r, backend, write_back,
                 width))

    # ------------- 0. coalesce duplicate (node, line) slots ---------------
    with jax.named_scope("round/coalesce"):
        valid = line >= 0
        idx = jnp.maximum(line, 0)
        is_w = jnp.logical_and(is_write.astype(bool), valid)
        key = node_id * n_lines + idx
        eq = jnp.logical_and(key[:, None] == key[None, :],
                             jnp.logical_and(valid[:, None],
                                             valid[None, :]))
        first = jnp.argmax(eq, axis=1)             # my group's first slot
        is_rep = jnp.logical_and(valid, first == jnp.arange(r))
        grp_write = jnp.any(jnp.logical_and(eq, is_w[None, :]), axis=1)
        lower = jnp.tril(jnp.ones((r, r), bool), k=-1)
        in_grp_w = jnp.logical_and(eq, is_w[None, :])
        w_rank = jnp.sum(jnp.logical_and(in_grp_w, lower), axis=1) \
            .astype(jnp.int32)                     # writes before me
        n_w_grp = jnp.sum(in_grp_w, axis=1).astype(jnp.int32)
        # last write slot of my group — slot order IS the serialization
        # order, so its wdata is the group's final payload
        last_w = jnp.maximum(
            jnp.max(jnp.where(in_grp_w, jnp.arange(r), -1), axis=1), 0)

    with jax.named_scope("round/latch"):
        # --------- 1. local hits (lazy latches) ---------------------------
        st = cstate[node_id, idx]
        hit_read = jnp.logical_and(~grp_write, st >= S)
        hit_write = jnp.logical_and(grp_write, st == M)
        hit = jnp.logical_and(is_rep, jnp.logical_or(hit_read, hit_write))

        # --------- 2. latch requests for misses ---------------------------
        miss = jnp.logical_and(is_rep, ~hit)
        upgrade = jnp.logical_and(miss,
                                  jnp.logical_and(grp_write, st == S))
        fresh_w = jnp.logical_and(miss,
                                  jnp.logical_and(grp_write, st != S))
        read_miss = jnp.logical_and(miss, ~grp_write)
        bit_hi, bit_lo = co.bit_lanes(node_id)
        wf = co.writer_field_hi(node_id)
        req = {
            "line": jnp.where(miss, line, -1).astype(jnp.int32),
            "op": jnp.where(grp_write, OP_CAS, OP_FAA).astype(jnp.int32),
            "arg_hi": jnp.where(grp_write, wf, bit_hi).astype(jnp.int32),
            "arg_lo": jnp.where(grp_write, 0, bit_lo).astype(jnp.int32),
            # S->X upgrade compares against the holder's own bit; a fresh
            # write compares against FREE (zeros)
            "cmp_hi": jnp.where(upgrade, bit_hi, 0).astype(jnp.int32),
            "cmp_lo": jnp.where(upgrade, bit_lo, 0).astype(jnp.int32),
        }
        _, old_hi, _, ok = apply_batch(words, req, backend=backend)
        ok = ok.astype(bool)
        old_writer = co.writer_of_hi(old_hi)
        no_writer = old_writer < 0
        read_grant = jnp.logical_and(read_miss, no_writer)
        write_grant = jnp.logical_and(jnp.logical_or(upgrade, fresh_w), ok)
        granted = jnp.logical_or(read_grant, write_grant)
        served_rep = jnp.logical_or(hit, granted)

        # --------- grants + versions --------------------------------------
        # start version of the serialized group: the node's own copy on a
        # hit (may run ahead of memory under write-back), memory otherwise
        # (upgrades keep a coherent S copy, so memory is equally current).
        start = jnp.where(hit, cver[node_id, idx], mver[idx])
        k = jnp.where(jnp.logical_and(served_rep, grp_write), n_w_grp, 0)
        final = start + k
        # NOTE on scatters: invalid/no-op slots are routed to row n_nodes /
        # line n_lines and dropped, so duplicate in-bounds indices never
        # carry stale values (scatter order is unspecified).
        upd = granted
        cstate = cstate.at[jnp.where(upd, node_id, n_nodes), idx].set(
            jnp.where(read_grant, jnp.int8(S), jnp.int8(M)), mode="drop")
        cver = cver.at[jnp.where(served_rep, node_id, n_nodes), idx].set(
            final, mode="drop")
        wrote = jnp.logical_and(served_rep, grp_write)
        if write_back:
            dirty = dirty.at[jnp.where(wrote, node_id, n_nodes), idx].set(
                True, mode="drop")
        else:
            mver = mver.at[jnp.where(wrote, idx, n_lines)].add(
                k, mode="drop")

    # ------------- payload plane: fetch-on-grant + write-apply ------------
    gdata = None
    if width:
        with jax.named_scope("round/fetch"):
            # fetch-on-grant: a miss grant installs the memory bytes (the
            # paper's combined latch+read round trip — on the pallas
            # backend the gather reuses the gcl_fetch kernel); a hit
            # serves the node's own local copy, which may run ahead under
            # write-back
            if backend == "pallas":
                fetch_req = jnp.where(granted, idx, -1).astype(jnp.int32)
                no_bits = jnp.zeros_like(fetch_req)
                fetched_g, _, _, _, _ = gcl_fetch_op(
                    mdata, words, fetch_req, no_bits, no_bits,
                    backend="pallas")
                fetched = jnp.where(granted[:, None], fetched_g,
                                    mdata[idx])
            else:
                fetched = mdata[idx]
            base = jnp.where(hit[:, None], cdata[node_id, idx], fetched)
            # write-apply: the group's final payload is its LAST write
            # slot's wdata (slot order = serialization order, version
            # start+k)
            gdata = jnp.where(grp_write[:, None], wdata[last_w], base)
            cdata = cdata.at[jnp.where(served_rep, node_id, n_nodes),
                             idx].set(gdata, mode="drop")
            if not write_back:
                mdata = mdata.at[jnp.where(wrote, idx, n_lines)].set(
                    gdata, mode="drop")

    # ------------- 3/4. round-boundary invalidations ----------------------
    with jax.named_scope("round/boundary"):
        fail_w = jnp.logical_and(jnp.logical_or(upgrade, fresh_w), ~ok)
        fail_r = jnp.logical_and(read_miss, ~no_writer)
        wr_cnt = jnp.zeros((n_lines,), jnp.int32).at[
            jnp.where(fail_w, idx, n_lines)].add(1, mode="drop")
        rd_fail = jnp.zeros((n_lines,), bool).at[
            jnp.where(fail_r, idx, n_lines)].set(True, mode="drop")
        self_wr_fail = jnp.zeros((n_nodes, n_lines), jnp.int32).at[
            jnp.where(fail_w, node_id, n_nodes), idx].set(1, mode="drop")
        # PeerWr/PeerUpgr from any OTHER node kills a holder (upgraders
        # never kill themselves; two racing upgraders kill each other and
        # fall back to fresh acquisition — Algorithm 2's release+reacquire)
        other_fail = (wr_cnt[None, :] - self_wr_fail) > 0
        holder = cstate >= S
        kill = jnp.logical_and(other_fail, holder)
        # PeerRd with no competing writer: the M holder downgrades
        m_mask = cstate == M
        dg_line = jnp.logical_and(jnp.logical_and(rd_fail, wr_cnt == 0),
                                  jnp.any(m_mask, axis=0))
        dg_mask = jnp.logical_and(dg_line[None, :], m_mask)
        if write_back:
            # a dirty M holder leaving M (killed or downgraded) writes back
            flush = jnp.logical_and(jnp.logical_or(kill, dg_mask),
                                    jnp.logical_and(m_mask, dirty))
            flush_ver = jnp.max(jnp.where(flush, cver, 0), axis=0)
            mver = jnp.where(jnp.any(flush, axis=0), flush_ver, mver)
            if width:
                # dirty-flush-with-bytes: the holder's cache_data IS the
                # flush source of truth (at most one M holder per line, so
                # the masked sum selects exactly its row)
                flush_data = jnp.sum(
                    jnp.where(flush[:, :, None], cdata, 0), axis=0)
                mdata = jnp.where(jnp.any(flush, axis=0)[:, None],
                                  flush_data, mdata)
            dirty = jnp.logical_and(dirty, ~jnp.logical_or(kill, dg_mask))
        cstate = jnp.where(kill, jnp.int8(I), cstate)
        cstate = jnp.where(dg_mask, jnp.int8(S), cstate)
        # the word IS the directory: rebuild it from the post-boundary
        # states (also clears failed readers' transient bits without a
        # second pass)
        words = co.directory_from_state(cstate)

    # ------------- per-slot replies (coalesced groups fan back out) -------
    with jax.named_scope("round/reply"):
        served = jnp.where(valid, served_rep[first], False)
        slot_start = start[first]
        version = jnp.where(
            served,
            jnp.where(is_w, slot_start + w_rank + 1, slot_start + n_w_grp),
            0).astype(jnp.int32)
        if width:
            # every served slot replies with its group's FINAL payload
            # (the bytes version start+k names) — reads return real data
            data = jnp.where(served[:, None], gdata[first], 0)
        else:
            data = jnp.zeros((r, 0), jnp.int32)
    # unknown leaves (home directory, replica plane) carry through: the
    # flat engine is placement-oblivious by design
    new_state = dict(state)
    new_state.update({"words": words, "cache_state": cstate,
                      "cache_version": cver, "mem_version": mver})
    if write_back:
        new_state["dirty"] = dirty
    if width:
        new_state["mem_data"] = mdata
        new_state["cache_data"] = cdata
    if "replica" in state and state["replica"].shape[0] == n_lines:
        # refresh the read-replica image at the round boundary (the
        # shape guard skips home-shard slabs inside the sharded router,
        # which refreshes through a psum instead — see
        # sharded._replica_refresh): a line with no exclusive holder
        # has a current memory image, so snapshotting it is coherent
        with jax.named_scope("round/replica"):
            rep = state["replica"]
            rok = jnp.logical_and(rep, ~jnp.any(cstate == M, axis=0))
            new_state["replica_ok"] = rok
            new_state["replica_version"] = jnp.where(
                rok, mver, state["replica_version"])
            if "replica_data" in state:
                new_state["replica_data"] = jnp.where(
                    rok[:, None], mdata, state["replica_data"])
    with jax.named_scope("round/telemetry"):
        events = jnp.stack([
            jnp.sum(miss.astype(jnp.int32)),
            jnp.sum(jnp.logical_or(fail_w, fail_r).astype(jnp.int32)),
            jnp.sum(jnp.logical_and(valid, hit[first]).astype(jnp.int32)),
        ])
    return new_state, served, version, data, events


# the fused flat driver calls the round through this jitted form, so one
# trace serves both it and :func:`coherence_round`
_round_jit = jax.jit(_round_impl, static_argnames=("n_nodes", "backend"))


def coherence_round(state, node_id, line, is_write, wdata=None, *,
                    n_nodes: int, backend: str = "ref"):
    """One round of R op slots (node_id, line, is_write) int32 [R];
    line = -1 marks an empty slot.  ``wdata`` [R, W] carries write
    payloads on a payload-plane state (None = zeros).  Returns
    (state', served[R], version[R], data[R, W]) — ``data`` is each
    served slot's read payload (W = 0 on version-only states).

    Duplicate (node, line) slots are legal and coalesce (see module
    docstring); duplicate LINES across nodes contend through the latch
    kernel exactly like concurrent RDMA atomics."""
    return _round_jit(state, node_id, line, is_write, wdata,
                      n_nodes=n_nodes, backend=backend)[:4]


def _evict_impl(state, node_id, line):
    """Unjitted eviction body (shared with the sharded plane)."""
    write_back = "dirty" in state
    cstate = state["cache_state"]
    cver = state["cache_version"]
    mver = state["mem_version"]
    n_nodes, n_lines = cstate.shape
    valid = line >= 0
    idx = jnp.maximum(line, 0)
    new_state = dict(state)
    if write_back:
        dirty = state["dirty"]
        flush = jnp.logical_and(
            valid, jnp.logical_and(cstate[node_id, idx] == M,
                                   dirty[node_id, idx]))
        mver = mver.at[jnp.where(flush, idx, n_lines)].max(
            cver[node_id, idx], mode="drop")
        if "mem_data" in state:
            # eviction write-back carries the bytes, not just the version
            cdata = state["cache_data"]
            new_state["mem_data"] = state["mem_data"].at[
                jnp.where(flush, idx, n_lines)].set(
                    cdata[node_id, idx], mode="drop")
        new_state["dirty"] = dirty.at[
            jnp.where(valid, node_id, n_nodes), idx].set(False, mode="drop")
        new_state["mem_version"] = mver
    cstate = cstate.at[jnp.where(valid, node_id, n_nodes), idx].set(
        jnp.int8(I), mode="drop")
    new_state["cache_state"] = cstate
    new_state["words"] = co.directory_from_state(cstate)
    return new_state


@jax.jit
def evict_lines(state, node_id, line):
    """Evict (node, line) slots: release the holder's latch and, in
    write-back mode, flush a dirty exclusive copy to memory first (the
    DES `_maybe_evict` -> `_release_global_any` path).  line = -1 skips
    a slot.  Returns the new state."""
    new_state = _evict_impl(state, node_id, line)
    if "replica" in state:
        # an eviction flush can advance memory past the replica image:
        # conservatively invalidate; the next round's boundary refresh
        # republishes it
        n_lines = state["replica"].shape[0]
        line = jnp.asarray(line, jnp.int32)
        new_state["replica_ok"] = new_state["replica_ok"].at[
            jnp.where(line >= 0, line, n_lines)].set(False, mode="drop")
    return new_state
