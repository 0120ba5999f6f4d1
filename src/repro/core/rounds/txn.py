"""Transaction concurrency control as ONE fused device loop.

The paper's Sec. 8.2 argument is that classic CC algorithms fall out of
the SELCC abstraction almost for free: latches are cache-line states,
tuple headers are payload bytes, and 2PL / TO need no server-side txn
logic at all.  ``apps/txn.py`` shows that on the host DES; this module
shows it on the device rounds plane — the whole batch of transactions
(acquire, execute, validate, commit/abort, RETRY) runs inside a single
jitted ``lax.while_loop``, with three coherence spins per scheduler
iteration and zero host syncs.

Line layout — each GCL packs a latch word plus ``T`` tuple headers into
its payload lanes (``W = 2 + 2*T``):

    lane 0           lock word: 0 = free, else holder's slot index + 1
    lane 1           committed-writes counter (the 2PL workload effect)
    lane 2+2t, 3+2t  tuple t's (read_ts, write_ts) header   (TO)

A transaction batch is ``node [B]``, ``glines [B, G]`` (each txn's
sorted ascending lines, ``-1``-padded at the END — canonical order is
the caller's contract and is validated host-side), ``rmask/wmask
[B, G, T]`` tuple touch masks, and ``ts [B]`` (TO timestamps, assigned
by the client at txn begin — batch arrival order need not match, which
is exactly what makes TO aborts real).

Per outer iteration, every live txn presents its NEXT line in canonical
order (so any deadlock cycle would need an ascending-order cycle —
impossible: deadlock-freedom by construction):

1. DEDUP — duplicate wanted lines keep only the lowest global slot
   (the rounds engine coalesces duplicate (node, line) ops, so one
   presenter per line per spin is a hard requirement, and the static
   priority makes flat and sharded planes bit-identical);
2. READ spin — winners read their line; ``lock == 0`` means acquired
   (no-wait: a held line is an immediate abort+retry, not a wait — the
   loser releases its whole held prefix and restarts from k = 0, the
   defer/respin idiom generalized from sharded bucket overflow);
3. ACQUIRE spin — acquired slots write the lanes back with the lock
   word set.  The read lanes are CARRIED in the loop (a held line
   cannot change under us, so the copy stays fresh by construction);
4. txns that acquired their last line APPLY their algorithm on the
   carried lanes (2PL: bump every write-line's counter, always commit;
   TO: the host engine's exact per-GCL, per-sorted-tuple timestamp
   checks — including its partial-update leak on abort — as a
   statically unrolled scan with a running ``stopped`` flag);
5. FINALIZE spin — completing txns write ALL their lines with new
   lanes and ``lock = 0`` in one combined publish-and-release write;
   no-wait losers write their held prefix back unchanged (releases).
   Every line written here is held by exactly one finishing txn, so
   the [B*G] slots never collide.

Commit/abort decisions and final memory images are bit-identical to
the host ``TxnEngine`` replayed sequentially in device completion
order ``(exec_step, slot)`` — txns completing in the same iteration
hold disjoint line sets, so their effects commute and any interleaving
of a tie is the same serial history.  ``tests/test_txn_device.py``
asserts this differentially on flat and 4-shard planes.

The sharded mirror (:func:`run_txn_rounds_sharded`) runs the SAME
scheduler inside one ``shard_map``: per-txn state stays put on its
shard, the dedup sees everyone through one ``all_gather`` of wanted
lines, each spin is the ``_route_round`` two-all_to_alls loop, and
liveness is a psum — global slot order is preserved by the block
distribution, so decisions match the flat plane exactly.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import coherence as co
from .driver import add_tele, run_rounds, zero_flat_tele
from .engine import _note_trace
from .sharded import (TELE_KEYS, _add_tele, _route_round, _state_specs,
                      _tele_rows, _tele_specs, _zero_tele, auto_mesh)
from .state import payload_width

LOCK_LANE = 0
WRITES_LANE = 1
HDR_LANES = 2


def txn_payload_width(tuples_per_line: int) -> int:
    """Payload lanes a txn GCL needs: lock + writes + (rts, wts) per
    tuple."""
    return HDR_LANES + 2 * tuples_per_line


# ------------------------------------------------------ algorithm bodies

def _apply_2pl(lanes, glines, rmask, wmask, ts):
    """2PL no-wait commit effect: all locks are already held (the loop
    IS the growing phase), so commit is unconditional; the workload
    effect is one counter bump per write-line."""
    valid = glines >= 0
    has_write = jnp.logical_and(wmask.astype(bool).any(axis=2), valid)
    new = lanes.at[:, :, WRITES_LANE].add(has_write.astype(jnp.int32))
    return jnp.ones(lanes.shape[0], bool), new


def _apply_to(lanes, glines, rmask, wmask, ts):
    """Timestamp ordering, replicating the host engine's sequential
    per-GCL, per-sorted-tuple semantics EXACTLY — including the
    partial-update leak: tuples checked before the failing one keep
    their header updates (the host mutates the live heap record and
    has already stored earlier GCLs when it aborts)."""
    B, G, W = lanes.shape
    T = (W - HDR_LANES) // 2
    stopped = jnp.zeros(B, bool)
    new = lanes
    for g in range(G):
        valid = glines[:, g] >= 0
        for t in range(T):
            r = rmask[:, g, t].astype(bool) & valid
            w = wmask[:, g, t].astype(bool) & valid
            active = (r | w) & ~stopped
            rts = new[:, g, HDR_LANES + 2 * t]
            wts = new[:, g, HDR_LANES + 2 * t + 1]
            # the write branch wins for read+write tuples (host: `t in
            # wset` is checked first)
            wfail = w & ((ts < rts) | (ts < wts))
            rfail = ~w & r & (ts < wts)
            ok_w = active & w & ~wfail
            ok_r = active & ~w & r & ~rfail
            new = new.at[:, g, HDR_LANES + 2 * t].set(
                jnp.where(ok_r, jnp.maximum(rts, ts), rts))
            new = new.at[:, g, HDR_LANES + 2 * t + 1].set(
                jnp.where(ok_w, ts, wts))
            stopped = stopped | (active & (wfail | rfail))
    return ~stopped, new


_APPLY = {"2pl": _apply_2pl, "to": _apply_to}


# ------------------------------------------------------- the flat driver

@functools.partial(jax.jit,
                   static_argnames=("algo", "n_nodes", "max_rounds",
                                    "max_iters", "backend"))
def run_txn_rounds(state, node_id, glines, rmask, wmask, ts, *,
                   algo: str, n_nodes: int, max_rounds: int = 64,
                   max_iters: int = 64, backend: str = "ref"):
    """Run a whole transaction batch to completion in ONE jit call.

    Returns ``(state', decision[B], exec_step[B], retries[B], iters,
    all_done, spins_ok, rounds, telemetry)`` — all device values.
    ``decision`` is commit (True) / abort (False); ``exec_step`` the
    iteration a txn completed at (its place in the serial order);
    ``retries`` its no-wait restarts; ``spins_ok`` False means an inner
    coherence spin hit ``max_rounds`` (results invalid — raise
    host-side); ``telemetry`` is the flat counter dict summed over
    every spin of the batch (``driver.zero_flat_tele`` keys)."""
    co.check_node_capacity(n_nodes)
    node_id = jnp.asarray(node_id, jnp.int32)
    glines = jnp.asarray(glines, jnp.int32)
    rmask = jnp.asarray(rmask, jnp.int32)
    wmask = jnp.asarray(wmask, jnp.int32)
    ts = jnp.asarray(ts, jnp.int32)
    B, G = glines.shape
    T = rmask.shape[2]
    W = payload_width(state)
    _note_trace(("txn", algo, B, G, T, n_nodes, max_rounds, max_iters,
                 backend, "dirty" in state, W))
    apply_fn = _APPLY[algo]
    nv = jnp.sum((glines >= 0).astype(jnp.int32), axis=1)
    slot = jnp.arange(B, dtype=jnp.int32)
    node_rep = jnp.repeat(node_id, G)
    g_idx = jnp.arange(G, dtype=jnp.int32)[None, :]

    def spin(stt, nodes, lines, is_write, wdata):
        stt, _, data, r, ok, tl = run_rounds(
            stt, nodes, lines, is_write, wdata, n_nodes=n_nodes,
            max_rounds=max_rounds, backend=backend)
        return stt, data, r, ok, tl

    def cond(carry):
        _, _, done, _, _, _, _, it, ok, _, _ = carry
        return ~jnp.all(done) & (it < max_iters) & ok

    def body(carry):
        (stt, k, done, dec, estep, retr, lanes, it, ok, rounds,
         tele) = carry
        live = ~done
        kc = jnp.minimum(k, G - 1)
        has_next = live & (k < nv)
        want = jnp.where(
            has_next,
            jnp.take_along_axis(glines, kc[:, None], axis=1)[:, 0], -1)
        # dedup wanted lines: lowest slot presents, the rest retry
        eq = (want[:, None] == want[None, :]) & (want[None, :] >= 0)
        loser = jnp.any(eq & (slot[None, :] < slot[:, None]), axis=1)
        winner = has_next & ~loser
        # READ spin: lock word == 0 at read time means acquired
        lines_r = jnp.where(winner, want, -1)
        stt, rdata, r1, ok1, t1 = spin(stt, node_id, lines_r,
                                       jnp.zeros_like(lines_r), None)
        got = winner & (rdata[:, LOCK_LANE] == 0)
        failed = has_next & ~got
        # carry the freshly-read lanes at position k (immutable while
        # the lock is held)
        onehot = (g_idx == kc[:, None]) & got[:, None]
        lanes = jnp.where(onehot[:, :, None], rdata[:, None, :], lanes)
        # ACQUIRE spin: publish the lock word
        wlock = rdata.at[:, LOCK_LANE].set(slot + 1)
        lines_a = jnp.where(got, want, -1)
        stt, _, r2, ok2, t2 = spin(stt, node_id, lines_a,
                                   jnp.ones_like(lines_a), wlock)
        k2 = k + got.astype(jnp.int32)
        complete = live & (k2 >= nv)
        decision_new, new_lanes = apply_fn(lanes, glines, rmask,
                                           wmask, ts)
        # FINALIZE spin: completers publish+release all lines, no-wait
        # losers release their held prefix (lanes carried unchanged)
        fin_c = complete[:, None] & (glines >= 0)
        fin_f = failed[:, None] & (g_idx < k[:, None])
        fdata = jnp.where(fin_c[:, :, None], new_lanes, lanes)
        fdata = fdata.at[:, :, LOCK_LANE].set(0)
        flines = jnp.where(fin_c | fin_f, glines, -1).reshape(B * G)
        stt, _, r3, ok3, t3 = spin(stt, node_rep, flines,
                                   jnp.ones_like(flines),
                                   fdata.reshape(B * G, W))
        return (stt, jnp.where(failed, 0, k2), done | complete,
                jnp.where(complete, decision_new, dec),
                jnp.where(complete, it, estep),
                retr + failed.astype(jnp.int32), lanes, it + 1,
                ok & ok1 & ok2 & ok3, rounds + r1 + r2 + r3,
                add_tele(tele, add_tele(t1, add_tele(t2, t3))))

    init = (state, jnp.zeros(B, jnp.int32), nv < 0,
            jnp.zeros(B, bool), jnp.zeros(B, jnp.int32),
            jnp.zeros(B, jnp.int32), jnp.zeros((B, G, W), jnp.int32),
            jnp.int32(0), jnp.bool_(True), jnp.int32(0),
            zero_flat_tele(state["words"].shape[0]))
    state, _, done, dec, estep, retr, _, it, ok, rounds, tele = \
        jax.lax.while_loop(cond, body, init)
    return (state, dec, estep, retr, it, jnp.all(done), ok, rounds,
            tele)


# ---------------------------------------------------- the sharded driver

@functools.partial(
    jax.jit, static_argnames=("algo", "mesh", "axis", "n_nodes",
                              "max_rounds", "max_iters", "bucket_cap",
                              "backend"))
def run_txn_rounds_sharded(state, node_id, glines, rmask, wmask, ts, *,
                           algo: str, mesh, axis: str = "shards",
                           n_nodes: int, max_rounds: int = 64,
                           max_iters: int = 64,
                           bucket_cap: int | None = None,
                           backend: str = "ref"):
    """Mesh mirror of :func:`run_txn_rounds`: the SAME scheduler inside
    one ``shard_map``.  Txn slots are block-distributed over the mesh
    (B divisible by the shard count; pad with ``glines = -1`` rows),
    dedup goes through an ``all_gather`` of wanted lines in GLOBAL slot
    order, every spin is the two-all_to_alls ``_route_round`` loop, and
    liveness is a psum — the flat return contract plus a trailing
    congestion-telemetry dict (same keys as
    :func:`run_rounds_sharded`, summed over all three spins of every
    scheduler iteration); decisions stay bit-identical."""
    co.check_node_capacity(n_nodes)
    n_shards = mesh.shape[axis]
    node_id = jnp.asarray(node_id, jnp.int32)
    glines = jnp.asarray(glines, jnp.int32)
    rmask = jnp.asarray(rmask, jnp.int32)
    wmask = jnp.asarray(wmask, jnp.int32)
    ts = jnp.asarray(ts, jnp.int32)
    B, G = glines.shape
    T = rmask.shape[2]
    W = payload_width(state)
    if B % n_shards:
        raise ValueError(f"B={B} not divisible by n_shards={n_shards}")
    bl = B // n_shards
    _note_trace(("txn_sharded", algo, n_shards, B, G, T, n_nodes,
                 max_rounds, max_iters, bucket_cap, backend,
                 "dirty" in state, W, "home" in state,
                 "replica" in state))
    apply_fn = _APPLY[algo]
    specs = _state_specs(state, axis)
    l_local = state["words"].shape[0] // n_shards
    g_idx = jnp.arange(G, dtype=jnp.int32)[None, :]

    def spmd(state_l, node_l, glines_l, rmask_l, wmask_l, ts_l):
        ai = jax.lax.axis_index(axis)
        gslot = ai * bl + jnp.arange(bl, dtype=jnp.int32)
        nv = jnp.sum((glines_l >= 0).astype(jnp.int32), axis=1)
        node_rep = jnp.repeat(node_l, G)

        def spin(stt_l, nodes, lines, is_write, wdata):
            # run_rounds composed from _route_round INSIDE this spmd
            # (shard_map can't nest) — the run_rounds_sharded loop body
            cap = (bucket_cap if bucket_cap is not None
                   else lines.shape[0])

            def n_pending(p):
                return jax.lax.psum(
                    jnp.sum((p >= 0).astype(jnp.int32)), axis)

            def s_cond(c):
                _, _, _, r, _, done = c
                return ~done & (r < max_rounds)

            def s_body(c):
                stt, pending, data, r, tele, _ = c
                stt, served, _, rdata, dtele = _route_round(
                    stt, nodes, pending, is_write, wdata,
                    n_shards=n_shards, axis=axis, n_nodes=n_nodes,
                    cap=cap, backend=backend)
                data = jnp.where(served[:, None], rdata, data)
                pending = jnp.where(served, jnp.int32(-1), pending)
                return (stt, pending, data, r + 1,
                        _add_tele(tele, dtele), n_pending(pending) == 0)

            init = (stt_l, lines,
                    jnp.zeros((lines.shape[0], W), jnp.int32),
                    jnp.int32(0), _zero_tele(n_shards, l_local),
                    n_pending(lines) == 0)
            stt_l, pending, data, r, tele, done = jax.lax.while_loop(
                s_cond, s_body, init)
            return stt_l, data, r, done, tele

        def n_live(done):
            return jax.lax.psum(
                jnp.sum((~done).astype(jnp.int32)), axis)

        def cond(carry):
            _, _, _, _, _, _, _, it, ok, _, _, alldone = carry
            return ~alldone & (it < max_iters) & ok

        def body(carry):
            (stt, k, done, dec, estep, retr, lanes, it, ok, rounds,
             tele, _) = carry
            live = ~done
            kc = jnp.minimum(k, G - 1)
            has_next = live & (k < nv)
            want = jnp.where(
                has_next,
                jnp.take_along_axis(glines_l, kc[:, None],
                                    axis=1)[:, 0], -1)
            # global dedup in GLOBAL slot order (block distribution
            # keeps gathered order == slot order)
            want_g = jax.lax.all_gather(want, axis).reshape(B)
            eq = (want_g[:, None] == want_g[None, :]) \
                & (want_g[None, :] >= 0)
            sg = jnp.arange(B, dtype=jnp.int32)
            loser_g = jnp.any(eq & (sg[None, :] < sg[:, None]), axis=1)
            loser = jax.lax.dynamic_slice_in_dim(loser_g, ai * bl, bl)
            winner = has_next & ~loser
            lines_r = jnp.where(winner, want, -1)
            stt, rdata, r1, ok1, t1 = spin(
                stt, node_l, lines_r, jnp.zeros_like(lines_r),
                jnp.zeros((bl, W), jnp.int32))
            got = winner & (rdata[:, LOCK_LANE] == 0)
            failed = has_next & ~got
            onehot = (g_idx == kc[:, None]) & got[:, None]
            lanes = jnp.where(onehot[:, :, None], rdata[:, None, :],
                              lanes)
            wlock = rdata.at[:, LOCK_LANE].set(gslot + 1)
            lines_a = jnp.where(got, want, -1)
            stt, _, r2, ok2, t2 = spin(stt, node_l, lines_a,
                                       jnp.ones_like(lines_a), wlock)
            k2 = k + got.astype(jnp.int32)
            complete = live & (k2 >= nv)
            decision_new, new_lanes = apply_fn(lanes, glines_l,
                                               rmask_l, wmask_l, ts_l)
            fin_c = complete[:, None] & (glines_l >= 0)
            fin_f = failed[:, None] & (g_idx < k[:, None])
            fdata = jnp.where(fin_c[:, :, None], new_lanes, lanes)
            fdata = fdata.at[:, :, LOCK_LANE].set(0)
            flines = jnp.where(fin_c | fin_f, glines_l,
                               -1).reshape(bl * G)
            stt, _, r3, ok3, t3 = spin(stt, node_rep, flines,
                                       jnp.ones_like(flines),
                                       fdata.reshape(bl * G, W))
            done2 = done | complete
            return (stt, jnp.where(failed, 0, k2), done2,
                    jnp.where(complete, decision_new, dec),
                    jnp.where(complete, it, estep),
                    retr + failed.astype(jnp.int32), lanes, it + 1,
                    ok & ok1 & ok2 & ok3, rounds + r1 + r2 + r3,
                    _add_tele(_add_tele(tele, _add_tele(t1, t2)), t3),
                    n_live(done2) == 0)

        init = (state_l, jnp.zeros(bl, jnp.int32), nv < 0,
                jnp.zeros(bl, bool), jnp.zeros(bl, jnp.int32),
                jnp.zeros(bl, jnp.int32),
                jnp.zeros((bl, G, W), jnp.int32), jnp.int32(0),
                jnp.bool_(True), jnp.int32(0),
                _zero_tele(n_shards, l_local), n_live(nv < 0) == 0)
        (state_l, _, done, dec, estep, retr, _, it, ok, rounds,
         tele, alldone) = jax.lax.while_loop(cond, body, init)
        return (state_l, dec, estep, retr, it, alldone, ok,
                rounds) + _tele_rows(tele)

    out = jax.shard_map(
        spmd, mesh=auto_mesh(mesh),
        in_specs=(specs, P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(specs, P(axis), P(axis), P(axis), P(), P(), P(),
                   P()) + _tele_specs(axis),
        check_vma=False,
    )(state, node_id, glines, rmask, wmask, ts)
    return out[:8] + (dict(zip(TELE_KEYS, out[8:])),)


# ------------------------------------------------------ host-facing API

@dataclass(frozen=True)
class TxnBatchResult:
    """Host-side result of one fused txn batch.

    ``decision`` bool [B] (commit/abort), ``exec_step`` int [B] (the
    scheduler iteration each txn completed at — its position in the
    serial order), ``retries`` int [B] (no-wait restarts), ``iters``
    total scheduler iterations, ``rounds`` total coherence rounds
    across all spins.  ``telemetry`` is the unified
    :class:`~repro.obs.PlaneTelemetry` record summed over every spin
    of the batch — populated on flat AND sharded planes (the host
    reference :func:`run_txn_batch_host` leaves it None; its per-phase
    ``plane.ops`` dispatches each carry their own)."""

    decision: np.ndarray
    exec_step: np.ndarray
    retries: np.ndarray
    iters: int
    rounds: int
    telemetry: "PlaneTelemetry | None" = None


def run_txn_batch(plane, node_id, glines, rmask, wmask, ts, *,
                  algo: str, max_iters: int | None = None,
                  max_rounds: int | None = None) -> TxnBatchResult:
    """Drive one txn batch through ``plane`` (DevicePlane, flat or
    sharded) and normalize the result; the canonical-order contract
    (each row of ``glines`` sorted ascending, ``-1`` pads at the end)
    is validated here, where it's cheap."""
    if algo not in _APPLY:
        raise ValueError(f"unknown txn algo {algo!r} "
                         f"(have {sorted(_APPLY)})")
    glines = np.asarray(glines, np.int32)
    node_id = np.asarray(node_id, np.int32)
    rmask = np.asarray(rmask, np.int32)
    wmask = np.asarray(wmask, np.int32)
    ts = np.asarray(ts, np.int32)
    B, G = glines.shape
    T = rmask.shape[2]
    need = txn_payload_width(T)
    if plane.payload_width != need:
        raise ValueError(
            f"plane payload_width={plane.payload_width} but "
            f"T={T} tuple headers need {need} lanes")
    valid = glines >= 0
    if (valid[:, 1:] & ~valid[:, :-1]).any():
        raise ValueError("glines pads (-1) must trail the valid lines")
    both = valid[:, 1:] & valid[:, :-1]
    if (both & (glines[:, 1:] <= glines[:, :-1])).any():
        raise ValueError("glines must be sorted strictly ascending "
                         "per txn (canonical latch order)")
    mr = plane.max_rounds if max_rounds is None else max_rounds
    mi = 4 * B + 16 if max_iters is None else max_iters
    if plane.sharded:
        pad = (-B) % plane.n_shards
        if pad:
            node_id = np.concatenate([node_id,
                                      np.zeros(pad, np.int32)])
            glines = np.concatenate(
                [glines, np.full((pad, G), -1, np.int32)])
            rmask = np.concatenate(
                [rmask, np.zeros((pad, G, T), np.int32)])
            wmask = np.concatenate(
                [wmask, np.zeros((pad, G, T), np.int32)])
            ts = np.concatenate([ts, np.zeros(pad, np.int32)])
        state, dec, estep, retr, it, alldone, ok, rounds, tele = \
            run_txn_rounds_sharded(
                plane.state, node_id, glines, rmask, wmask, ts,
                algo=algo, mesh=plane.mesh, axis=plane.axis,
                n_nodes=plane.n_nodes, max_rounds=mr, max_iters=mi,
                bucket_cap=plane.bucket_cap, backend=plane.backend)
    else:
        state, dec, estep, retr, it, alldone, ok, rounds, tele = \
            run_txn_rounds(
                plane.state, node_id, glines, rmask, wmask, ts,
                algo=algo, n_nodes=plane.n_nodes, max_rounds=mr,
                max_iters=mi, backend=plane.backend)
    telemetry = plane._telemetry(tele)
    if not bool(ok):
        raise RuntimeError(
            f"txn coherence spin hit max_rounds={mr}")
    if not bool(alldone):
        raise RuntimeError(
            f"txn batch not done after {mi} scheduler iterations "
            f"(livelock? raise max_iters)")
    plane.state = state
    return TxnBatchResult(np.asarray(dec)[:B], np.asarray(estep)[:B],
                          np.asarray(retr)[:B], int(it), int(rounds),
                          telemetry)


def _apply_host_one(algo, lanes, glines, rmask, wmask, ts):
    """Python mirror of ``_APPLY[algo]`` for ONE txn's carried lanes —
    the host-driven reference scheduler applies per completing txn."""
    G, W = lanes.shape
    T = (W - HDR_LANES) // 2
    new = lanes.copy()
    if algo == "2pl":
        for g in range(G):
            if glines[g] >= 0 and wmask[g].any():
                new[g, WRITES_LANE] += 1
        return True, new
    for g in range(G):
        if glines[g] < 0:
            continue
        for t in range(T):
            r, w = bool(rmask[g, t]), bool(wmask[g, t])
            if not (r or w):
                continue
            rts = new[g, HDR_LANES + 2 * t]
            wts = new[g, HDR_LANES + 2 * t + 1]
            if w:
                if ts < rts or ts < wts:
                    return False, new
                new[g, HDR_LANES + 2 * t + 1] = ts
            else:
                if ts < wts:
                    return False, new
                new[g, HDR_LANES + 2 * t] = max(rts, ts)
    return True, new


def run_txn_batch_host(plane, node_id, glines, rmask, wmask, ts, *,
                       algo: str,
                       max_iters: int | None = None) -> TxnBatchResult:
    """The PRE-FUSE reference: the same txn scheduler, driven from the
    host — one ``plane.ops`` dispatch (with a host sync) per phase per
    iteration, dedup/apply/bookkeeping in numpy between dispatches.
    Bit-identical decisions, exec order, retries and memory image to
    :func:`run_txn_batch`; exists as the fused loop's differential
    oracle on the device plane and as the ``txn_fused_speedup``
    baseline in benchmarks/fig11_tpcc_rounds.py (the fig10 ``host``
    driver, for transactions)."""
    if algo not in _APPLY:
        raise ValueError(f"unknown txn algo {algo!r}")
    glines = np.asarray(glines, np.int32)
    rmask = np.asarray(rmask, np.int32)
    wmask = np.asarray(wmask, np.int32)
    ts = np.asarray(ts, np.int32)
    B, G = glines.shape
    W = plane.payload_width
    node_id = np.broadcast_to(np.asarray(node_id, np.int32),
                              (B,)).astype(np.int32)
    nv = (glines >= 0).sum(axis=1)
    mi = 4 * B + 16 if max_iters is None else max_iters
    g_idx = np.arange(G)
    k = np.zeros(B, np.int64)
    done = nv == 0
    dec = np.zeros(B, bool)
    estep = np.zeros(B, np.int64)
    retr = np.zeros(B, np.int64)
    lanes = np.zeros((B, G, W), np.int32)
    rounds = it = 0
    while not done.all():
        if it >= mi:
            raise RuntimeError(
                f"txn batch not done after {mi} scheduler iterations "
                f"(livelock? raise max_iters)")
        live = ~done
        kc = np.minimum(k, G - 1)
        has_next = live & (k < nv)
        want = np.where(has_next, glines[np.arange(B), kc], -1)
        winner = np.zeros(B, bool)
        seen: set = set()
        for i in range(B):              # lowest slot wins, like device
            if want[i] >= 0 and want[i] not in seen:
                seen.add(int(want[i]))
                winner[i] = True
        res = plane.ops(node_id,
                        np.where(winner, want, -1).astype(np.int32),
                        np.zeros(B, np.int32))
        rounds += res.rounds
        rdata = np.asarray(res.data)
        got = winner & (rdata[:, LOCK_LANE] == 0)
        failed = has_next & ~got
        lanes[got, kc[got]] = rdata[got]
        wlock = rdata.copy()
        wlock[:, LOCK_LANE] = np.arange(B) + 1
        res = plane.ops(node_id,
                        np.where(got, want, -1).astype(np.int32),
                        np.ones(B, np.int32), wlock)
        rounds += res.rounds
        k2 = k + got
        complete = live & (k2 >= nv)
        fdata = lanes.copy()
        for i in np.flatnonzero(complete):
            dec[i], fdata[i] = _apply_host_one(
                algo, lanes[i], glines[i], rmask[i], wmask[i],
                int(ts[i]))
        fdata[:, :, LOCK_LANE] = 0
        fin = (complete[:, None] & (glines >= 0)) \
            | (failed[:, None] & (g_idx[None, :] < k[:, None]))
        res = plane.ops(np.repeat(node_id, G),
                        np.where(fin, glines, -1).reshape(B * G)
                        .astype(np.int32),
                        np.ones(B * G, np.int32),
                        fdata.reshape(B * G, W))
        rounds += res.rounds
        estep[complete] = it
        done = done | complete
        retr += failed
        k = np.where(failed, 0, k2)
        it += 1
    return TxnBatchResult(dec, estep.astype(np.int64),
                          retr.astype(np.int64), it, int(rounds))
