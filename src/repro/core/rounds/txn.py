"""Transaction concurrency control as ONE fused device loop.

The paper's Sec. 8.2 argument is that classic CC algorithms fall out of
the SELCC abstraction almost for free: latches are cache-line states,
row locks are payload lanes, and 2PL / TO need no server-side txn logic
at all.  ``apps/txn.py`` shows that on the host DES; this module shows
it on the device rounds plane — the whole batch of transactions
(acquire, execute, commit, no-wait restart) runs inside a single jitted
``lax.while_loop`` with two coherence spins per scheduler iteration and
zero host syncs.

What a transaction does is a :class:`TxnProgram`: a static object with
``entries`` line slots per transaction, each naming a line and a lock
mode (``NONE`` — read without a lock, for rows nobody writes;
``SHARED``; ``EXCL``), and two pure ``jnp`` functions:

* ``plan(phase, typ, inp, lines, mode, lanes)`` — the line set of the
  NEXT phase, computed from the lanes held so far (a TPC-C NewOrder
  inserts at the district's ``d_next_o_id``, a StockLevel reads the
  district, then its last 20 orders, then their items' stock);
* ``effect(typ, inp, lines, mode, lanes)`` — after the last phase: the
  commit decision, the new lanes of every ``EXCL`` entry and the
  transaction's outputs.

Lane 0 of every transactional line is the lock word: ``0`` free, ``n >
0`` held shared by ``n`` readers, ``-(slot + 1)`` held exclusive by
batch slot ``slot``.  A batch is ``typ [B]``, ``inp [B, I]`` and the
phase-0 entries ``lines [B, M]`` (``-1`` = unused) and ``mode [B, M]``.

Per scheduler iteration every live transaction presents ALL lines of
its current phase at once (no-wait cannot deadlock: nobody waits), plus
the lines it holds shared (their reader count may have to change):

1. READ spin — every presented entry reads its line (reads never
   conflict, so no dedup is needed here); the read lanes are carried in
   the loop (a held line cannot change under its holder);
2. GRANT — per line, the lowest presenting entry (slot-major order)
   decides: an exclusive request is granted iff it is that entry and
   the word read 0; shared requests are granted together iff the lowest
   request is shared and the word holds no exclusive holder.  A
   transaction that is not granted EVERY lock of its phase is a no-wait
   loser: it releases everything and restarts from phase 0 next
   iteration; a winner computes its next phase (``plan``) or, when that
   phase is empty or it ran its last, completes (``effect``);
3. WRITE spin — one write per changed line (its lowest writing entry's
   node): new lock words (readers added and released, exclusive grants),
   completers' published lanes with the word cleared, losers' exclusive
   lines released with their carried lanes.

Serial order is ``(exec_step, slot)``: a transaction that completes at
iteration ``i`` held every lock it needed through ``i``'s write spin,
and a conflicting one got its lock only at a later read, so it
completes later; transactions that complete together hold no
conflicting modes (an exclusive grant excludes every other holder of
the line), so their effects commute.  ``tests/test_txn_device.py`` and
``tests/test_tpcc_device.py`` check the generic cases and TPC-C
against plain references replayed in that order, on flat and 4-shard
planes.

The scheduler loop (``_run``) is written once.  The flat entry point
binds it to one engine round per spin step; the sharded one
(:func:`run_txn_rounds_sharded`) runs it inside one ``shard_map``:
per-transaction state stays on its shard, every grant and write
decision is computed from an ``all_gather`` of the batch's entry
metadata (global slot order is preserved by the block distribution, so
the decisions are the flat plane's), each spin routes its rounds
(``sharded._route_round``), and liveness is a psum.

The generic tuple-header cases (``algo="2pl"`` / ``"to"``, lane 1 a
committed-writes counter, lanes ``2 + 2t, 3 + 2t`` tuple ``t``'s
``(read_ts, write_ts)``) are two programs of one phase over the same
loop; TPC-C is ``apps/tpcc_device.py``'s program.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import coherence as co
from .driver import (TELE_KEYS, _add_tele, _same, _tele_rows, _zero_tele,
                     flat_step, spin, tele_dict)
from .engine import _note_trace
from .sharded import _router, _state_specs, _tele_specs, auto_mesh
from .state import payload_width

LOCK_LANE = 0
WRITES_LANE = 1
HDR_LANES = 2

NONE, SHARED, EXCL = 0, 1, 2
SPIN_SLOTS = 512             # slots a spin presents at once (per shard)

# program counters of one batch, summed over its iterations
COUNTER_KEYS = ("iters", "restarts", "phases", "s_grants", "x_grants",
                "rollbacks")


def txn_payload_width(tuples_per_line: int) -> int:
    """Payload lanes a tuple-header GCL needs: lock + writes + (rts, wts)
    per tuple."""
    return HDR_LANES + 2 * tuples_per_line


class TxnProgram:
    """What the transactions of one kind do (see the module docstring).

    Subclasses set ``name``, ``entries`` (M), ``inputs`` (I),
    ``outputs`` (O), ``depths`` (phases per transaction type) and
    ``width`` (the payload lanes a line must have), and define
    :meth:`plan`, :meth:`effect` and :meth:`encode`.  Instances are
    static arguments of the jitted loops: keep one per configuration."""

    name = "program"
    entries = 1
    inputs = 1
    outputs = 1
    depths = (1,)
    width = 0

    def plan(self, phase, typ, inp, lines, mode, lanes):
        """Lines and modes with the entries of phase ``phase + 1`` filled
        in (entries a transaction has not used yet), per transaction."""
        return lines, mode

    def effect(self, typ, inp, lines, mode, lanes):
        """``(commit [B], lanes [B, M, W], out [B, O])`` after the last
        phase: ``lanes`` holds the new lanes of every EXCL entry."""
        raise NotImplementedError

    def encode(self, *batch):
        """Host arrays ``(typ [B], inp [B, I], lines [B, M], mode [B,
        M])`` of one batch (numpy, validated)."""
        raise NotImplementedError


# ------------------------------------------------ the tuple-header cases

def _apply_2pl(lanes, glines, rmask, wmask, ts):
    """2PL no-wait commit effect: every lock is held, so commit is
    unconditional; the workload effect is one counter bump per
    write-line."""
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


class TupleProgram(TxnProgram):
    """One-phase transactions over tuple-header GCLs: ``glines [B, G]``
    (each row strictly ascending, ``-1`` pads trailing), ``rmask/wmask
    [B, G, T]`` tuple touch masks, ``ts [B]`` TO timestamps.  2PL locks
    a line exclusive when it writes a tuple there and shared when it
    only reads; TO latches every line exclusive (its checks write the
    read timestamps)."""

    depths = (1,)
    outputs = 1

    def __init__(self, algo: str, lines: int, tuples: int):
        self.name, self.algo = algo, algo
        self.entries, self.tuples = lines, tuples
        self.inputs = 2 * lines * tuples + 1
        self.width = txn_payload_width(tuples)
        self._apply = {"2pl": _apply_2pl, "to": _apply_to}[algo]

    def _split(self, inp):
        B, G, T = inp.shape[0], self.entries, self.tuples
        n = G * T
        return (inp[:, :n].reshape(B, G, T), inp[:, n:2 * n]
                .reshape(B, G, T), inp[:, 2 * n])

    def effect(self, typ, inp, lines, mode, lanes):
        rmask, wmask, ts = self._split(inp)
        commit, new = self._apply(lanes, lines, rmask, wmask, ts)
        return commit, new, jnp.zeros((lanes.shape[0], 1), jnp.int32)

    def encode(self, glines, rmask, wmask, ts):
        glines = np.asarray(glines, np.int32)
        rmask = np.asarray(rmask, np.int32)
        wmask = np.asarray(wmask, np.int32)
        ts = np.asarray(ts, np.int32)
        valid = glines >= 0
        if (valid[:, 1:] & ~valid[:, :-1]).any():
            raise ValueError("glines pads (-1) must trail the valid lines")
        both = valid[:, 1:] & valid[:, :-1]
        if (both & (glines[:, 1:] <= glines[:, :-1])).any():
            raise ValueError("glines must be sorted strictly ascending "
                             "per txn (distinct lines)")
        B = glines.shape[0]
        excl = wmask.astype(bool).any(axis=2) | (self.algo == "to")
        mode = np.where(valid, np.where(excl, EXCL, SHARED), NONE)
        inp = np.concatenate([rmask.reshape(B, -1), wmask.reshape(B, -1),
                              ts[:, None]], axis=1)
        return (np.zeros(B, np.int32), inp.astype(np.int32), glines,
                mode.astype(np.int32))


@functools.lru_cache(maxsize=None)
def tuple_program(algo: str, lines: int, tuples: int) -> TupleProgram:
    """The (cached, so jit-stable) tuple program of ``algo``."""
    if algo not in ("2pl", "to"):
        raise ValueError(f"unknown txn algo {algo!r} (have ['2pl', 'to'])")
    return TupleProgram(algo, lines, tuples)


# --------------------------------------------------- one iteration's core

def _grant(lines, mode, want, word, n_lines):
    """Grant decisions over the WHOLE batch's presented entries ([B, M],
    slot-major): ``(granted [B, M], ok [B])`` — ``ok`` a transaction got
    every entry of its phase (NONE entries always do)."""
    B, M = lines.shape
    idx = jnp.arange(B * M, dtype=jnp.int32).reshape(B, M)
    lock = want & (mode != NONE)
    at = jnp.maximum(lines, 0)
    first = jnp.full((n_lines,), B * M, jnp.int32).at[
        jnp.where(lock, lines, n_lines)].min(idx, mode="drop")[at]
    fmode = mode.reshape(-1)[jnp.minimum(first, B * M - 1)]
    g = jnp.where(mode == EXCL, (first == idx) & (word == 0),
                  (fmode == SHARED) & (word >= 0))
    g = want & jnp.where(lock, g, True)
    ok = ~jnp.any(want & ~g, axis=1)
    return g & ok[:, None], ok


def _writes(lines, mode, held, granted, complete, failed, word, n_lines):
    """Write decisions over the whole batch: ``(rep [B, M], new_word [B,
    M], counts)`` — ``rep`` marks the one entry that writes each changed
    line, ``new_word`` its lock word."""
    B, M = lines.shape
    idx = jnp.arange(B * M, dtype=jnp.int32).reshape(B, M)
    slot = jnp.arange(B, dtype=jnp.int32)[:, None]
    lockg = granted & (mode != NONE)
    rel = (complete[:, None] & (held | lockg)) | (failed[:, None] & held)
    rel_s = rel & (mode == SHARED)
    rel_x = rel & (mode == EXCL)
    g_s = lockg & (mode == SHARED)
    g_x = lockg & (mode == EXCL) & ~complete[:, None]
    writer = g_s | rel_s | g_x | rel_x
    drop = jnp.where(writer, lines, n_lines)
    at = jnp.maximum(lines, 0)
    d_s = jnp.zeros((n_lines,), jnp.int32).at[drop].add(
        g_s.astype(jnp.int32) - rel_s.astype(jnp.int32), mode="drop")[at]
    x_new = jnp.zeros((n_lines,), jnp.int32).at[
        jnp.where(g_x, lines, n_lines)].max(
        jnp.broadcast_to(slot + 1, (B, M)), mode="drop")[at]
    x_rel = jnp.zeros((n_lines,), bool).at[
        jnp.where(rel_x, lines, n_lines)].set(True, mode="drop")[at]
    first = jnp.full((n_lines,), B * M, jnp.int32).at[drop].min(
        idx, mode="drop")[at]
    change = (d_s != 0) | (x_new > 0) | x_rel
    rep = writer & (first == idx) & change
    new_word = jnp.where(x_new > 0, -x_new,
                         jnp.where(x_rel, 0, word + d_s))
    counts = {"s_grants": jnp.sum(g_s.astype(jnp.int32)),
              "x_grants": jnp.sum((lockg & (mode == EXCL))
                                  .astype(jnp.int32))}
    return rep, new_word, counts


def _chunked(spin, chunk: int, tele0, total):
    """``spin`` over only the live slots (``line >= 0``) of a spin's
    slots, compacted to the front and presented ``chunk`` at a time, as
    many chunks as the most-loaded shard needs (``total`` lifts a local
    count to that maximum; ``tele0`` the spin's telemetry zero).
    Reads commute and a write spin writes each line once, so chunks may
    run one after another; a spin the ``ref`` latch kernel serves costs
    a sequential step per presented slot, so this keeps a round's cost
    to the lines in flight, not ``B * M``."""
    def run(stt, nodes, lines, is_write, wdata):
        n = lines.shape[0]
        n_pad = -(-n // chunk) * chunk
        valid = lines >= 0
        order = jnp.argsort(~valid, stable=True)
        pad = n_pad - n

        def take(x, fill):
            x = x[order]
            return jnp.concatenate(
                [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
        lc, nc, wc = take(lines, -1), take(nodes, 0), take(is_write, 0)
        dc = None if wdata is None else take(wdata, 0)
        k_n = total(-(-jnp.sum(valid.astype(jnp.int32)) // chunk))
        w = payload_width(stt)

        def body(c):
            stt, k, data, r, ok, tele = c
            at = k * chunk

            def sl(x):
                return jax.lax.dynamic_slice_in_dim(x, at, chunk)
            stt, d, r1, ok1, t1 = spin(
                stt, sl(nc), sl(lc), sl(wc), None if dc is None else sl(dc))
            data = jax.lax.dynamic_update_slice_in_dim(data, d, at, 0)
            return stt, k + 1, data, r + r1, ok & ok1, _add_tele(tele, t1)

        init = (stt, jnp.int32(0), jnp.zeros((n_pad, w), jnp.int32),
                jnp.int32(0), jnp.bool_(True), tele0)
        stt, _, data, r, ok, tele = jax.lax.while_loop(
            lambda c: c[1] < k_n, body, init)
        out = jnp.zeros((n, w), jnp.int32).at[order].set(data[:n])
        return stt, out, r, ok, tele
    return run


def _zero_counters():
    return {k: jnp.int32(0) for k in COUNTER_KEYS}


def _iteration(program, n_lines, carry, batch, *, spin, gather, mine):
    """One scheduler iteration on this shard's transactions (all of them
    on a flat plane).  ``gather`` lifts a per-transaction array to the
    whole batch (identity flat, ``all_gather`` sharded), ``mine`` slices
    a whole-batch array back to this shard's block."""
    (stt, phase, done, dec, estep, retr, lines, mode, eph, held, lanes,
     out, it, ok_spins, rounds, tele, ctr) = carry
    node, typ, inp, lines0, mode0 = batch
    b, m = lines.shape
    w = lanes.shape[2]
    node_rep = jnp.repeat(node, m)
    live = ~done
    want = live[:, None] & (lines >= 0) & (eph == phase[:, None])
    present = want | (live[:, None] & held & (mode == SHARED))

    # 1. READ spin: every presented entry reads its line
    rl = jnp.where(present, lines, -1).reshape(b * m)
    stt, rdata, r1, ok1, t1 = spin(stt, node_rep, rl, jnp.zeros_like(rl),
                                   None)
    rdata = rdata.reshape(b, m, w)
    lanes = jnp.where(present[:, :, None], rdata, lanes)
    word = lanes[:, :, LOCK_LANE]

    # 2. GRANT: decided over the whole batch, in global slot order
    g_lines, g_mode, g_want, g_word = (gather(x) for x in
                                       (lines, mode, want, word))
    g_granted, g_ok = _grant(g_lines, g_mode, g_want, g_word, n_lines)
    granted, ok = mine(g_granted), mine(g_ok)
    ok = ok & live
    failed = live & ~ok
    lockg = granted & (mode != NONE)
    held2 = held | lockg
    nxt_lines, nxt_mode = program.plan(phase, typ, inp, lines, mode,
                                       lanes)
    new = (nxt_lines >= 0) & (lines < 0)
    depth = jnp.asarray(program.depths, jnp.int32)[jnp.maximum(typ, 0)]
    more = jnp.any(new, axis=1) & (phase + 1 < depth)
    complete = ok & ~more
    advance = ok & more
    commit, fx_lanes, fx_out = program.effect(typ, inp, lines, mode,
                                              lanes)

    # 3. WRITE spin: new lock words, publications, releases
    g_complete, g_failed, g_held = (gather(x) for x in
                                    (complete, failed, held))
    g_rep, g_new_word, gctr = _writes(g_lines, g_mode, g_held, g_granted,
                                      g_complete, g_failed, g_word,
                                      n_lines)
    rep, new_word = mine(g_rep), mine(g_new_word)
    publish = complete[:, None] & (mode == EXCL)
    wdata = jnp.where(publish[:, :, None], fx_lanes, lanes)
    wdata = wdata.at[:, :, LOCK_LANE].set(new_word)
    wl = jnp.where(rep, lines, -1).reshape(b * m)
    stt, _, r2, ok2, t2 = spin(stt, node_rep, wl, jnp.ones_like(wl),
                               wdata.reshape(b * m, w))

    # 4. bookkeeping: losers restart from phase 0 with released locks
    adv2 = advance[:, None]
    lines_n = jnp.where(adv2, nxt_lines, lines)
    eph_n = jnp.where(adv2 & new, (phase + 1)[:, None], eph)
    fail2 = failed[:, None]
    ctr = {"iters": ctr["iters"] + 1,
           "restarts": ctr["restarts"]
           + jnp.sum(gather(failed).astype(jnp.int32)),
           "phases": ctr["phases"] + jnp.sum(gather(ok).astype(jnp.int32)),
           "s_grants": ctr["s_grants"] + gctr["s_grants"],
           "x_grants": ctr["x_grants"] + gctr["x_grants"],
           "rollbacks": ctr["rollbacks"] + jnp.sum(
               gather(complete & ~commit).astype(jnp.int32))}
    return (stt,
            jnp.where(failed, 0, jnp.where(advance, phase + 1, phase)),
            done | complete,
            jnp.where(complete, commit, dec),
            jnp.where(complete, it, estep),
            retr + failed.astype(jnp.int32),
            jnp.where(fail2, lines0, lines_n),
            jnp.where(fail2, mode0, jnp.where(adv2, nxt_mode, mode)),
            jnp.where(fail2, 0, eph_n),
            jnp.where(fail2 | complete[:, None], False, held2),
            lanes,
            jnp.where(complete[:, None], fx_out, out),
            it + 1, ok_spins & ok1 & ok2, rounds + r1 + r2,
            _add_tele(_add_tele(tele, t1), t2), ctr)


def _init_carry(program, state_l, batch, tele0, w):
    node, typ, inp, lines0, mode0 = batch
    b, m = lines0.shape
    return (state_l, jnp.zeros(b, jnp.int32), typ < 0, jnp.zeros(b, bool),
            jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32), lines0,
            mode0, jnp.zeros((b, m), jnp.int32), jnp.zeros((b, m), bool),
            jnp.zeros((b, m, w), jnp.int32),
            jnp.zeros((b, program.outputs), jnp.int32), jnp.int32(0),
            jnp.bool_(True), jnp.int32(0), tele0, _zero_counters())


def _as_batch(node_id, typ, inp, lines, mode):
    return (jnp.asarray(node_id, jnp.int32), jnp.asarray(typ, jnp.int32),
            jnp.asarray(inp, jnp.int32), jnp.asarray(lines, jnp.int32),
            jnp.asarray(mode, jnp.int32))


def _run(program, state, batch, *, n_lines, step, count, gather, mine,
         total, chunk, tele0, max_rounds, max_iters):
    """The scheduler loop over this shard's transactions (all of them on
    one chip): ``step``/``count`` are ``driver.spin``'s round step and
    count lift, ``gather``/``mine`` lift a per-transaction array to the
    whole batch and back, ``total`` lifts a chunk count to its maximum
    over shards.  Returns the entry points' first ten results, the
    telemetry rows and the counters."""
    w = payload_width(state)

    def raw_spin(stt, nodes, lns, is_write, wdata):
        if wdata is None:
            wdata = jnp.zeros((lns.shape[0], w), jnp.int32)
        stt, _, data, r, ok, tele = spin(step, count, stt, nodes, lns,
                                         is_write, wdata,
                                         max_rounds=max_rounds, tele0=tele0)
        return stt, data, r, ok, tele
    chunked = _chunked(raw_spin, chunk, tele0, total)

    def left(done):
        return count(jnp.sum((~done).astype(jnp.int32)))

    def cond(c):
        carry, n = c
        return (n > 0) & (carry[12] < max_iters) & carry[13]

    def body(c):
        carry = _iteration(program, n_lines, c[0], batch, spin=chunked,
                           gather=gather, mine=mine)
        return carry, left(carry[2])

    init = _init_carry(program, state, batch, tele0, w)
    carry, n = jax.lax.while_loop(cond, body, (init, left(init[2])))
    (state, _, _, dec, estep, retr, lns, mds, _, _, _, outs, _, ok,
     rounds, tele, ctr) = carry
    return ((state, dec, estep, retr, outs, lns, mds, n == 0, ok, rounds),
            _tele_rows(tele), ctr)


@functools.partial(jax.jit,
                   static_argnames=("program", "n_nodes", "max_rounds",
                                    "max_iters", "backend"))
def run_txn_rounds(state, node_id, typ, inp, lines, mode, *, program,
                   n_nodes: int, max_rounds: int = 64, max_iters: int = 64,
                   backend: str = "ref"):
    """Run a whole transaction batch to completion in ONE jit call.

    Returns ``(state', decision[B], exec_step[B], retries[B], out[B, O],
    lines[B, M], mode[B, M], all_done, spins_ok, rounds, telemetry,
    counters)`` — all device values.  ``decision`` is commit (True) /
    abort or rollback (False); ``exec_step`` the iteration a txn
    completed at (its place in the serial order); ``retries`` its
    no-wait restarts; ``lines``/``mode`` the entries each txn completed
    with (every phase's); ``spins_ok`` False means an inner coherence
    spin hit ``max_rounds`` (results invalid);
    ``telemetry`` the counter dict (``driver.TELE_KEYS``) summed over
    every spin; ``counters`` the :data:`COUNTER_KEYS` program
    counters."""
    co.check_node_capacity(n_nodes)
    batch = _as_batch(node_id, typ, inp, lines, mode)
    B, M = batch[3].shape
    n_lines = state["words"].shape[0]
    _note_trace(("txn", program.name, B, M, program.inputs, n_nodes,
                 max_rounds, max_iters, backend, "dirty" in state,
                 payload_width(state)))
    out, tele, ctr = _run(
        program, state, batch, n_lines=n_lines,
        step=flat_step(n_nodes, backend), count=_same, gather=_same,
        mine=_same, total=_same, chunk=min(B * M, SPIN_SLOTS),
        tele0=_zero_tele(1, n_lines), max_rounds=max_rounds,
        max_iters=max_iters)
    return out + (tele_dict(tele), ctr)


@functools.partial(
    jax.jit, static_argnames=("program", "mesh", "axis", "n_nodes",
                              "max_rounds", "max_iters", "bucket_cap",
                              "backend"))
def run_txn_rounds_sharded(state, node_id, typ, inp, lines, mode, *,
                           program, mesh, axis: str = "shards",
                           n_nodes: int, max_rounds: int = 64,
                           max_iters: int = 64,
                           bucket_cap: int | None = None,
                           backend: str = "ref"):
    """Mesh form of :func:`run_txn_rounds`: the SAME loop inside one
    ``shard_map``.  Txn slots are block-distributed over the mesh
    (B divisible by the shard count; pad with ``typ = -1`` rows), every
    grant and write decision is computed from an ``all_gather`` of the
    batch's entries in GLOBAL slot order, every spin routes its rounds
    (``sharded._route_round``), and liveness is a psum — the flat
    return contract, with the congestion-telemetry dict of
    :func:`run_rounds_sharded`'s keys; decisions stay bit-identical."""
    co.check_node_capacity(n_nodes)
    n_shards = mesh.shape[axis]
    batch = _as_batch(node_id, typ, inp, lines, mode)
    B, M = batch[3].shape
    n_lines = state["words"].shape[0]
    if B % n_shards:
        raise ValueError(f"B={B} not divisible by n_shards={n_shards}")
    bl = B // n_shards
    _note_trace(("txn_sharded", program.name, n_shards, B, M,
                 program.inputs, n_nodes, max_rounds, max_iters,
                 bucket_cap, backend, "dirty" in state,
                 payload_width(state), "home" in state,
                 "replica" in state))
    specs = _state_specs(state, axis)
    chunk = min(bl * M, SPIN_SLOTS)
    cap = chunk if bucket_cap is None else bucket_cap

    def spmd(state_l, *batch_l):
        ai = jax.lax.axis_index(axis)
        step, count = _router(n_shards, axis, n_nodes, cap, backend)
        out, tele, ctr = _run(
            program, state_l, batch_l, n_lines=n_lines, step=step,
            count=count,
            gather=lambda x: jax.lax.all_gather(x, axis, tiled=True),
            mine=lambda x: jax.lax.dynamic_slice_in_dim(x, ai * bl, bl),
            total=lambda x: jax.lax.pmax(x, axis), chunk=chunk,
            tele0=_zero_tele(n_shards, n_lines // n_shards),
            max_rounds=max_rounds, max_iters=max_iters)
        return out + tele + tuple(ctr[k] for k in COUNTER_KEYS)

    nk = len(COUNTER_KEYS)
    out = jax.shard_map(
        spmd, mesh=auto_mesh(mesh),
        in_specs=(specs,) + (P(axis),) * 5,
        out_specs=(specs,) + (P(axis),) * 6 + (P(), P(), P())
        + _tele_specs(axis) + (P(),) * nk,
        check_vma=False,
    )(state, *batch)
    nt = len(TELE_KEYS)
    return (out[:10] + (tele_dict(out[10:10 + nt]),)
            + (dict(zip(COUNTER_KEYS, out[10 + nt:])),))


# ------------------------------------------------------ host-facing API

@dataclass(frozen=True)
class TxnBatchResult:
    """Host-side result of one fused txn batch.

    ``decision`` bool [B] (commit / abort or rollback), ``exec_step``
    int [B] (the scheduler iteration each txn completed at — its
    position in the serial order), ``retries`` int [B] (no-wait
    restarts), ``outputs`` int [B, O] (what each transaction's program
    returned), ``lines`` / ``modes`` int [B, M] (the entries each txn
    completed with, every phase's), ``iters`` scheduler iterations,
    ``rounds`` coherence rounds across all spins, ``counters`` the :data:`COUNTER_KEYS`
    program counters (``restarts``, ``phases`` granted, ``s_grants``,
    ``x_grants``, ``rollbacks``) plus ``attempts`` (transactions started,
    restarts included).  ``telemetry`` is the
    :class:`~repro.obs.PlaneTelemetry` record summed over every spin;
    ``host_s`` the host seconds of the verb's child spans (``pad``,
    ``dispatch``, ``sync``, ``telemetry``, ``results``)."""

    decision: np.ndarray
    exec_step: np.ndarray
    retries: np.ndarray
    iters: int
    rounds: int
    telemetry: "PlaneTelemetry | None" = None
    outputs: np.ndarray | None = None
    lines: np.ndarray | None = None
    modes: np.ndarray | None = None
    counters: dict = field(default_factory=dict)
    host_s: dict = field(default_factory=dict)


def resolve_program(algo, glines=None, rmask=None) -> TxnProgram:
    """``algo`` as a program: a :class:`TxnProgram` as it is, ``"2pl"``
    or ``"to"`` the tuple program of the batch's ``[B, G]`` lines and
    ``[B, G, T]`` masks."""
    if isinstance(algo, TxnProgram):
        return algo
    return tuple_program(algo, int(np.shape(glines)[1]),
                         int(np.shape(rmask)[2]))
