"""`DevicePlane` — ONE facade over the device coherence plane.

Applications (``index/tree.py``, ``dsm/kvpool.py``, ``serve/loop.py``)
drive the plane through one object and never branch on the geometry —
the programmability layer MIND and "Memory Disaggregation: Advances and
Open Challenges" ask of a disaggregated memory plane.
:class:`DevicePlane` owns ``state + mesh + n_nodes + write_back`` and
exposes the verbs with ONE keyword surface and ONE result type:

    plane = DevicePlane.open(state, mesh=None, n_nodes=4)    # or
    plane = layer.as_plane(payload_width=W, mesh=mesh)       # from DES

    res = plane.ops(node, line, is_write, wdata=wdata)   # PlaneResult
    res = plane.rmw(node, line, modify=splice, operands=(tok,))
    res = plane.descent(node, key, root, transition=step)
    out = plane.txn(node, glines, rmask, wmask, ts, algo="2pl")
    out = plane.txn(node, typ, inp, lines, mode, algo=program)

Every verb mutates ``plane.state`` in place (the plane IS the memory),
materializes its result arrays exactly once at the end (zero syncs
inside the fused loops), raises ``RuntimeError`` if the round/step
bound was hit, and returns a :class:`PlaneResult` (``version``,
``data``, ``rounds``, ``stats``).  Sharded planes route through the
very same calls: the plane picks its drivers (flat or mesh) once, when
it is built, and the slot padding and result re-slicing live HERE.

Every verb — flat AND sharded — also surfaces the telemetry the fused
loops accumulate in their carries as a typed
:class:`~repro.obs.telemetry.PlaneTelemetry`
(``PlaneResult.telemetry``: occupancy/deferred/served counters plus
per-line hit counts, diff-able bit-for-bit between a flat plane and any
shard count on the same op trace).  The verb copies none of it to the
host: the record holds the counter leaves on the device and copies a
group (the small counters, or the per-line pair) the first time a
consumer reads one of its fields, so a caller that never reads the
telemetry pays no copy.  Two placement verbs act on it at
op-quiescent boundaries: :meth:`DevicePlane.rehome` migrates lines
between home shards through the coherent directory,
:meth:`DevicePlane.replicate` marks read-mostly lines for replica
serving.  ``core/rounds/placement.py`` turns the counters (or the
EWMA heat a :class:`~repro.obs.recorder.FlightRecorder` distills from
them) into migration/replication picks.

Every verb runs inside ONE bracket, :meth:`DevicePlane._span`: a
``jax.profiler.TraceAnnotation`` named ``plane.<verb>`` on the
profiler's clock, so a device trace shows each dispatch beside the
device work it caused.  ``ops``, ``rmw`` and ``descent`` nest fixed
child annotations in it — ``plane.pad`` (slot and operand padding,
on a mesh of more than one shard), ``plane.dispatch`` (the call into
the jitted loop), ``plane.sync`` (the blocking ``bool(done)``),
``plane.telemetry`` (wrapping the counters, uncopied) and
``plane.results`` (the result copies) — and report each child's host seconds in
``PlaneResult.host_s``.  With a recorder attached
(``DevicePlane.open(..., recorder=rec)`` or ``attach_recorder``) the
bracket also appends one span — wall time, rounds, serve totals,
jit-compile events — to the recorder's bounded ring.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from ...obs import PlaneTelemetry
from .. import coherence as co
from .descent import run_descent
from .driver import run_rmw, run_rounds
from .engine import EVENT_KEYS, TRACE_COUNTS, evict_lines
from .sharded import (auto_mesh, evict_lines_sharded, rehome_exchange,
                      run_descent_sharded, run_rmw_sharded,
                      run_rounds_sharded, unshard_state)
from .state import check_invariants, payload_width
from .txn import (TxnBatchResult, resolve_program, run_txn_rounds,
                  run_txn_rounds_sharded)


@dataclass(frozen=True)
class PlaneResult:
    """Normalized result of every DevicePlane verb.

    * ``version`` — per-slot protocol versions [R] (``None`` for
      descents: a read walk names lanes, not versions);
    * ``data``    — per-slot payload lanes [R, W] (the read bytes for
      ``ops``/``rmw``, the LEAF lanes for ``descent``; width 0 on a
      version-only plane);
    * ``rounds``  — coherence rounds (or descent steps) the fused loop
      spent, summed over phases;
    * ``stats``   — verb-specific extras (descent: ``line``, ``levels``,
      ``hops``, ``paths``, ``path_len``; other verbs: ``{}``);
    * ``telemetry`` — the :class:`~repro.obs.telemetry.PlaneTelemetry`
      counters accumulated inside the fused loop, on EVERY plane
      geometry: ``occupancy``/``deferred`` [S, S] (row = source shard,
      col = home: bucket entries sent / deferred on overflow; S = 1
      flat, where nothing defers), ``served_per_home`` [S],
      ``replica_served`` [S] (per source shard), and per-line
      ``line_hits``/``line_whits`` [L] (ops served per line id; whits =
      write subset).  The per-line counters are bit-identical between a
      flat plane and any shard count on the same op trace.  Lazy: the
      first read of a small counter copies all of them to the host in
      one ``jax.device_get``, the first read of ``line_hits`` or
      ``line_whits`` copies that pair (and remaps a sharded plane's
      slots through the home directory the verb left).  Until then a
      held result keeps the unread leaves on the device (two [L]
      int32 counters and a few [S, S]); drop it to free them;
    * ``host_s``  — host seconds of each child span of the verb's
      bracket (``pad``, on more than one shard only; ``dispatch``,
      ``sync``, ``telemetry``, ``results``).
    """

    version: np.ndarray | None
    data: np.ndarray | None
    rounds: int
    stats: dict = field(default_factory=dict)
    telemetry: PlaneTelemetry | None = None
    host_s: dict = field(default_factory=dict)


class _Span:
    """What one verb's bracket collects: its children's host seconds,
    and the rounds, telemetry and attributes a recorder span carries."""

    __slots__ = ("host_s", "rounds", "telemetry", "attrs")

    def __init__(self):
        self.host_s: dict = {}
        self.rounds = 0
        self.telemetry = None
        self.attrs = None

    @contextmanager
    def phase(self, name: str):
        """Child span ``plane.<name>``: a profiler annotation, timed."""
        t0 = time.perf_counter()
        with TraceAnnotation("plane." + name):
            yield
        self.host_s[name] = time.perf_counter() - t0


class DevicePlane:
    """Facade owning a rounds-plane state and its execution geometry.

    ``open`` adopts an EXISTING state (flat or mesh-sharded); build
    fresh states with ``make_state`` / ``make_sharded_state`` or the
    DES bridge ``SELCCLayer.as_plane``.  All verbs mutate
    ``self.state``; read it back (flat layout, host-side) with
    :meth:`flat_state`.
    """

    def __init__(self, state, mesh=None, *, axis: str = "shards",
                 n_nodes: int | None = None, backend: str = "ref",
                 max_rounds: int = 64, bucket_cap: int | None = None,
                 recorder=None):
        self.state = state
        self.mesh = mesh
        self.axis = axis
        self.n_nodes = (int(state["cache_state"].shape[0])
                        if n_nodes is None else int(n_nodes))
        self.backend = backend
        self.max_rounds = int(max_rounds)
        self.bucket_cap = bucket_cap
        self.recorder = recorder
        # the geometry, chosen once: each verb's fused driver and the
        # keywords only a mesh takes
        if mesh is None:
            self._run = {"ops": run_rounds, "rmw": run_rmw,
                         "descent": run_descent, "txn": run_txn_rounds,
                         "evict": evict_lines}
            self._geo = {}
        else:
            self._run = {"ops": run_rounds_sharded, "rmw": run_rmw_sharded,
                         "descent": run_descent_sharded,
                         "txn": run_txn_rounds_sharded,
                         "evict": evict_lines_sharded}
            self._geo = {"mesh": mesh, "axis": axis,
                         "bucket_cap": bucket_cap}

    @classmethod
    def open(cls, state, mesh=None, *, axis: str = "shards",
             n_nodes: int | None = None, backend: str = "ref",
             max_rounds: int = 64, bucket_cap: int | None = None,
             recorder=None) -> "DevicePlane":
        """The one constructor: wrap a round state (+ optional mesh).
        ``recorder`` optionally attaches an ``obs.FlightRecorder`` that
        receives one span per verb dispatch."""
        return cls(state, mesh, axis=axis, n_nodes=n_nodes,
                   backend=backend, max_rounds=max_rounds,
                   bucket_cap=bucket_cap, recorder=recorder)

    def attach_recorder(self, recorder) -> None:
        """Attach (or replace, or with ``None`` detach) the plane's
        ``obs.FlightRecorder`` — spans start/stop appearing on the
        next verb dispatch."""
        self.recorder = recorder

    # ------------------------------------------------------------ geometry
    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis] if self.sharded else 1

    @property
    def n_lines(self) -> int:
        return int(self.state["words"].shape[0])

    @property
    def payload_width(self) -> int:
        return payload_width(self.state)

    @property
    def write_back(self) -> bool:
        return "dirty" in self.state

    def flat_state(self) -> dict:
        """Host-side snapshot in FLAT (line-major) layout — unstripes a
        sharded state; use for invariants and image checks."""
        if self.sharded:
            return unshard_state(self.state, self.mesh, self.axis)
        return self.state

    def check(self) -> None:
        """Protocol invariants over the (unsharded) state."""
        check_invariants(self.flat_state())

    # --------------------------------------------------------- telemetry
    def _telemetry(self, tele) -> PlaneTelemetry:
        """Wrap a fused loop's telemetry counter dict, still on the
        device, in a lazy :class:`PlaneTelemetry`; nothing is copied
        here.  The first read of a small counter fetches every small
        leaf in one ``jax.device_get`` (the [S, 3] ``events`` leaf
        summed into the three protocol counters); the first read of a
        per-line counter fetches both [L] leaves in one, and remaps the
        physical-slot counters to LINE ids.  Sharded counters come back
        in the shard-major slab concatenation and route through the
        home directory as this verb left it: ``home`` is captured here,
        an immutable array, so a later :meth:`rehome` cannot change an
        earlier result.  The flat engine presents ops BY line id, so its
        counters are line-indexed already (identity — the home perm does
        not reorder them)."""
        small = {k: v for k, v in tele.items()
                 if k not in ("slot_hits", "slot_whits")}
        slots = (tele["slot_hits"], tele["slot_whits"])
        sharded, l, s = self.sharded, self.n_lines, self.n_shards
        # the record holds this state leaf: a verb that donates its
        # state must copy or keep ``home`` undonated
        home = self.state.get("home") if sharded else None

        def counters():
            c = jax.device_get(small)
            # one [S, 3] leaf carries the protocol events, a row per home
            c.update(zip(EVENT_KEYS, c.pop("events").sum(axis=0)))
            return c

        def lines():
            hits, whits, perm = jax.device_get(slots + (home,))
            if sharded:
                if perm is None:
                    perm = np.arange(l, dtype=np.int64)
                # slot p lives at row (p % S) * (L // S) + p // S of the
                # shard-major concatenation the counters come back in
                pos = (perm % s) * (l // s) + perm // s
                hits, whits = hits[pos], whits[pos]
            return {"line_hits": hits, "line_whits": whits}

        return PlaneTelemetry.lazy(counters, lines)

    @contextmanager
    def _span(self, verb: str, batch=()):
        """The verb's one bracket: the ``plane.<verb>`` profiler
        annotation and, when a recorder is attached, one recorder span
        (compile events = the TRACE_COUNTS delta over the dispatch).
        Yields a :class:`_Span` the verb fills; a verb that raises
        records no span."""
        rec = self.recorder
        if rec is not None:
            t0, c0 = time.perf_counter(), sum(TRACE_COUNTS.values())
        span = _Span()
        with TraceAnnotation("plane." + verb):
            yield span
        if rec is not None:
            rec.record(verb, duration=time.perf_counter() - t0,
                       batch=batch, rounds=span.rounds,
                       telemetry=span.telemetry,
                       compiled=sum(TRACE_COUNTS.values()) - c0,
                       attrs=span.attrs)

    def _settle(self, sp: _Span, state, tele, *checks) -> None:
        """The tail every fused verb shares: wait for the loop
        (``plane.sync``), raise the failure of the first ``(flag,
        failure)`` check whose flag is False, adopt the new state and
        wrap the counters, uncopied (``plane.telemetry``)."""
        with sp.phase("sync"):
            flags = [bool(flag) for flag, _ in checks]
        for ok, (_, failure) in zip(flags, checks):
            if not ok:
                raise RuntimeError(failure)
        self.state = state
        with sp.phase("telemetry"):
            sp.telemetry = self._telemetry(tele)

    def _rows(self, n: int, *cols):
        """``(array, fill)`` columns of ``n`` rows as host arrays, padded
        with ``fill`` rows to a multiple of the shard count (``None``
        passes)."""
        pad = (-n) % self.n_shards
        out = []
        for c, fill in cols:
            if c is not None:
                c = np.asarray(c)
                if pad:
                    c = np.concatenate(
                        [c, np.full((pad,) + c.shape[1:], fill, c.dtype)])
            out.append(c)
        return out

    def _pad(self, sp: _Span, n: int, *cols):
        """:meth:`_rows` under the ``plane.pad`` child; one shard pads
        nothing, so there the columns pass with no child."""
        if self.n_shards == 1:
            return [c for c, _ in cols]
        with sp.phase("pad"):
            return self._rows(n, *cols)

    # ------------------------------------------------------------- verbs
    def ops(self, node_id, line, is_write, wdata=None, *,
            max_rounds: int | None = None) -> PlaneResult:
        """Drive op slots ``(node, line, is_write[, wdata])`` to
        completion through the fused spin loop (flat or sharded)."""
        mr = self.max_rounds if max_rounds is None else max_rounds
        r = np.asarray(line).shape[0]
        with self._span("ops", batch=(r,)) as sp:
            node_id, line, is_write, wdata = self._pad(
                sp, r, (node_id, 0), (line, -1), (is_write, 0), (wdata, 0))
            with sp.phase("dispatch"):
                state, versions, data, rounds, done, tele = \
                    self._run["ops"](self.state, node_id, line, is_write,
                                     wdata, n_nodes=self.n_nodes,
                                     max_rounds=mr, backend=self.backend,
                                     **self._geo)
            self._settle(sp, state, tele,
                         (done, f"ops not served after {mr} rounds"))
            with sp.phase("results"):
                sp.rounds = int(rounds)
                versions = np.asarray(versions)[:r]
                data = np.asarray(data)[:r]
        return PlaneResult(versions, data, sp.rounds, {}, sp.telemetry,
                           sp.host_s)

    def rmw(self, node_id, line, *, modify, operands=(),
            max_rounds: int | None = None) -> PlaneResult:
        """Fused coherent read-modify-write: ``modify(data, line,
        *operands)`` runs on device between the read and write phases.
        ``modify`` must be a static callable (cache it per shape) and
        treat ``line = -1`` rows as no-ops; operands must be ``[R, ...]``
        row-aligned with the op slots (sharded planes zero-pad them
        alongside the slots)."""
        mr = self.max_rounds if max_rounds is None else max_rounds
        r = np.asarray(line).shape[0]
        with self._span("rmw", batch=(r,)) as sp:
            node_id, line, *operands = self._pad(
                sp, r, (node_id, 0), (line, -1),
                *((op, 0) for op in operands))
            with sp.phase("dispatch"):
                state, versions, data, rounds, done, tele = \
                    self._run["rmw"](self.state, node_id, line,
                                     tuple(operands), modify=modify,
                                     n_nodes=self.n_nodes, max_rounds=mr,
                                     backend=self.backend, **self._geo)
            self._settle(sp, state, tele, (
                done, f"RMW ops not served after {mr} rounds per phase"))
            with sp.phase("results"):
                sp.rounds = int(rounds)
                versions = np.asarray(versions)[:r]
                data = np.asarray(data)[:r]
        return PlaneResult(versions, data, sp.rounds, {}, sp.telemetry,
                           sp.host_s)

    def descent(self, node_id, key, root, *, transition,
                path_cap: int = 16,
                max_steps: int | None = None) -> PlaneResult:
        """Whole pointer-chase walk in one dispatch: ``transition(data,
        key) -> (at_leaf, hop, nxt)`` advances every slot on device.
        ``data`` is each slot's LEAF lanes; ``stats`` carries ``line``,
        ``levels``, ``hops``, ``paths``, ``path_len``."""
        ms = self.max_rounds if max_steps is None else max_steps
        r = np.asarray(root).shape[0]
        with self._span("descent", batch=(r,)) as sp:
            node_id, key, root = self._pad(sp, r, (node_id, 0), (key, 0),
                                           (root, -1))
            with sp.phase("dispatch"):
                (state, line, lanes, levels, hops, paths, plen, steps,
                 done, tele) = self._run["descent"](
                    self.state, node_id, key, root, transition=transition,
                    n_nodes=self.n_nodes, max_steps=ms,
                    backend=self.backend, path_cap=path_cap, **self._geo)
            self._settle(sp, state, tele, (
                done, f"descent did not settle after {ms} steps "
                      f"(broken links?)"))
            with sp.phase("results"):
                sp.rounds = int(steps)
                stats = {"line": np.asarray(line)[:r],
                         "levels": np.asarray(levels)[:r],
                         "hops": np.asarray(hops)[:r],
                         "paths": np.asarray(paths)[:r],
                         "path_len": np.asarray(plen)[:r]}
                lanes = np.asarray(lanes)[:r]
        return PlaneResult(None, lanes, sp.rounds, stats=stats,
                           telemetry=sp.telemetry, host_s=sp.host_s)

    def txn(self, node_id, *batch, algo, max_iters: int | None = None,
            max_rounds: int | None = None) -> TxnBatchResult:
        """Run one transaction batch through the fused device CC loop
        (:mod:`repro.core.rounds.txn`): ``algo`` is ``"2pl"`` or
        ``"to"`` (``batch`` = ``glines, rmask, wmask, ts``) or a
        ``TxnProgram`` (``batch`` = what its ``encode`` takes).  The
        bracket's children ``plane.pad`` (encoding, and slot padding on
        more than one shard), ``dispatch``, ``sync``, ``telemetry`` and
        ``results`` are timed into the returned
        ``TxnBatchResult.host_s``."""
        mr = self.max_rounds if max_rounds is None else max_rounds
        shape = tuple(np.shape(batch[0])) if batch else ()
        with self._span("txn", batch=shape) as sp:
            with sp.phase("pad"):
                program = resolve_program(algo, *batch[:2])
                typ, inp, lines, mode = program.encode(*batch)
                if self.payload_width != program.width:
                    raise ValueError(
                        f"plane payload_width={self.payload_width} but "
                        f"program {program.name!r} needs {program.width} "
                        f"lanes")
                b = lines.shape[0]
                node_id = np.broadcast_to(np.asarray(node_id, np.int32),
                                          (b,)).astype(np.int32)
                mi = 4 * b + 16 if max_iters is None else max_iters
                node_id, typ, inp, lines, mode = self._rows(
                    b, (node_id, 0), (typ, -1), (inp, 0), (lines, -1),
                    (mode, 0))
            with sp.phase("dispatch"):
                (state, dec, estep, retr, outs, lns, mds, alldone, ok,
                 rounds, tele, ctr) = self._run["txn"](
                    self.state, node_id, typ, inp, lines, mode,
                    program=program, n_nodes=self.n_nodes, max_rounds=mr,
                    max_iters=mi, backend=self.backend, **self._geo)
            self._settle(
                sp, state, tele,
                (ok, f"txn coherence spin hit max_rounds={mr}"),
                (alldone, f"txn batch not done after {mi} scheduler "
                          f"iterations (livelock? raise max_iters)"))
            with sp.phase("results"):
                counters = {k: int(v) for k, v in
                            jax.device_get(ctr).items()}
                counters["attempts"] = b + counters["restarts"]
                sp.rounds = int(rounds)
                res = TxnBatchResult(
                    np.asarray(dec)[:b], np.asarray(estep)[:b],
                    np.asarray(retr)[:b], counters["iters"], sp.rounds,
                    sp.telemetry, np.asarray(outs)[:b],
                    np.asarray(lns)[:b], np.asarray(mds)[:b], counters,
                    sp.host_s)
            sp.attrs = {"algo": program.name}
        return res

    def evict(self, node_id, line) -> None:
        """Evict (node, line) pairs: release holder latches, flushing
        dirty write-back copies first."""
        r = np.asarray(line).shape[0]
        with self._span("evict", batch=(r,)) as sp:
            node_id, line = self._pad(sp, r, (node_id, 0), (line, -1))
            self.state = self._run["evict"](self.state, node_id, line,
                                            **self._geo)

    # -------------------------------------------------------- placement
    def rehome(self, lines, new_homes, victims=None) -> int:
        """Migrate ``lines[i]`` to home shard ``new_homes[i]`` through
        the coherent directory — pairwise SLOT SWAPS with a victim line
        currently homed on the target shard, executed as one bucketed
        all_to_all slab-row exchange (:func:`sharded.rehome_exchange`).
        Legal only at op-quiescent boundaries (between verbs — there is
        no in-flight op to race).  ``victims[i]`` picks the swap partner
        explicitly (``plan_rehome`` supplies one); otherwise the
        highest-id line still homed on the target is chosen.  Lines
        already on their target, or requested twice, are skipped.
        Returns the number of migrations performed.  On a FLAT plane
        the directory updates but no rows move (everything is local
        anyway) — kept so flat/sharded differentials can replay the
        same call sequence."""
        if "home" not in self.state:
            raise ValueError(
                "rehome needs a home-directory state "
                "(make_state(..., home_directory=True))")
        lines = np.asarray(lines, np.int64).reshape(-1)
        new_homes = np.asarray(new_homes, np.int64).reshape(-1)
        if lines.shape != new_homes.shape:
            raise ValueError("lines and new_homes must match in length")
        if victims is not None:
            victims = np.asarray(victims, np.int64).reshape(-1)
            if victims.shape != lines.shape:
                raise ValueError("victims must match lines in length")
        l, s = self.n_lines, self.n_shards
        if lines.size and (lines.min() < 0 or lines.max() >= l):
            raise ValueError(f"line ids out of range [0, {l})")
        if new_homes.size and (new_homes.min() < 0
                               or new_homes.max() >= s):
            raise ValueError(f"home shards out of range [0, {s})")
        perm = np.asarray(self.state["home"]).astype(np.int64).copy()
        taken: set = set()
        src, dst = [], []
        for i in range(lines.size):
            a, h = int(lines[i]), int(new_homes[i])
            if a in taken or perm[a] % s == h:
                continue
            if victims is not None:
                b = int(victims[i])
                if b in taken or b == a or perm[b] % s != h:
                    continue
            else:
                cands = np.flatnonzero(perm % s == h)
                cands = [c for c in cands[::-1] if int(c) not in taken]
                if not cands:
                    continue
                b = int(cands[0])
            taken.update((a, b))
            src.extend((perm[a], perm[b]))
            dst.extend((perm[b], perm[a]))
            perm[a], perm[b] = perm[b], perm[a]
        if not src:
            return 0
        if self.sharded:
            # pad the move list to a power of two: one compiled
            # exchange shape serves many migration sizes
            m = 1
            while m < len(src):
                m *= 2
            src = np.asarray(src + [-1] * (m - len(src)), np.int32)
            dst = np.asarray(dst + [0] * (m - len(dst)), np.int32)
            self.state = rehome_exchange(
                self.state, src, dst, perm.astype(np.int32),
                mesh=self.mesh, axis=self.axis)
        else:
            self.state = dict(self.state)
            self.state["home"] = jnp.asarray(perm, jnp.int32)
        return len(taken) // 2

    def replicate(self, lines, *, enable: bool = True) -> None:
        """Mark ``lines`` read-replicated (or drop the mark with
        ``enable=False``): S-latch reads on a replicated line serve
        from the requester's own shard's boundary-snapshot image
        instead of routing to the home, and any granted write
        invalidates the image through the normal MSI path.  Host-side
        and boundary-only, like :meth:`rehome`: the replica images of
        newly marked lines whose memory is current (no exclusive
        holder) are seeded here; the rest seed at the next round
        boundary."""
        if "replica" not in self.state:
            raise ValueError(
                "replicate needs a replica-plane state "
                "(make_state(..., replicas=True))")
        lines = np.asarray(lines, np.int64).reshape(-1)
        l = self.n_lines
        if lines.size and (lines.min() < 0 or lines.max() >= l):
            raise ValueError(f"line ids out of range [0, {l})")
        flat = {k: np.asarray(v) for k, v in self.flat_state().items()}
        rep = flat["replica"].copy()
        rep[lines] = bool(enable)
        no_m = ~(flat["cache_state"] == co.M).any(axis=0)
        rok = rep & no_m
        rver = np.where(rok, flat["mem_version"],
                        flat["replica_version"])
        leaves = {"replica": rep, "replica_ok": rok,
                  "replica_version": rver.astype(np.int32)}
        if "replica_data" in flat:
            leaves["replica_data"] = np.where(
                rok[:, None], flat["mem_data"],
                flat["replica_data"]).astype(np.int32)
        self.state = dict(self.state)
        if self.sharded:
            mesh = auto_mesh(self.mesh)
            for k, v in leaves.items():
                self.state[k] = jax.device_put(
                    jnp.asarray(v),
                    NamedSharding(mesh, P(*([None] * v.ndim))))
        else:
            for k, v in leaves.items():
                self.state[k] = jnp.asarray(v)

    def __repr__(self) -> str:
        geo = (f"sharded x{self.n_shards}" if self.sharded else "flat")
        return (f"DevicePlane({geo}, n_nodes={self.n_nodes}, "
                f"n_lines={self.n_lines}, W={self.payload_width}, "
                f"{'write-back' if self.write_back else 'write-through'})")
