"""`DevicePlane` — ONE facade over the device coherence plane.

Pre-facade, driving the rounds engine meant choosing among six
entrypoints (``run_rounds`` / ``run_rmw`` / ``run_descent`` and their
``*_sharded`` mirrors) plus three host-facing ``run_*_to_completion``
dispatchers, each with its own tuple arity (``run_ops_to_completion``
widens to a 4-tuple when ``wdata`` is passed, the RMW wrapper always
returns 4, descent returns 8) — and every APPLICATION re-implemented
the same ``mesh is None`` branch, slot padding, operand zero-padding
and bound-hit check (``index/tree.py``, ``dsm/kvpool.py``,
``serve/loop.py`` each carried a copy).  That is exactly the
programmability gap the layered-abstraction line of work (MIND; "Memory
Disaggregation: Advances and Open Challenges") says a disaggregated
memory plane must close.

:class:`DevicePlane` owns the whole bundle — ``state + mesh + n_nodes +
write_back`` — and exposes the three verbs with ONE keyword surface and
ONE result type:

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
bound was hit, and returns a :class:`PlaneResult` — ``version``, ``data``, ``rounds``,
``stats`` — instead of a positional tuple whose arity the caller must
memorize.  Sharded planes route through the very same calls: the mesh
dispatch, ``pad_ops`` slot padding and result re-slicing all live HERE,
once.

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
child annotations in it — ``plane.pad`` (sharded slot and operand
padding), ``plane.dispatch`` (the call into the jitted loop),
``plane.sync`` (the blocking ``bool(done)``), ``plane.telemetry``
(wrapping the counters, uncopied) and ``plane.results`` (the result
copies) — and report each child's host seconds in
``PlaneResult.host_s``.  With a recorder attached
(``DevicePlane.open(..., recorder=rec)`` or ``attach_recorder``) the
bracket also appends one span — wall time, rounds, serve totals,
jit-compile events — to the recorder's bounded ring.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np
from jax.profiler import TraceAnnotation

from ...obs import PlaneTelemetry


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
      bracket (``pad``, sharded only; ``dispatch``, ``sync``,
      ``telemetry``, ``results``).
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
        from .state import payload_width
        return payload_width(self.state)

    @property
    def write_back(self) -> bool:
        return "dirty" in self.state

    def flat_state(self) -> dict:
        """Host-side snapshot in FLAT (line-major) layout — unstripes a
        sharded state; use for invariants and image checks."""
        if self.sharded:
            from .sharded import unshard_state
            return unshard_state(self.state, self.mesh, self.axis)
        return self.state

    def check(self) -> None:
        """Protocol invariants over the (unsharded) state."""
        from .state import check_invariants
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
        import jax
        from .engine import EVENT_KEYS
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
        from .engine import TRACE_COUNTS
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

    def _settle(self, sp: _Span, state, done, tele, failure: str) -> None:
        """The tail every fused verb shares: wait for the loop
        (``plane.sync``), raise ``failure`` if it hit its bound, adopt
        the new state and wrap the counters, uncopied
        (``plane.telemetry``)."""
        with sp.phase("sync"):
            done = bool(done)
        if not done:
            raise RuntimeError(failure)
        self.state = state
        with sp.phase("telemetry"):
            sp.telemetry = self._telemetry(tele)

    # ------------------------------------------------------------- verbs
    def ops(self, node_id, line, is_write, wdata=None, *,
            max_rounds: int | None = None) -> PlaneResult:
        """Drive op slots ``(node, line, is_write[, wdata])`` to
        completion through the fused spin loop (flat or sharded)."""
        mr = self.max_rounds if max_rounds is None else max_rounds
        r = np.asarray(line).shape[0]
        with self._span("ops", batch=(r,)) as sp:
            if self.sharded:
                from .sharded import pad_ops, run_rounds_sharded
                with sp.phase("pad"):
                    if wdata is None:
                        node_id, line, is_write = pad_ops(
                            node_id, line, is_write, self.n_shards)
                    else:
                        node_id, line, is_write, wdata = pad_ops(
                            node_id, line, is_write, self.n_shards,
                            wdata)
                with sp.phase("dispatch"):
                    state, versions, data, rounds, done, tele = \
                        run_rounds_sharded(
                            self.state, node_id, line, is_write, wdata,
                            mesh=self.mesh, axis=self.axis,
                            n_nodes=self.n_nodes, max_rounds=mr,
                            bucket_cap=self.bucket_cap,
                            backend=self.backend)
            else:
                from .driver import run_rounds
                with sp.phase("dispatch"):
                    state, versions, data, rounds, done, tele = \
                        run_rounds(self.state, node_id, line, is_write,
                                   wdata, n_nodes=self.n_nodes,
                                   max_rounds=mr, backend=self.backend)
            self._settle(sp, state, done, tele,
                         f"ops not served after {mr} rounds")
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
            if self.sharded:
                from .sharded import pad_ops, run_rmw_sharded
                with sp.phase("pad"):
                    node_id, line, _ = pad_ops(node_id, line,
                                               np.zeros(r, np.int32),
                                               self.n_shards)
                    pad = np.asarray(line).shape[0] - r
                    if pad:
                        operands = tuple(
                            np.concatenate(
                                [np.asarray(op),
                                 np.zeros((pad,) + np.asarray(op).shape[1:],
                                          np.asarray(op).dtype)])
                            for op in operands)
                with sp.phase("dispatch"):
                    state, versions, data, rounds, done, tele = \
                        run_rmw_sharded(
                            self.state, node_id, line, tuple(operands),
                            modify=modify, mesh=self.mesh, axis=self.axis,
                            n_nodes=self.n_nodes, max_rounds=mr,
                            bucket_cap=self.bucket_cap,
                            backend=self.backend)
            else:
                from .driver import run_rmw
                with sp.phase("dispatch"):
                    state, versions, data, rounds, done, tele = run_rmw(
                        self.state, node_id, line, tuple(operands),
                        modify=modify, n_nodes=self.n_nodes,
                        max_rounds=mr, backend=self.backend)
            self._settle(sp, state, done, tele,
                         f"RMW ops not served after {mr} rounds per phase")
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
            if self.sharded:
                from .sharded import pad_ops, run_descent_sharded
                with sp.phase("pad"):
                    node_id, root, key = pad_ops(node_id, root, key,
                                                 self.n_shards)
                with sp.phase("dispatch"):
                    (state, line, lanes, levels, hops, paths, plen, steps,
                     done, tele) = run_descent_sharded(
                            self.state, node_id, key, root,
                            transition=transition, mesh=self.mesh,
                            axis=self.axis, n_nodes=self.n_nodes,
                            max_steps=ms, bucket_cap=self.bucket_cap,
                            backend=self.backend, path_cap=path_cap)
            else:
                from .descent import run_descent
                with sp.phase("dispatch"):
                    (state, line, lanes, levels, hops, paths, plen, steps,
                     done, tele) = run_descent(
                            self.state, node_id, key, root,
                            transition=transition, n_nodes=self.n_nodes,
                            max_steps=ms, backend=self.backend,
                            path_cap=path_cap)
            self._settle(sp, state, done, tele,
                         f"descent did not settle after {ms} steps "
                         f"(broken links?)")
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
            max_rounds: int | None = None):
        """Run one transaction batch through the fused device CC loop
        (:mod:`repro.core.rounds.txn`): ``algo`` is ``"2pl"`` or
        ``"to"`` (``batch`` = ``glines, rmask, wmask, ts``) or a
        ``TxnProgram`` (``batch`` = what its ``encode`` takes).  The
        bracket's children ``plane.pad`` (encoding, sharded slot
        padding), ``dispatch``, ``sync``, ``telemetry`` and ``results``
        are timed into the returned ``TxnBatchResult.host_s``."""
        from .txn import run_txn_batch
        shape = tuple(np.shape(batch[0])) if batch else ()
        with self._span("txn", batch=shape) as sp:
            res = run_txn_batch(self, node_id, *batch, algo=algo,
                                max_iters=max_iters,
                                max_rounds=max_rounds, span=sp)
            sp.rounds, sp.telemetry = res.rounds, res.telemetry
            sp.attrs = {"algo": getattr(algo, "name", algo)}
        return res

    def evict(self, node_id, line) -> None:
        """Evict (node, line) pairs: release holder latches, flushing
        dirty write-back copies first."""
        r = np.asarray(line).shape[0]
        with self._span("evict", batch=(r,)):
            if self.sharded:
                from .sharded import evict_lines_sharded, pad_ops
                node_id, line, _ = pad_ops(
                    node_id, line, np.zeros(r, np.int32), self.n_shards)
                self.state = evict_lines_sharded(
                    self.state, node_id, line, mesh=self.mesh,
                    axis=self.axis, bucket_cap=self.bucket_cap)
            else:
                from .engine import evict_lines
                self.state = evict_lines(self.state, node_id, line)

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
            from .sharded import rehome_exchange
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
            import jax.numpy as jnp
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
        import jax
        import jax.numpy as jnp
        from .. import coherence as co
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
            from jax.sharding import NamedSharding, PartitionSpec as P

            from .sharded import auto_mesh
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
