"""`PlaneTelemetry` — ONE typed record for the fused loops' counters.

Pre-obs, telemetry was an ad-hoc dict: the sharded drivers returned six
loosely-named arrays in ``PlaneResult.stats``, the FLAT drivers returned
``{}`` (so every consumer grew an ``if res.stats:`` guard), and
``placement.py`` documented its inputs by dict-key spelling.  This
module is the schema both planes now share: every verb — flat or
sharded — returns one :class:`PlaneTelemetry` whose per-line counters
are diff-able bit-for-bit between a flat plane and any shard count on
the same op trace (the flat differential oracles assert exactly that).

Field geometry (S = home shards, 1 on a flat plane; L = lines):

* ``occupancy``     [S, S] — request-bucket entries SENT per (source,
  home) per round, summed over the spin (flat: ops presented per
  round, all in the single [0, 0] cell);
* ``deferred``      [S, S] — entries deferred on bucket overflow (flat:
  always 0 — nothing crosses a transport);
* ``served_per_home`` [S]  — ops served at each home's slab;
* ``replica_served``  [S]  — reads served from the source shard's local
  replica image (flat: 0 — the flat engine has no replica serve path);
* ``line_hits``       [L]  — served ops per LINE id (home-slot counters
  remapped through the directory; the placement probe signal);
* ``line_whits``      [L]  — the write subset of ``line_hits``;
* ``latch_requests``  []   — group representatives that issued a latch
  CAS/FAA, summed over the rounds (the round body's ``miss``);
* ``latch_failed``    []   — those requests that failed and retry next
  round (a CAS lost to a peer, a read blocked by a writer);
* ``local_hits``      []   — served slots whose group hit the node's own
  cached copy with no latch request: SELCC's lazy latch.

The three protocol counters are counted at the home that runs the round
body, so a sharded plane whose buckets never overflow counts what a flat
plane counts on the same op trace.

The record is also a read-only mapping (``tele["line_hits"]``,
``dict(tele)``) so counter-dict call sites port mechanically, and
``__add__`` accumulates across verbs/batches (``sum(teles,
PlaneTelemetry.zeros(...))`` or plain ``a + b``).

When the copy happens.  A verb's record (:meth:`PlaneTelemetry.lazy`)
holds the fused loop's counter leaves as device arrays; the verb copies
nothing to the host.  The first read of a field copies its GROUP, once
(``DevicePlane`` fetches a group in one ``jax.device_get``):

* the small counters (``occupancy``, ``deferred``, ``served_per_home``,
  ``replica_served`` and the three protocol counters), read by
  ``served``, ``deferred_total`` and ``n_shards`` too;
* the per-line pair ``line_hits``/``line_whits`` ([L] int32 each on
  the device, 4 MiB each at 2^20 lines), read by ``n_lines`` and
  ``write_fraction`` too.

Equality, ``+``, ``items()``/``as_dict()`` and ``repr`` read both.  A
held record keeps each unread group's leaves on the device until it is
dropped.  Values and types do not depend on when the copy happens
(int64 numpy on access).  :data:`TELEMETRY_READS` counts the copies per
group, so a test can show that a loop that reads nothing copies
nothing.
"""

from __future__ import annotations

import numpy as np

__all__ = ["PlaneTelemetry", "TELEMETRY_READS"]

_COUNTER_KEYS = ("occupancy", "deferred", "served_per_home",
                 "replica_served", "latch_requests", "latch_failed",
                 "local_hits")
_LINE_KEYS = ("line_hits", "line_whits")
_KEYS = _COUNTER_KEYS[:4] + _LINE_KEYS + _COUNTER_KEYS[4:]
_GROUPS = {"counters": _COUNTER_KEYS, "lines": _LINE_KEYS}

# Host copies of a lazy record's groups, by group (process-wide, like
# ``engine.TRACE_COUNTS``: tests compare before and after).
TELEMETRY_READS: dict = {"counters": 0, "lines": 0}


def _host(values, keys) -> dict:
    return {k: np.asarray(values[k], np.int64) for k in keys}


def _field(group: str, key: str) -> property:
    return property(lambda self: self._group(group)[key])


class PlaneTelemetry:
    """Congestion/serve counters of one fused dispatch (or a sum)."""

    __slots__ = ("_groups", "_fetch")

    occupancy = _field("counters", "occupancy")        # [S, S] sent
    deferred = _field("counters", "deferred")          # [S, S] defers
    served_per_home = _field("counters", "served_per_home")  # [S]
    replica_served = _field("counters", "replica_served")    # [S]
    line_hits = _field("lines", "line_hits")           # [L] served ops
    line_whits = _field("lines", "line_whits")         # [L] writes
    latch_requests = _field("counters", "latch_requests")    # []
    latch_failed = _field("counters", "latch_failed")        # []
    local_hits = _field("counters", "local_hits")            # []

    def __init__(self, **counters):
        """A record of host values, one per field name (any integer
        array-likes; stored as int64)."""
        if set(counters) != set(_KEYS):
            raise TypeError(f"PlaneTelemetry needs exactly {_KEYS}, "
                            f"got {tuple(counters)}")
        self._groups = {g: _host(counters, keys)
                        for g, keys in _GROUPS.items()}
        self._fetch = {}

    # ------------------------------------------------------ constructors
    @classmethod
    def lazy(cls, counters, lines) -> "PlaneTelemetry":
        """A record copied on first read, group by group: ``counters()``
        returns the small fields, ``lines()`` the per-line pair, each a
        dict of host array-likes.  Each is called at most once, then
        dropped with whatever device arrays it holds."""
        tele = cls.__new__(cls)
        tele._groups = {}
        tele._fetch = {"counters": counters, "lines": lines}
        return tele

    @classmethod
    def zeros(cls, n_shards: int, n_lines: int) -> "PlaneTelemetry":
        s, l = int(n_shards), int(n_lines)
        return cls(occupancy=np.zeros((s, s), np.int64),
                   deferred=np.zeros((s, s), np.int64),
                   served_per_home=np.zeros((s,), np.int64),
                   replica_served=np.zeros((s,), np.int64),
                   line_hits=np.zeros((l,), np.int64),
                   line_whits=np.zeros((l,), np.int64),
                   latch_requests=np.zeros((), np.int64),
                   latch_failed=np.zeros((), np.int64),
                   local_hits=np.zeros((), np.int64))

    @classmethod
    def from_counters(cls, counters) -> "PlaneTelemetry":
        """Adopt a host counter dict (hit counters already remapped to
        LINE ids)."""
        return cls(**{k: counters[k] for k in _KEYS})

    def _group(self, group: str) -> dict:
        values = self._groups.get(group)
        if values is None:
            TELEMETRY_READS[group] += 1
            values = _host(self._fetch.pop(group)(), _GROUPS[group])
            self._groups[group] = values
        return values

    # --------------------------------------------------------- geometry
    @property
    def n_shards(self) -> int:
        return int(self.served_per_home.shape[0])

    @property
    def n_lines(self) -> int:
        return int(self.line_hits.shape[0])

    # ---------------------------------------------------------- totals
    @property
    def served(self) -> int:
        """All served ops: home serves plus replica serves."""
        return int(self.served_per_home.sum()
                   + self.replica_served.sum())

    @property
    def deferred_total(self) -> int:
        return int(self.deferred.sum())

    @property
    def write_fraction(self) -> float:
        hits = int(self.line_hits.sum())
        return float(self.line_whits.sum()) / hits if hits else 0.0

    # ------------------------------------------------------ accumulation
    def __add__(self, other) -> "PlaneTelemetry":
        if isinstance(other, int) and other == 0:   # sum() start value
            return self
        if not isinstance(other, PlaneTelemetry):
            return NotImplemented
        return PlaneTelemetry(**{k: getattr(self, k) + getattr(other, k)
                                 for k in _KEYS})

    __radd__ = __add__

    # ------------------------------------------------- mapping protocol
    def keys(self):
        return _KEYS

    def __getitem__(self, key: str) -> np.ndarray:
        if key not in self.keys():
            raise KeyError(key)
        return getattr(self, key)

    def __contains__(self, key) -> bool:
        return key in self.keys()

    def __iter__(self):
        return iter(self.keys())

    def items(self):
        return tuple((k, getattr(self, k)) for k in self.keys())

    def get(self, key, default=None):
        return getattr(self, key) if key in self.keys() else default

    def as_dict(self) -> dict:
        return dict(self.items())

    def __eq__(self, other) -> bool:
        if not isinstance(other, PlaneTelemetry):
            return NotImplemented
        return all(np.array_equal(getattr(self, k), getattr(other, k))
                   for k in _KEYS)

    def __repr__(self) -> str:
        return (f"PlaneTelemetry(S={self.n_shards}, L={self.n_lines}, "
                f"served={self.served}, deferred={self.deferred_total}, "
                f"writes={int(self.line_whits.sum())})")
