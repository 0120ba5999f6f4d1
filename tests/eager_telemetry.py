"""Reference for the lazy telemetry record: the eager copy
``DevicePlane._telemetry`` made before its records turned lazy — every
counter leaf to the host at once, the slot counters remapped to LINE
ids through the home directory as the verb left it.

``spy`` stands in for ``DevicePlane._telemetry`` and keeps each verb's
raw leaves (host copies) beside the lazy record it returns, so a test
compares the two on the very same op trace.
"""

import jax
import numpy as np

from repro.core.rounds.engine import EVENT_KEYS
from repro.obs import PlaneTelemetry


def eager_telemetry(raw, home, *, n_lines, n_shards, sharded):
    """The pre-lazy remap, verbatim in effect: ``raw`` the loop's
    counter dict on the host, ``home`` the directory (or None)."""
    c = {k: np.asarray(v) for k, v in raw.items()}
    hits = c.pop("slot_hits")
    whits = c.pop("slot_whits")
    c.update(zip(EVENT_KEYS, c.pop("events").sum(axis=0)))
    if sharded:
        l, s = n_lines, n_shards
        perm = (np.asarray(home) if home is not None
                else np.arange(l, dtype=np.int64))
        pos = (perm % s) * (l // s) + perm // s
        hits, whits = hits[pos], whits[pos]
    c["line_hits"] = hits
    c["line_whits"] = whits
    return PlaneTelemetry.from_counters(c)


def spy(lazy_telemetry):
    """``(seen, wrapped)``: ``wrapped(plane, tele)`` appends ``(raw,
    home, geometry)`` host copies to ``seen``, then returns what
    ``lazy_telemetry`` returns."""
    seen = []

    def wrapped(plane, tele):
        home = plane.state.get("home")
        seen.append((jax.device_get(dict(tele)),
                     None if home is None else np.asarray(home),
                     {"n_lines": plane.n_lines,
                      "n_shards": plane.n_shards,
                      "sharded": plane.sharded}))
        return lazy_telemetry(plane, tele)

    return seen, wrapped


def eager_of(seen_item, home=...):
    """The eager record of one spied verb (``home`` overrides the
    directory it saw)."""
    raw, seen_home, geo = seen_item
    return eager_telemetry(raw, seen_home if home is ... else home, **geo)


def assert_same(lazy, eager):
    """Field for field: equal values, int64 numpy arrays on access."""
    assert isinstance(lazy, PlaneTelemetry)
    for k in eager.keys():
        v = lazy[k]
        assert isinstance(v, np.ndarray) and v.dtype == np.int64, k
        np.testing.assert_array_equal(v, eager[k], err_msg=k)
    assert lazy == eager
    assert (lazy.served, lazy.deferred_total, lazy.n_lines,
            lazy.n_shards) == (eager.served, eager.deferred_total,
                               eager.n_lines, eager.n_shards)
