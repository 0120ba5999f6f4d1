"""From a profiler trace to the numbers the per-layer metrics read.

Two steps.  :func:`extract` reads the ``.xplane.pb`` that
``jax.profiler`` wrote into a small plain form: per chip the device's
operations (``XLA Ops`` line) and, from the host, the harness's own
``bench.*`` annotations and the events of the thread that made them,
all as ``[name, start_ns, duration_ns]``.  :func:`reduce` turns that
form into busy time, the traced window, per-call device time, exposed
collective time, the top device operations and the longest idle gaps,
labelled by what the host was doing.  The reduction is a pure function
of the plain form, so it is tested on a small recorded trace.
"""

from __future__ import annotations

import re

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
ANNOTATION = "bench."
CALL = "bench.ops"
# HLO's collective instructions (a psum lowers to all-reduce)
COLLECTIVE = re.compile(
    r"all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute")


def op_name(text: str) -> str:
    """An operation's HLO instruction name: the trace names each one by
    its whole HLO text, ``%name = shape op(operands)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def extract(path: str) -> dict:
    """Plain form of one ``.xplane.pb``: ``{"devices": {id: [[name,
    start_ns, dur_ns], ...]}, "host": [[name, start_ns, dur_ns], ...]}``
    with device operations named by :func:`op_name`.  ``host`` keeps
    every event of the threads that carry a ``bench.*`` annotation."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[m.group(1)] = [
                        [op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events]
                if any(e[0].startswith(ANNOTATION) for e in evs):
                    host.extend(e for e in evs if e[2] > 0)
    return {"devices": devices, "host": host}


def _union(iv: list) -> list:
    """Union of [start, end) intervals, sorted."""
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in iv if e > lo and s < hi]


def _length(iv: list) -> float:
    return sum(e - s for s, e in iv)


def _minus(a: list, b: list) -> list:
    """Parts of the union ``a`` not covered by the union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _nesting(evs: list):
    """Self time of each event, its duration less that of the events
    nested in it (a ``while`` op spans the ops of its body), and whether
    it holds any."""
    order = sorted(range(len(evs)),
                   key=lambda i: (evs[i][1], -evs[i][2]))
    self_t = [e[2] for e in evs]
    parent = [False] * len(evs)
    stack: list = []
    for i in order:
        s, e = evs[i][1], evs[i][1] + evs[i][2]
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            top = stack[-1]
            self_t[top] -= min(e, evs[top][1] + evs[top][2]) - s
            parent[top] = True
        stack.append(i)
    return self_t, parent


def _label(host: list, t: float) -> str:
    """What the host was doing at ``t``: the innermost ``bench.*``
    annotation and, below it, the innermost other event."""
    live = [h for h in host if h[1] <= t < h[1] + h[2]]
    ours = [h for h in live if h[0].startswith(ANNOTATION)]
    other = [h for h in live if not h[0].startswith(ANNOTATION)]
    name = min(ours, key=lambda h: h[2])[0] if ours else "outside"
    if other:
        name += " > " + min(other, key=lambda h: h[2])[0]
    return name


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle time over the traced window (first ``bench.ops``
    start to last ``bench.ops`` end), per chip and as a mean over chips.

    Returns ``window_s``, ``busy_s`` (mean over chips), ``busy_by_chip``,
    ``calls`` (per ``bench.ops`` call: its wall time and the device busy
    time inside it, mean over chips), ``collective_s`` and
    ``collective_exposed_s`` by chip (collective operations, and the
    part of them during which no other operation ran on that chip),
    ``device_ops`` (the ``top`` operations by self time, mean over
    chips) and
    ``idle_gaps`` (the ``top`` longest gaps on any chip, each labelled
    by :func:`_label`)."""
    calls = sorted([h for h in trace["host"] if h[0] == CALL],
                   key=lambda h: h[1])
    if not calls or not trace["devices"]:
        return {}
    lo = calls[0][1]
    hi = max(h[1] + h[2] for h in calls)
    chips = sorted(trace["devices"])
    busy, coll, exposed, per_op, gaps = {}, {}, {}, {}, []
    call_busy = [0.0] * len(calls)
    for c in chips:
        evs = [e for e in trace["devices"][c]
               if e[1] + e[2] > lo and e[1] < hi]
        ops = _union(_clip([[e[1], e[1] + e[2]] for e in evs], lo, hi))
        busy[c] = _length(ops) / 1e9
        for i, h in enumerate(calls):
            call_busy[i] += _length(_clip(ops, h[1], h[1] + h[2])) / 1e9
        self_t, parent = _nesting(evs)
        # a collective is exposed where no other leaf operation runs: the
        # loop that holds it does not count as running beside it
        is_coll = [bool(COLLECTIVE.search(e[0])) for e in evs]
        cu = _union(_clip([[e[1], e[1] + e[2]]
                           for e, k in zip(evs, is_coll) if k], lo, hi))
        nu = _union(_clip([[e[1], e[1] + e[2]]
                           for e, k, p in zip(evs, is_coll, parent)
                           if not (k or p)], lo, hi))
        coll[c] = _length(cu) / 1e9
        exposed[c] = _length(_minus(cu, nu)) / 1e9
        for e, t in zip(evs, self_t):
            per_op[e[0]] = per_op.get(e[0], 0.0) + t / 1e9 / len(chips)
        edges = [lo] + [x for iv in ops for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) / 2))
    gaps.sort(reverse=True)
    n = len(chips)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy.values()) / n,
        "busy_by_chip": busy,
        "calls": [{"wall_s": h[2] / 1e9, "busy_s": b / n}
                  for h, b in zip(calls, call_busy)],
        "collective_s": coll,
        "collective_exposed_s": exposed,
        "device_ops": sorted(([k, v] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_label(trace["host"], mid), d / 1e9]
                      for d, mid in gaps[:top]],
        "n_chips": n,
    }
