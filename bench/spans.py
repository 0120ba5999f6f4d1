"""Where a ``DevicePlane`` verb's time goes, from a profiler trace.

The plane brackets every verb in a ``plane.<verb>`` annotation with
fixed children (``plane.pad``, ``plane.dispatch``, ``plane.sync``,
``plane.telemetry``, ``plane.results``), and the round body's phases
run under ``jax.named_scope``s (``round/latch``, ``round/boundary``,
``route/exchange``, ...).  :func:`reduce` takes the plain trace form of
:func:`bench.trace.extract` and, per traced ``bench.ops`` call, gives
each child span's wall time, the device busy time inside it, and what
is left of the call outside every child; over all calls, the device's
self time by scope.  The device operations are named by HLO
instruction; :func:`scopes_from_hlo` maps each name to its scope from
the compiled program's text, where every instruction carries its
``op_name`` metadata (a fusion carries its root's).

Both functions are pure, so they are tested on a small trace recorded
on a TPU v5e.  ``python3 -m bench.spans --workload <cell> --seed <n>
--seconds <s> [--out DIR]`` runs one traced window of a cell through
:func:`bench.harness.run` on the chip and writes the reduction, with
the verb's compiled HLO (from the cell driver's ``lower_verb``; a driver
without it leaves the device time unscoped), to
``DIR/spans_<cell>_<seed>.json`` (``DIR`` defaults to ``spans_out/`` in
the checkout).
"""

from __future__ import annotations

import re

from . import trace as trace_mod

PLANE = "plane."
CALL = trace_mod.CALL
UNSCOPED = "(unscoped)"
# the scopes the program names: round-body phases, sharded routing
SCOPE = re.compile(r"(?:round|route)/[a-z_]+")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def scope_of(op_name: str) -> str:
    """The innermost program scope in an ``op_name``, or ``UNSCOPED``."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else UNSCOPED


def scopes_from_hlo(text: str) -> dict:
    """``{instruction name: scope}`` for every instruction of an HLO
    module's text whose metadata names a program scope."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            s = scope_of(m.group(2))
            if s != UNSCOPED:
                out[m.group(1)] = s
    return out


def _children(host: list, call: list) -> list:
    """The ``plane.*`` events inside one ``bench.ops`` call except the
    verb's own bracket, in start order."""
    lo, hi = call[1], call[1] + call[2]
    kids = [h for h in host if h[0].startswith(PLANE)
            and lo <= h[1] and h[1] + h[2] <= hi]
    verbs = [h for h in kids if not any(
        k is not h and k[1] <= h[1] and h[1] + h[2] <= k[1] + k[2]
        for k in kids)]
    return sorted((h for h in kids if not any(h is v for v in verbs)),
                  key=lambda h: h[1])


def reduce(trace: dict, scopes: dict | None = None) -> dict:
    """Per traced call and as a mean over calls: each child span's wall
    time and the device busy time inside it (mean over chips), and the
    call's time outside every child; the device's self time by scope
    (``scopes`` from :func:`scopes_from_hlo`; without it every
    operation is ``UNSCOPED``).  Times in seconds.

    Returns ``{}`` when the trace holds no call or no device.  Else
    ``calls`` (per call: ``wall_s``, ``busy_s``, ``spans`` {name:
    {``wall_s``, ``busy_s``}}, ``unspanned`` {``wall_s``,
    ``busy_s``}), ``spans`` (name: mean ``wall_s``, ``busy_s`` and
    ``idle_s`` = wall less busy, per call), ``unspanned`` (the same for
    the time outside every child), ``order`` (the children's names in
    the first call), ``by_scope`` ([scope, self s] by size, mean over
    chips) and ``busy_s`` (mean over chips)."""
    calls = sorted([h for h in trace["host"] if h[0] == CALL],
                   key=lambda h: h[1])
    if not calls or not trace["devices"]:
        return {}
    lo = calls[0][1]
    hi = max(h[1] + h[2] for h in calls)
    chips = sorted(trace["devices"])
    n = len(chips)
    busy_iv = {}
    by_scope: dict = {}
    scopes = scopes or {}
    for c in chips:
        evs = [e for e in trace["devices"][c]
               if e[1] + e[2] > lo and e[1] < hi]
        busy_iv[c] = trace_mod._union(trace_mod._clip(
            [[e[1], e[1] + e[2]] for e in evs], lo, hi))
        self_t, _ = trace_mod._nesting(evs)
        for e, t in zip(evs, self_t):
            s = scopes.get(e[0], UNSCOPED)
            by_scope[s] = by_scope.get(s, 0.0) + t / 1e9 / n

    def busy(s, e):
        return sum(trace_mod._length(trace_mod._clip(busy_iv[c], s, e))
                   for c in chips) / n / 1e9

    per_call = []
    for call in calls:
        kids = _children(trace["host"], call)
        spans: dict = {}
        for k in kids:
            d = spans.setdefault(k[0], {"wall_s": 0.0, "busy_s": 0.0})
            d["wall_s"] += k[2] / 1e9
            d["busy_s"] += busy(k[1], k[1] + k[2])
        wall = call[2] / 1e9
        b = busy(call[1], call[1] + call[2])
        per_call.append({
            "wall_s": wall, "busy_s": b, "spans": spans,
            "order": [k[0] for k in kids],
            "unspanned": {
                "wall_s": wall - sum(d["wall_s"] for d in spans.values()),
                "busy_s": b - sum(d["busy_s"] for d in spans.values())}})
    names = sorted({k for pc in per_call for k in pc["spans"]})
    m = len(per_call)

    def mean(key_fn):
        return sum(key_fn(pc) for pc in per_call) / m
    spans = {}
    for k in names:
        w = mean(lambda pc: pc["spans"].get(k, {}).get("wall_s", 0.0))
        b = mean(lambda pc: pc["spans"].get(k, {}).get("busy_s", 0.0))
        spans[k] = {"wall_s": w, "busy_s": b, "idle_s": w - b}
    uw = mean(lambda pc: pc["unspanned"]["wall_s"])
    ub = mean(lambda pc: pc["unspanned"]["busy_s"])
    return {
        "calls": per_call,
        "spans": spans,
        "unspanned": {"wall_s": uw, "busy_s": ub, "idle_s": uw - ub},
        "order": per_call[0]["order"],
        "by_scope": sorted(([k, v] for k, v in by_scope.items()),
                           key=lambda kv: -kv[1]),
        "busy_s": sum(trace_mod._length(busy_iv[c])
                      for c in chips) / n / 1e9,
    }


def main(argv=None) -> int:
    """One traced run of a cell (``bench.run`` with ``--trace 1``) that
    keeps the plain trace, reduces it here and writes the reduction,
    the scope map and the run's result line as JSON."""
    import argparse
    import gzip
    import json
    import sys
    from pathlib import Path

    from . import harness
    from . import run as run_mod

    ap = argparse.ArgumentParser(description=main.__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=str(Path(run_mod.ROOT) / "spans_out"),
                    help="directory for the output files")
    ap.add_argument("--keep-trace", action="store_true",
                    help="also write the plain trace form (gzipped)")
    args = ap.parse_args(argv)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    stem = out_dir / f"spans_{args.workload}_{args.seed}"
    kept: dict = {}
    reduce_all = trace_mod.reduce
    run_cell = harness.run

    def keep_reduce(plain, *a, **k):
        kept["plain"] = plain
        return reduce_all(plain, *a, **k)

    def traced_run(cell, **kw):
        kept["cell"] = cell
        out = run_cell(cell, **kw)
        kept["out"] = out
        return out

    trace_mod.reduce = keep_reduce
    harness.run = traced_run
    try:
        rc = run_mod.main(["--workload", args.workload, "--seed",
                           str(args.seed), "--seconds", str(args.seconds),
                           "--trace", "1"])
    finally:
        trace_mod.reduce = reduce_all
        harness.run = run_cell
    if rc or "plain" not in kept:
        return rc or 1
    cell = kept["cell"]
    lower_verb = getattr(cell.driver, "lower_verb", None)
    if lower_verb is None:
        print(f"bench.spans: the driver {cell.driver.__name__} has no "
              "lower_verb; device time is left unscoped",
              file=sys.stderr, flush=True)
        text = None
    else:
        text = lower_verb(cell.config,
                          harness.chips_for(cell.chips)).compile().as_text()
    scopes = scopes_from_hlo(text or "")
    red = reduce(kept["plain"], scopes)
    red.pop("calls", None)
    doc = {"workload": args.workload, "seed": args.seed,
           "spans": red, "n_scoped_instructions": len(scopes),
           "result": kept["out"]}
    Path(str(stem) + ".json").write_text(json.dumps(doc, indent=1))
    if text is not None:
        with gzip.open(str(stem) + ".hlo.txt.gz", "wt") as f:
            f.write(text)
    if args.keep_trace:
        with gzip.open(str(stem) + ".trace.json.gz", "wt") as f:
            json.dump(kept["plain"], f)
    print(json.dumps({"spans": {k: v for k, v in red.items()
                                if k != "calls"}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
