"""Drive the SELCC device plane once on a TPU, at deployment size.

    python chip_smoke.py                # one chip: all phases below
    python chip_smoke.py --four-chips   # four chips: the sharded plane only

Deployment: N = 8 compute nodes sharing L = 2^18 global cache lines of
1 KB each (W = 256 int32 payload lanes: YCSB's record of 10 fields x
100 B, Cooper et al., SoCC 2010), YCSB-A traffic (50/50 read/update,
Zipf theta = 0.99), R = 256 op slots per batch.  The memory image and
the eight node caches live on the device: (N + 1) * L * W * 4 B = 2.4 GB.

One chip, in one process:

* ``ycsb`` — the same seeded YCSB-A trace through ``DevicePlane.ops``
  with ``backend="ref"`` and with ``backend="pallas"`` (the compiled
  ``latch_ops`` and ``gcl_fetch`` kernels), in write-through and in
  write-back.  The two backends must return bit-identical versions and
  payloads, and each run must agree with a plain host reference: per
  line the write versions run 1..k with no gap or duplicate, every read
  returns a (version, bytes) pair some write produced, every written
  line reads back the bytes of its last write, and ``plane.check()``
  passes;
* ``btree`` — a ``DeviceBTree`` load and lookup, checked against a dict;
* ``txn`` — one 2PL batch through ``plane.txn``, checked against the
  host ``TxnEngine`` replaying the device's serial order (decisions and
  the final memory image).

``--four-chips`` runs the YCSB-A trace on the plane sharded over
``jax.devices()[:4]`` (``make_sharded_state``, same N, L and W) and on
a flat plane on device 0, and requires bit-identical versions and
payloads.  It runs no other phase.

Each phase prints one JSON line (wall times after the device finished,
first-batch time including compilation, the phase's lowering and
compile time, rounds per batch, peak device memory).  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or when any check
fails, the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from collections import defaultdict
from dataclasses import asdict, dataclass
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


@dataclass(frozen=True)
class Deployment:
    n_nodes: int = 8
    n_lines: int = 1 << 18
    width: int = 256
    r_slots: int = 256
    batches: int = 32
    read_ratio: float = 0.5
    zipf_theta: float = 0.99
    max_rounds: int = 256
    seed: int = 0
    # B-tree phase: keys loaded, lines, fanout
    bt_keys: int = 4096
    bt_lines: int = 8192
    bt_fanout: int = 16


FULL = Deployment()


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# seconds this process spent lowering and compiling (or loading a compile
# from the cache), summed from JAX's own events; tracing is left out
# because its events nest
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
compile_s = 0.0


def _on_duration(event: str, secs: float, **_) -> None:
    global compile_s
    if event in COMPILE_EVENTS:
        compile_s += secs


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------ YCSB trace

def ycsb_trace(d: Deployment):
    from repro.apps.workloads import DeviceRoundsConfig, \
        device_rounds_batches
    cfg = DeviceRoundsConfig(n_nodes=d.n_nodes, n_lines=d.n_lines,
                             r_slots=d.r_slots, read_ratio=d.read_ratio,
                             zipf_theta=d.zipf_theta, iters=d.batches,
                             payload_width=d.width)
    return device_rounds_batches(cfg, seed=d.seed)


def run_trace(plane, batches):
    """Every batch through ``plane.ops``; returns the per-batch
    (versions, payloads), rounds and wall times (``ops`` materializes
    its results on the host, so each time ends after the device)."""
    out, rounds, times = [], [], []
    for node, line, isw, wdata in batches:
        t0 = time.perf_counter()
        res = plane.ops(node, line, isw, wdata)
        times.append(time.perf_counter() - t0)
        out.append((res.version, res.data))
        rounds.append(res.rounds)
    return out, rounds, times


def host_reference(batches, results):
    """Check the replies against the plain semantics of the trace and
    return each written line's (last version, last bytes)."""
    produced = {}                       # (line, version) -> bytes
    versions = defaultdict(list)
    for (_, line, isw, wdata), (ver, _) in zip(batches, results):
        for i in np.flatnonzero(isw):
            key = (int(line[i]), int(ver[i]))
            assert key not in produced, f"version {key} written twice"
            produced[key] = wdata[i]
            versions[key[0]].append(key[1])
    for ln, vs in versions.items():
        assert sorted(vs) == list(range(1, len(vs) + 1)), \
            f"line {ln}: write versions {sorted(vs)} are not 1..k"
    for (_, line, isw, _), (ver, data) in zip(batches, results):
        for i in np.flatnonzero(isw == 0):
            v = int(ver[i])
            if v == 0:
                assert not data[i].any(), f"line {line[i]}: v0 not zero"
            else:
                want = produced.get((int(line[i]), v))
                assert want is not None, \
                    f"line {line[i]}: read version {v} was never written"
                assert np.array_equal(want, data[i]), \
                    f"line {line[i]} v{v}: read bytes differ from write"
    return {ln: (max(vs), produced[(ln, max(vs))])
            for ln, vs in versions.items()}


def read_back(plane, last, d: Deployment):
    """Read every written line from node 0 and compare it with its last
    write; returns the (versions, payloads) read."""
    lines = np.asarray(sorted(last), np.int32)
    pad = (-lines.size) % d.r_slots
    lines = np.concatenate([lines, np.full(pad, -1, np.int32)])
    got = []
    for chunk in lines.reshape(-1, d.r_slots):
        zeros = np.zeros(d.r_slots, np.int32)
        res = plane.ops(zeros, chunk, zeros,
                        np.zeros((d.r_slots, d.width), np.int32))
        for i, ln in enumerate(chunk[chunk >= 0]):
            k, data = last[int(ln)]
            assert int(res.version[i]) == k, \
                f"line {ln}: read back v{res.version[i]}, last write v{k}"
            assert np.array_equal(res.data[i], data), \
                f"line {ln}: read back bytes differ from the last write"
        got.append((res.version, res.data))
    return got


def same_replies(a, b, what: str) -> None:
    assert len(a) == len(b), what
    for i, ((va, da), (vb, db)) in enumerate(zip(a, b)):
        assert np.array_equal(va, vb), f"{what}: versions differ, batch {i}"
        assert np.array_equal(da, db), f"{what}: payloads differ, batch {i}"


def drive(plane, batches, d: Deployment, label: str, **fields):
    """One plane through the trace, the host reference, read-back and
    invariants; logs the phase and returns (replies, read-back)."""
    c0, t0 = compile_s, time.perf_counter()
    results, rounds, times = run_trace(plane, batches)
    t_trace = time.perf_counter() - t0
    last = host_reference(batches, results)
    readback = read_back(plane, last, d)
    t1 = time.perf_counter()
    plane.check()
    t_check = time.perf_counter() - t1
    steady = sorted(times[1:]) or times
    log(label, **fields, batches=len(batches), trace_s=t_trace,
        first_batch_s=times[0], median_batch_s=steady[len(steady) // 2],
        ops_per_s=d.r_slots / steady[len(steady) // 2],
        rounds_per_batch=float(np.mean(rounds)), max_rounds=max(rounds),
        lines_written=len(last), check_s=t_check,
        compile_s=compile_s - c0, peak_bytes_in_use=peak_bytes())
    return results, readback


def ycsb_phase(d: Deployment = FULL) -> None:
    from repro.core import rounds as rp
    batches = ycsb_trace(d)
    for write_back in (False, True):
        mode = "write-back" if write_back else "write-through"
        replies = {}
        for backend in ("ref", "pallas"):
            plane = rp.DevicePlane.open(
                rp.make_state(d.n_nodes, d.n_lines, write_back=write_back,
                              payload_width=d.width),
                n_nodes=d.n_nodes, backend=backend,
                max_rounds=d.max_rounds)
            replies[backend] = drive(plane, batches, d, "ycsb",
                                     mode=mode, backend=backend)
            del plane
        for part, name in ((0, "trace"), (1, "read-back")):
            same_replies(replies["ref"][part], replies["pallas"][part],
                         f"{mode} {name}: ref vs pallas")
        log("ycsb", mode=mode, ref_vs_pallas="bit-identical")


# ---------------------------------------------------------------- B-tree

def btree_phase(d: Deployment = FULL) -> None:
    from repro.index import DeviceBTree
    c0, t0 = compile_s, time.perf_counter()
    rng = np.random.default_rng(d.seed + 1)
    keys = rng.choice(1 << 30, d.bt_keys + d.r_slots, replace=False) \
        .astype(np.int32)
    absent, keys = keys[:d.r_slots], keys[d.r_slots:]
    vals = rng.integers(1, 1 << 30, keys.size).astype(np.int32)
    want = dict(zip(keys.tolist(), vals.tolist()))
    tree = DeviceBTree.create(n_nodes=d.n_nodes, n_lines=d.bt_lines,
                              fanout=d.bt_fanout, backend="pallas",
                              max_rounds=d.max_rounds)
    for b, s in enumerate(range(0, keys.size, d.r_slots)):
        tree.insert_batch(keys[s:s + d.r_slots], vals[s:s + d.r_slots],
                          node=b % d.n_nodes)
    t_load = time.perf_counter() - t0
    for b, s in enumerate(range(0, keys.size, d.r_slots)):
        got, found = tree.lookup_batch(keys[s:s + d.r_slots],
                                       node=(b + 1) % d.n_nodes)
        assert found.all(), "a loaded key was not found"
        assert np.array_equal(got, vals[s:s + d.r_slots]), \
            "a lookup returned another value than the dict"
    _, found = tree.lookup_batch(absent)
    assert not found.any(), "an absent key was found"
    assert dict(tree.items()) == want, "tree image differs from the dict"
    tree.check_invariants()
    log("btree", keys=int(keys.size), height=tree.height,
        splits=tree.stats["splits"], load_s=t_load,
        total_s=time.perf_counter() - t0, compile_s=compile_s - c0,
        peak_bytes_in_use=peak_bytes())


# ------------------------------------------------------------ 2PL batch

def txn_phase(d: Deployment = FULL) -> None:
    from repro.apps.txn import TxnConfig, TxnEngine
    from repro.apps.txn_device import (DeviceTxnConfig, DeviceTxnEngine,
                                       host_record_lanes)
    from repro.apps.workloads import TxnBatchConfig, device_txn_batches
    from repro.core import ClusterConfig, SELCCLayer
    from repro.core import rounds as rp
    from repro.core.rounds.txn import txn_payload_width

    c0, t0 = compile_s, time.perf_counter()
    cfg = TxnBatchConfig(n_gcls=64, tuples_per_gcl=8, batch=64, iters=1,
                         max_group_lines=4, zipf_theta=0.9,
                         n_nodes=d.n_nodes)
    plane = rp.DevicePlane.open(
        rp.make_state(cfg.n_nodes, cfg.n_gcls,
                      payload_width=txn_payload_width(cfg.tuples_per_gcl)),
        n_nodes=cfg.n_nodes, backend="pallas")
    dev = DeviceTxnEngine(plane, DeviceTxnConfig(
        algo="2pl", tuples_per_gcl=cfg.tuples_per_gcl,
        max_group_lines=cfg.max_group_lines))
    txns, node, ts = device_txn_batches(cfg, seed=d.seed)[0]
    res, effective = dev.run_batch(node, txns, ts=ts)
    # the oracle: the host engine runs the device's effective txns one at
    # a time in its serial order (exec_step, slot); one memory node makes
    # the host's latch order the device's ascending line order
    layer = SELCCLayer(ClusterConfig(n_compute=cfg.n_nodes, n_memory=1,
                                     threads_per_node=4))
    engines = [TxnEngine(layer, nd, TxnConfig(
        algo="2pl", tuples_per_gcl=cfg.tuples_per_gcl),
        cfg.n_gcls * cfg.tuples_per_gcl) for nd in layer.nodes]
    for i in sorted(range(len(txns)),
                    key=lambda i: (int(res.exec_step[i]), i)):
        out = {}

        def one(i=i, out=out):
            eff_r, eff_w = effective[i]
            out["ok"] = yield from engines[int(node[i])].run(
                eff_r, eff_w, ts=int(ts[i]))
        layer.env.run_until_complete([layer.env.process(one())])
        assert bool(res.decision[i]) == out["ok"], f"txn {i} decision"
    image = np.stack([host_record_lanes(layer.heap.load(engines[0].gcls[g]),
                                        g, cfg.tuples_per_gcl)
                      for g in range(cfg.n_gcls)])
    assert np.array_equal(dev.final_image(), image), \
        "device memory image differs from the host engine's"
    plane.check()
    log("txn", algo="2pl", txns=len(txns), commits=int(res.decision.sum()),
        retries=int(res.retries.sum()), rounds=res.rounds,
        total_s=time.perf_counter() - t0, compile_s=compile_s - c0,
        peak_bytes_in_use=peak_bytes())


# -------------------------------------------------------------- 4 chips

def sharded_phase(devices, d: Deployment = FULL) -> None:
    import jax
    from repro.core import rounds as rp
    mesh = jax.make_mesh((len(devices),), ("shards",), devices=devices)
    batches = ycsb_trace(d)
    for write_back in (False, True):
        mode = "write-back" if write_back else "write-through"
        flat = rp.DevicePlane.open(
            rp.make_state(d.n_nodes, d.n_lines, write_back=write_back,
                          payload_width=d.width),
            n_nodes=d.n_nodes, backend="pallas", max_rounds=d.max_rounds)
        want = drive(flat, batches, d, "sharded", mode=mode, geometry="flat")
        del flat
        shd = rp.DevicePlane.open(
            rp.make_sharded_state(d.n_nodes, d.n_lines, mesh,
                                  write_back=write_back,
                                  payload_width=d.width),
            mesh, n_nodes=d.n_nodes, backend="pallas",
            max_rounds=d.max_rounds)
        got = drive(shd, batches, d, "sharded", mode=mode,
                    geometry=f"sharded x{len(devices)}")
        del shd
        for part, name in ((0, "trace"), (1, "read-back")):
            same_replies(want[part], got[part],
                         f"{mode} {name}: flat vs sharded")
        log("sharded", mode=mode, flat_vs_sharded="bit-identical")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded plane over four chips "
                         "against a flat plane on device 0")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log("start", device=device, compile_cache=cache,
        deployment=asdict(FULL))
    t0 = time.perf_counter()
    if args.four_chips:
        sharded_phase(devices[:4])
    else:
        ycsb_phase()
        btree_phase()
        txn_phase()
    log("done", total_s=time.perf_counter() - t0, compile_s=compile_s)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
