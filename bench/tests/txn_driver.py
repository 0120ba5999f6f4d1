"""A test-only driver: 2PL no-wait transaction batches through
``DevicePlane.txn``, checked against the host ``TxnEngine``.

The configuration is ``apps/workloads.TxnBatchConfig`` at its defaults
(Fig. 11-shaped NewOrder, Payment and OrderStatus shapes over 64
Zipf-skewed GCLs of 8 tuples, 16 txns a batch, 4 nodes).  The check
replays every batch's effective read and write sets on the host engine
in the device's serial order (``exec_step``, then slot), as
``tests/test_txn_device.py`` does, and compares each commit decision
and, after the window, every GCL's lanes read back through the plane.
It implements the contract of :mod:`bench.drivers` and nothing more.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import numpy as np

from bench import roofline
from repro.apps.workloads import TxnBatchConfig, device_txn_batches

CONFIG = dict(dataclasses.asdict(TxnBatchConfig()), algo="2pl")


def _batch_config(config: dict) -> TxnBatchConfig:
    fields = {f.name for f in dataclasses.fields(TxnBatchConfig)}
    return TxnBatchConfig(**{k: v for k, v in config.items()
                             if k in fields})


def open_plane(config: dict, seed: int, devices: list):
    import jax
    from repro.apps.txn_device import DeviceTxnConfig, DeviceTxnEngine
    from repro.core import rounds as rp
    from repro.core.rounds.txn import txn_payload_width

    cfg = _batch_config(config)
    state = jax.device_put(rp.make_state(
        cfg.n_nodes, cfg.n_gcls,
        payload_width=txn_payload_width(cfg.tuples_per_gcl)), devices[0])
    plane = rp.DevicePlane.open(state, n_nodes=cfg.n_nodes)
    return DeviceTxnEngine(plane, DeviceTxnConfig(
        algo=config["algo"], tuples_per_gcl=cfg.tuples_per_gcl,
        max_group_lines=cfg.max_group_lines))


class _Batches:
    def __init__(self, config: dict, seed: int):
        self.cfg = dataclasses.replace(_batch_config(config), iters=1)
        self.r = self.cfg.batch
        self.seed = int(seed) % (1 << 64)
        self.count = 0

    def next(self):
        txns, node, ts = device_txn_batches(
            self.cfg, seed=[self.seed, self.count])[0]
        ts = ts + self.count * self.r        # unique across batches
        self.count += 1
        return txns, node, ts


def traffic(config: dict, mix: dict, seed: int) -> _Batches:
    return _Batches(config, seed)


def serve(engine, batch):
    txns, node, ts = batch
    res, effective = engine.run_batch(node, txns, ts=ts)
    return SimpleNamespace(res=res, effective=effective, rounds=res.rounds,
                           telemetry=res.telemetry)


def record(batch, result):
    txns, node, ts = batch
    if result is None:
        return SimpleNamespace(ops=0, rounds=0, failed=True)
    res = result.res
    return SimpleNamespace(
        ops=int(res.decision.sum()), rounds=int(res.rounds), failed=False,
        node=node, ts=ts, effective=result.effective,
        decision=np.asarray(res.decision),
        exec_step=np.asarray(res.exec_step))


def check(config: dict, mix: dict, seed: int, records: list, engine):
    from repro.apps.txn import TxnConfig, TxnEngine
    from repro.apps.txn_device import host_record_lanes
    from repro.core import ClusterConfig, SELCCLayer

    cfg = _batch_config(config)
    # one memory node, so the host latches GCLs in the device's order
    layer = SELCCLayer(ClusterConfig(n_compute=cfg.n_nodes, n_memory=1,
                                     threads_per_node=4))
    hosts = [TxnEngine(layer, nd, TxnConfig(
        algo=config["algo"], tuples_per_gcl=cfg.tuples_per_gcl),
        cfg.n_gcls * cfg.tuples_per_gcl) for nd in layer.nodes]

    def host_run(host, eff_r, eff_w, ts):
        out = {}

        def one():
            out["ok"] = yield from host.run(eff_r, eff_w, ts=ts)
        layer.env.run_until_complete([layer.env.process(one())])
        return out["ok"]

    bad = compared = 0
    for b in records:
        if b.failed:
            continue
        for i in sorted(range(len(b.effective)),
                        key=lambda i: (int(b.exec_step[i]), i)):
            eff_r, eff_w = b.effective[i]
            ok = host_run(hosts[int(b.node[i])], eff_r, eff_w,
                          int(b.ts[i]))
            bad += ok != bool(b.decision[i])
            compared += 1
    gcls = hosts[0].gcls
    want = np.stack([host_record_lanes(layer.heap.load(gcls[g]), g,
                                       cfg.tuples_per_gcl)
                     for g in range(cfg.n_gcls)])
    got = engine.final_image()
    checks = {"decision_mismatches": {"value": int(bad), "limit": 0},
              "image_mismatches": {
                  "value": int((got != want).any(axis=1).sum()),
                  "limit": 0}}
    return checks, compared


def least_bytes(config: dict, records: list) -> int:
    """Each committed txn's distinct GCLs, each a latch word and a line
    read and written once."""
    from repro.core.rounds.txn import txn_payload_width
    cfg = _batch_config(config)
    width = txn_payload_width(cfg.tuples_per_gcl)
    lines = sum(len({t // cfg.tuples_per_gcl for t in r_ + w_})
                for b in records if not b.failed
                for (r_, w_), ok in zip(b.effective, b.decision) if ok)
    return roofline.least_bytes(lines, width)
