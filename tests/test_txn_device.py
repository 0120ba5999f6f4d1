"""Differential: fused device transaction CC vs the host ``TxnEngine``.

The device loop (core/rounds/txn.py) serializes a whole batch by
(exec_step, slot): lock hold intervals per line are disjoint, so the
batch is serially equivalent to executing txns one at a time in that
order.  The oracle here IS that serial execution — the DES
``TxnEngine`` replaying the device's EFFECTIVE tuple sets sequentially
in device order, with the device's client timestamps injected
(``engine.run(..., ts=...)``) — and the tests demand bit-identical
commit/abort decisions AND final memory images (host ``GclHeap``
records rendered to lanes vs a protocol-fresh device read-back) for
both 2PL no-wait and TO, on the flat plane and (in a subprocess with 4
virtual devices) the mesh-sharded plane.
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.apps.txn import TxnConfig, TxnEngine
from repro.core import ClusterConfig, SELCCLayer

jax = pytest.importorskip("jax")

from repro.apps.txn_device import (DeviceTxnConfig, DeviceTxnEngine,
                                   encode_txns,
                                   host_record_lanes)        # noqa: E402
from repro.apps.workloads import (TxnBatchConfig,
                                  device_txn_batches)        # noqa: E402
from repro.core import rounds as rp                          # noqa: E402
from repro.core.rounds.engine import TRACE_COUNTS            # noqa: E402
from repro.core.rounds.txn import txn_payload_width          # noqa: E402

CFG = TxnBatchConfig(n_gcls=12, tuples_per_gcl=4, batch=8, iters=3,
                     max_group_lines=4, zipf_theta=0.9, n_nodes=3)


def _device_engine(algo, cfg=CFG):
    state = rp.make_state(
        cfg.n_nodes, cfg.n_gcls,
        payload_width=txn_payload_width(cfg.tuples_per_gcl))
    plane = rp.DevicePlane.open(state, n_nodes=cfg.n_nodes)
    dcfg = DeviceTxnConfig(algo=algo,
                           tuples_per_gcl=cfg.tuples_per_gcl,
                           max_group_lines=cfg.max_group_lines)
    return DeviceTxnEngine(plane, dcfg)


def _host_oracle(algo, cfg=CFG):
    # ONE memory node: the host engine latches GCLs in sorted-GAddr
    # (node_id, offset) order, the device in ascending line order; with
    # n_memory=1 the two canonical orders coincide, so TO's abort-time
    # partial-update leak lands in the SAME tuples on both planes
    # (with striping both orders are valid but differ, and the leaked
    # headers differ with them — decisions stay order-independent)
    layer = SELCCLayer(ClusterConfig(n_compute=cfg.n_nodes, n_memory=1,
                                     threads_per_node=4))
    engines = [TxnEngine(layer, nd,
                         TxnConfig(algo=algo,
                                   tuples_per_gcl=cfg.tuples_per_gcl),
                         cfg.n_gcls * cfg.tuples_per_gcl)
               for nd in layer.nodes]
    return layer, engines


def _host_run_one(layer, engine, eff_r, eff_w, ts):
    out = {}

    def one():
        out["ok"] = yield from engine.run(eff_r, eff_w, ts=ts)
    layer.env.run_until_complete([layer.env.process(one())])
    return out["ok"]


def _host_image(layer, engines, cfg=CFG):
    gcls = engines[0].gcls
    return np.stack([
        host_record_lanes(layer.heap.load(gcls[g]), g,
                          cfg.tuples_per_gcl)
        for g in range(cfg.n_gcls)])


def _differential(algo, seed=3):
    dev = _device_engine(algo)
    layer, engines = _host_oracle(algo)
    batches = device_txn_batches(CFG, seed=seed)
    total_retries = total_aborts = 0
    for txns, node, ts in batches:
        res, effective = dev.run_batch(node, txns, ts=ts)
        total_retries += int(res.retries.sum())
        total_aborts += int((~res.decision).sum())
        # replay sequentially in the device's serial order
        order = sorted(range(len(txns)),
                       key=lambda i: (int(res.exec_step[i]), i))
        host_dec = {}
        for i in order:
            eff_r, eff_w = effective[i]
            host_dec[i] = _host_run_one(layer, engines[int(node[i])],
                                        eff_r, eff_w, int(ts[i]))
        for i in range(len(txns)):
            assert bool(res.decision[i]) == host_dec[i], \
                (algo, i, int(ts[i]), effective[i])
    np.testing.assert_array_equal(dev.final_image(),
                                  _host_image(layer, engines))
    dev.plane.check()
    return total_retries, total_aborts


def test_differential_2pl_decisions_and_image():
    retries, aborts = _differential("2pl")
    assert aborts == 0          # no-wait retries in-loop until commit
    assert retries > 0          # ...and the workload does conflict
    # host-parity accounting: retries surface as nowait abort attempts
    # (satellite: TxnStats carries abort reasons + latency percentiles)


def test_differential_to_decisions_and_image():
    retries, aborts = _differential("to")
    assert aborts > 0           # shuffled client ts: TO really aborts


def test_txn_stats_reasons_and_percentiles():
    dev = _device_engine("to")
    txns, node, ts = device_txn_batches(CFG, seed=3)[0]
    res, _ = dev.run_batch(node, txns, ts=ts)
    s = dev.stats
    assert s.commits == int(res.decision.sum())
    assert s.abort_reasons.get("ts", 0) == int((~res.decision).sum())
    assert s.abort_reasons.get("nowait", 0) == int(res.retries.sum())
    assert s.latency.count == len(txns)
    assert 0 < s.p50 <= s.p99


def test_encode_txns_trim_policy():
    """No silent trim: a txn over more GCLs than the batch's static
    width is refused; one that fits comes back whole."""
    cfg = DeviceTxnConfig(tuples_per_gcl=4, max_group_lines=2)
    # 3 write gcls (0, 2, 5) + read gcl 7: four lines, two slots
    with pytest.raises(ValueError, match="max_group_lines"):
        encode_txns([([28, 1], [0, 8, 20, 1])], cfg)
    glines, rmask, wmask, eff = encode_txns([([4, 5], [9])], cfg)
    assert glines.tolist() == [[1, 2]]
    assert eff[0] == ([4, 5], [9])
    assert rmask[0, 0].tolist() == [1, 1, 0, 0]
    assert wmask[0, 1].tolist() == [0, 1, 0, 0]
    # a read of a tuple the txn also writes: wmask wins
    glines, rmask, wmask, eff = encode_txns([([1], [0, 1])], cfg)
    assert glines.tolist() == [[0, -1]]
    assert wmask[0, 0].tolist() == [1, 1, 0, 0] and rmask.sum() == 0


def test_txn_loop_compiles_once_per_shape():
    dev = _device_engine("2pl")
    key_of = lambda: {k: v for k, v in TRACE_COUNTS.items()
                      if k[0] == "txn" and k[1] == "2pl"}
    batches = device_txn_batches(CFG, seed=11)
    dev.run_batch(batches[0][1], batches[0][0], ts=batches[0][2])
    after_one = key_of()
    assert sum(after_one.values()) >= 1
    dev.run_batch(batches[1][1], batches[1][0], ts=batches[1][2])
    assert key_of() == after_one     # same shape: ZERO new traces


def test_flat_vs_sharded_txn_subprocess():
    """The mesh-sharded txn loop serializes EXACTLY like the flat one:
    same decisions, same serial order, same retries, same final memory
    image, both algos, on 4 virtual devices."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import jax, numpy as np
        from repro.apps.txn_device import DeviceTxnConfig, DeviceTxnEngine
        from repro.apps.workloads import TxnBatchConfig, device_txn_batches
        from repro.core import rounds as rp
        from repro.core.rounds.txn import txn_payload_width

        cfg = TxnBatchConfig(n_gcls=12, tuples_per_gcl=4, batch=8,
                             iters=2, max_group_lines=4,
                             zipf_theta=0.9, n_nodes=4)
        mesh = jax.make_mesh((4,), ("shards",))
        W = txn_payload_width(cfg.tuples_per_gcl)

        for algo in ("2pl", "to"):
            dcfg = DeviceTxnConfig(algo=algo,
                                   tuples_per_gcl=cfg.tuples_per_gcl,
                                   max_group_lines=cfg.max_group_lines)
            flat = DeviceTxnEngine(rp.DevicePlane.open(
                rp.make_state(cfg.n_nodes, cfg.n_gcls,
                              payload_width=W)), dcfg)
            shd = DeviceTxnEngine(rp.DevicePlane.open(
                rp.make_sharded_state(cfg.n_nodes, cfg.n_gcls, mesh,
                                      payload_width=W), mesh), dcfg)
            saw_abort = saw_retry = 0
            for txns, node, ts in device_txn_batches(cfg, seed=7):
                r1, _ = flat.run_batch(node, txns, ts=ts)
                r2, _ = shd.run_batch(node, txns, ts=ts)
                assert r1.decision.tolist() == r2.decision.tolist(), algo
                assert r1.exec_step.tolist() == r2.exec_step.tolist()
                assert r1.retries.tolist() == r2.retries.tolist()
                saw_abort += int((~r1.decision).sum())
                saw_retry += int(r1.retries.sum())
            for k, v in flat.plane.state.items():
                np.testing.assert_array_equal(
                    np.asarray(v), np.asarray(shd.plane.flat_state()[k]),
                    err_msg=f"{algo}:{k}")
            shd.plane.check()
            assert saw_retry > 0, algo
            if algo == "to":
                assert saw_abort > 0
        print("TXN_SHARDED_PARITY_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert "TXN_SHARDED_PARITY_OK" in out.stdout, out.stderr[-3000:]
