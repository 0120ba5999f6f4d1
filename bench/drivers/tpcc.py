"""TPC-C through ``DevicePlane.txn``: the transaction cells' driver.

A batch is one transaction per terminal (``apps.tpcc_device.Terminals``:
clause 4.2's terminals, clause 5.2.3's mix, the profiles' input rules),
served by one ``plane.txn`` call of the TPC-C program under 2PL no-wait.
The plane holds the configuration's whole population, made on the
device from the seed in one jitted call, the node caches empty.

After the window the plain reference (:mod:`bench.drivers.tpcc_reference`,
which imports nothing of the program) runs every finished transaction of
every batch one at a time in the device's serial order (``exec_step``,
then slot) and the check compares:

* ``output_mismatches`` — each transaction's outputs;
* ``image_mismatches`` — every row the reference touched, read back
  through ``plane.ops`` from a node other than its line's last writer;
* ``consistency_violations`` — clause 3.3.2.1 to 3.3.2.4 over the final
  image of every warehouse, district and order ring;
* ``order_ring_overruns`` — NewOrders that found an undelivered order in
  their ring slot.

A store without ``txn`` (the controls of :mod:`bench.control`) is served
by running the same program one transaction at a time over its ``ops``,
the lines loaded with the population on first touch.  See
:mod:`bench.drivers` for the contract.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import numpy as np

from bench import roofline
from bench.drivers import tpcc_reference
from bench.ycsb import seed32

READBACK_SLOTS = 4096          # lines a read-back call presents


def _tpcc():
    from repro.apps import tpcc_device
    return tpcc_device


def _scale(config: dict):
    return _tpcc().Scale.from_config(config)


def open_plane(config: dict, seed: int, devices: list):
    """A ``DevicePlane`` over the population built on ``devices[0]``."""
    from repro.core.rounds import DevicePlane
    n = int(config["n_nodes"])
    state = _tpcc().build_state(_scale(config), seed32(seed), n_nodes=n,
                                write_back=bool(config["write_back"]),
                                device=devices[0])
    return DevicePlane.open(state, n_nodes=n)


class Batches:
    """The terminals' batches: ``.r`` transactions each."""

    def __init__(self, config: dict, mix: dict, seed: int):
        t = _tpcc()
        self.scale = _scale(config)
        self.program = t.program(self.scale)
        self.terminals = t.Terminals(
            self.scale, seed32(seed), n_nodes=int(config["n_nodes"]),
            per_warehouse=int(mix["terminals_per_warehouse"]),
            mix=tuple(mix["mix"][k] for k in t.TYPES),
            remote_item_pct=mix["remote_item_pct"],
            remote_payment_pct=mix["remote_payment_pct"],
            by_last_name_pct=mix["by_last_name_pct"],
            rollback_pct=mix["rollback_pct"], ol_cnt=mix["ol_cnt"])
        self.r = self.terminals.n

    def next(self):
        txns, arrays = self.terminals.next()
        return txns, arrays, self.program


def traffic(config: dict, mix: dict, seed: int) -> Batches:
    return Batches(config, mix, seed)


def serve(plane, batch):
    """One ``plane.txn`` call: its result, or None if the batch did not
    finish within the loop's bounds."""
    txns, arrays, program = batch
    if not hasattr(plane, "txn"):
        return _serve_ops(plane, arrays, program)
    try:
        return plane.txn(*arrays, algo=program)
    except RuntimeError as e:
        if "not done" not in str(e) and "max_rounds" not in str(e):
            raise
        return None


def record(batch, result):
    txns, arrays, _ = batch
    if result is None:
        return SimpleNamespace(ops=0, rounds=0, failed=True)
    return SimpleNamespace(
        ops=len(txns), rounds=int(result.rounds), failed=False, txns=txns,
        node=arrays[0], exec_step=np.asarray(result.exec_step),
        outputs=np.asarray(result.outputs), lines=np.asarray(result.lines),
        modes=np.asarray(result.modes))


# ------------------------------------------------------------ the check

def check(config: dict, mix: dict, seed: int, records: list, plane):
    t = _tpcc()
    sc = _scale(config)
    n = int(config["n_nodes"])
    ref = tpcc_reference.TpccReference(
        seed32(seed), warehouses=sc.warehouses, districts=sc.districts,
        customers=sc.customers, items=sc.items, new_orders=sc.new_orders)
    out_bad = overruns = compared = 0
    writer: dict = {}                       # line -> node of last write
    for b in records:
        if b.failed:
            continue
        for i in sorted(range(len(b.txns)),
                        key=lambda i: (int(b.exec_step[i]), i)):
            txn = b.txns[i]
            want = ref.run(t.TYPES[txn.typ], **txn.args)
            out_bad += want != t.outputs(sc, txn.typ, b.outputs[i])
            overruns += txn.typ == t.NO and int(b.outputs[i][3]) != 0
            compared += 1
            for line in b.lines[i][b.modes[i] == 2]:
                writer[int(line)] = int(b.node[i])
    lines = _touched_lines(t, sc, ref)
    every = np.concatenate([
        np.arange(sc.base["W"], sc.base["C"]),
        np.arange(sc.base["O"], sc.base["end"])])
    lines = np.unique(np.concatenate([lines, every]))
    nodes = np.array([(writer[ln] + 1) % n if ln in writer else 0
                      for ln in lines], np.int32)
    image = dict(zip(lines.tolist(), _read(plane, lines, nodes, sc, seed)))
    checks = {
        "output_mismatches": {"value": int(out_bad), "limit": 0},
        "image_mismatches": {"value": _image_mismatches(t, sc, ref, image),
                             "limit": 0},
        "consistency_violations": {
            "value": len(_consistency(t, sc, image)), "limit": 0},
        "order_ring_overruns": {"value": int(overruns), "limit": 0}}
    return checks, compared


def _touched_lines(t, sc, ref) -> np.ndarray:
    out = []
    for table, key in ref.rows:
        out.append(_line_of(t, sc, table, key))
    for (w, d), rows in ref.history.items():
        for i in range(len(rows)):
            out.append(t.line_h(sc, w - 1, d - 1, sc.customers + i))
    return np.asarray(out, np.int64)


def _line_of(t, sc, table, key):
    if table == "W":
        return t.line_w(sc, key[0] - 1)
    if table == "D":
        return t.line_d(sc, key[0] - 1, key[1] - 1)
    if table == "C":
        return t.line_c(sc, key[0] - 1, key[1] - 1, key[2] - 1)
    if table == "S":
        return t.line_s(sc, key[0] - 1, key[1] - 1)
    if table == "I":
        return t.line_i(sc, key[0] - 1)
    return t.line_o(sc, key[0] - 1, key[1] - 1, key[2])


def _image_mismatches(t, sc, ref, image) -> int:
    """Rows the reference touched that differ in the read-back image."""
    bad = 0
    ring_rows = sc.history_lines * t.HIST_PER_LINE
    for (table, key), want in ref.rows.items():
        if want is None:
            continue
        line = _line_of(t, sc, table, key)
        rows = t.decode(sc, line, image[line])
        if table == "O":
            w, d, o = key
            got = rows[("O", (w, d, (o - 1) % sc.ring))]
            undelivered = o in ref.undelivered(w, d)
            olc = want["o_ol_cnt"]
            ok = (all(got[k] == v for k, v in want.items() if k != "lines")
                  and got["no_o_id"] == (o if undelivered else 0)
                  and got["lines"][:olc] == want["lines"]
                  and not any(v for ol in got["lines"][olc:]
                              for v in ol.values()))
        else:
            got = rows[(table, key)]
            ok = all(got[k] == v for k, v in want.items())
        bad += not ok
    for (w, d), appended in ref.history.items():
        for i, want in enumerate(appended[-ring_rows:],
                                 max(0, len(appended) - ring_rows)):
            r = sc.customers + i
            line = t.line_h(sc, w - 1, d - 1, r)
            got = t.decode(sc, line, image[line])[
                ("H", (w, d, r % ring_rows))]
            bad += any(got[k] != v for k, v in want.items())
    return int(bad)


def _consistency(t, sc, image) -> list:
    """Clause 3.3.2.1 to 3.3.2.4 over the read-back image."""
    wh = {w + 1: t.decode(sc, t.line_w(sc, w), image[t.line_w(sc, w)])[
        ("W", (w + 1,))] for w in range(sc.warehouses)}
    dist, orders = {}, {}
    ol_at = np.array([t.O_HDR + k * t.OL_LANES + t.OL_F["ol_o_id"][0] - 1
                      for k in range(t.MAX_OL)])
    for w in range(sc.warehouses):
        for d in range(sc.districts):
            key = (w + 1, d + 1)
            line = t.line_d(sc, w, d)
            dist[key] = t.decode(sc, line, image[line])[("D", key)]
            first = t.line_o(sc, w, d, 1)
            ring = np.stack([image[first + s] for s in range(sc.ring)])
            o_id = ring[:, t.O_F["o_id"][0]]
            live = o_id > 0
            orders[key] = list(zip(
                o_id[live].tolist(),
                ring[live, t.O_F["o_ol_cnt"][0]].tolist(),
                (ring[live, t.O_F["no_o_id"][0]] != 0).tolist(),
                (ring[live][:, ol_at] != 0).sum(axis=1).tolist()))
    return tpcc_reference.consistency(wh, dist, orders)


def _read(plane, lines, nodes, sc, seed) -> list:
    """Lanes of ``lines[i]`` read through ``plane.ops`` from
    ``nodes[i]``, in calls of one shape."""
    r = min(READBACK_SLOTS, sc.n_lines)
    out = []
    for s in range(0, lines.size, r):
        k = min(r, lines.size - s)
        ln = np.full(r, -1, np.int32)
        nd = np.zeros(r, np.int32)
        ln[:k], nd[:k] = lines[s:s + k], nodes[s:s + k]
        if not hasattr(plane, "txn"):
            _load(plane, ln[:k], sc, seed)
        out.extend(np.asarray(plane.ops(nd, ln, np.zeros(r, np.int32))
                              .data)[:k])
    return out


# ----------------------------------------- a store without txn (controls)

def _load(store, lines, sc, seed) -> None:
    """Write the population of lines the store has not seen yet."""
    if not hasattr(store, "_tpcc_pop"):
        t = _tpcc()
        pop = np.zeros((sc.n_lines, t.WIDTH), np.int32)
        for first, lanes in t.population(np, sc, seed32(seed)):
            pop[first:first + lanes.shape[0]] = lanes
        store._tpcc_pop, store._tpcc_seen = pop, set()
    new = np.array(sorted({int(x) for x in lines} - store._tpcc_seen),
                   np.int32)
    if new.size:
        store.ops(np.zeros(new.size, np.int32), new,
                  np.ones(new.size, np.int32), store._tpcc_pop[new])
        store._tpcc_seen.update(new.tolist())


@functools.lru_cache(maxsize=None)
def _jitted(program):
    import jax
    return jax.jit(program.plan), jax.jit(program.effect)


def _serve_ops(store, arrays, program):
    """The program run one transaction at a time, in slot order, over
    ``store.ops``: read a phase's lines, plan the next, then write the
    effect's exclusive lines back."""
    node, typ, inp, lines0, mode0 = arrays
    sc = program.scale
    plan, effect = _jitted(program)
    b, m = lines0.shape
    outs = np.zeros((b, program.outputs), np.int32)
    fin_l, fin_m = lines0.copy(), mode0.copy()
    calls = 0
    for i in range(b):
        ln, md = lines0[i:i + 1], mode0[i:i + 1]
        lanes = np.zeros((1, m, program.width), np.int32)
        done = np.zeros(m, bool)
        phase = 0
        while True:
            fresh = (ln[0] >= 0) & ~done
            sel = ln[0][fresh]
            _load(store, sel, sc, store.seed)
            lanes[0, fresh] = store.ops(np.full(sel.size, node[i], np.int32),
                                        sel, np.zeros(sel.size, np.int32)
                                        ).data
            done |= fresh
            calls += 1
            nl, nm = (np.asarray(x) for x in plan(
                np.int32([phase]), typ[i:i + 1], inp[i:i + 1], ln, md,
                lanes))
            if ((nl >= 0) & (ln < 0)).any() \
                    and phase + 1 < program.depths[typ[i]]:
                ln, md, phase = nl, nm, phase + 1
                continue
            _, fx, out = (np.asarray(x) for x in effect(
                typ[i:i + 1], inp[i:i + 1], ln, md, lanes))
            wr = (md[0] == 2) & (ln[0] >= 0)
            data = fx[0, wr].copy()
            data[:, 0] = 0
            store.ops(np.full(int(wr.sum()), node[i], np.int32), ln[0][wr],
                      np.ones(int(wr.sum()), np.int32), data)
            outs[i], fin_l[i], fin_m[i] = out[0], ln[0], md[0]
            break
    return SimpleNamespace(exec_step=np.arange(b), outputs=outs,
                           lines=fin_l, modes=fin_m, rounds=calls)


def least_bytes(config: dict, records: list) -> int:
    """Each finished transaction's distinct lines, each a latch word and
    a line read and written once."""
    lines = sum(int((b.lines >= 0).sum()) for b in records if not b.failed)
    return roofline.least_bytes(lines, int(config["payload_width"]))


# ---------------------------------------- compile hooks (no chip needed)

def lower_verb(config: dict, devices: list):
    """The fused loop ``plane.txn`` runs (``run_txn_rounds``), lowered at
    the cell's shapes on one chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.core.rounds import make_state, run_txn_rounds
    t = _tpcc()
    sc = _scale(config)
    n = int(config["n_nodes"])
    b = sc.warehouses * int(config["terminals_per_warehouse"])
    one = SingleDeviceSharding(devices[0])
    shapes = jax.eval_shape(lambda: make_state(
        n, sc.n_lines, write_back=bool(config["write_back"]),
        payload_width=t.WIDTH))
    state = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
             for k, s in shapes.items()}

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    return run_txn_rounds.lower(
        state, arr(b), arr(b), arr(b, t.INPUTS), arr(b, t.ENTRIES),
        arr(b, t.ENTRIES), program=t.program(sc), n_nodes=n,
        max_iters=4 * b + 16)


def lower_build(config: dict, devices: list):
    """The population build of ``open_plane``, lowered on one chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    fn = _tpcc().build_fn(_scale(config), n_nodes=int(config["n_nodes"]),
                          write_back=bool(config["write_back"]))
    return fn.lower(jax.ShapeDtypeStruct(
        (), jnp.uint32, sharding=SingleDeviceSharding(devices[0])))
