"""TPC-C on the device transaction loop against the plain reference.

At a small size on the CPU (2 warehouses of 3 districts, 20 customers,
50 items, 512 lines, 3 nodes, 4 terminals a warehouse), every
transaction type alone and the clause 5.2.3 mix run through
``DevicePlane.txn``; ``apps/tpcc_ref.py`` replays the same inputs one at
a time in the device's serial order (``exec_step``, then slot), and the
outputs, the final image of every row it touched and clause 3.3.2.1 to
3.3.2.4 must agree bit for bit — remote items and payments, last-name
lookups and rollbacks included.  The 4-shard plane must match the flat
one exactly.  Unit cases pin the shared/exclusive lock word, the phase
functions and the compile count.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.apps import tpcc_device as T                       # noqa: E402
from repro.apps import tpcc_ref                               # noqa: E402
from repro.core import rounds as rp                           # noqa: E402
from repro.core.rounds.engine import TRACE_COUNTS             # noqa: E402
from repro.core.rounds.txn import SHARED                      # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SC = T.Scale(warehouses=2, districts=3, customers=20, items=50, orders=20,
             new_orders=6, ring=30, history_lines=1, n_lines=512)
N = 3
SEED = 2 ** 31 + 77


def _reference(seed=SEED):
    return tpcc_ref.TpccReference(
        seed, warehouses=SC.warehouses, districts=SC.districts,
        customers=SC.customers, items=SC.items, new_orders=SC.new_orders)


def _plane(seed=SEED):
    return rp.DevicePlane.open(T.build_state(SC, seed, n_nodes=N),
                               n_nodes=N)


def _image(plane):
    """Every line's lanes, read through the plane from the last node."""
    n = SC.n_lines
    res = plane.ops(np.full(n, N - 1, np.int32),
                    np.arange(n, dtype=np.int32), np.zeros(n, np.int32))
    return dict(enumerate(np.asarray(res.data)))


def _run(plane, terms, ref, batches):
    """Serve ``batches`` batches and replay them on ``ref``: counts of
    output mismatches and of each type seen, and the results."""
    bad, seen, results = 0, [0] * 5, []
    prog = T.program(SC)
    for _ in range(batches):
        txns, arrays = terms.next()
        res = plane.txn(*arrays, algo=prog)
        results.append((txns, res))
        for i in sorted(range(len(txns)),
                        key=lambda i: (int(res.exec_step[i]), i)):
            t = txns[i]
            want = ref.run(T.TYPES[t.typ], **t.args)
            bad += want != T.outputs(SC, t.typ, res.outputs[i])
            seen[t.typ] += 1
    return bad, seen, results


def _image_and_consistency(plane, ref):
    from bench.drivers import tpcc as drv
    image = _image(plane)
    return (drv._image_mismatches(T, SC, ref, image),
            drv._consistency(T, SC, image))


MIXES = {
    "new_order": dict(mix=(1, 0, 0, 0, 0), rollback_pct=25,
                      remote_item_pct=30),
    "payment": dict(mix=(0, 1, 0, 0, 0), remote_payment_pct=50),
    "order_status": dict(mix=(0, 0, 1, 0, 0)),
    "delivery": dict(mix=(0.5, 0, 0, 0.5, 0)),
    "stock_level": dict(mix=(0.5, 0, 0, 0, 0.5)),
    "mix": dict(),
}


@pytest.mark.parametrize("case", sorted(MIXES))
def test_tpcc_matches_reference(case):
    plane, ref = _plane(), _reference()
    terms = T.Terminals(SC, SEED, n_nodes=N, per_warehouse=4,
                        **MIXES[case])
    bad, seen, results = _run(plane, terms, ref, 3)
    assert bad == 0
    assert _image_and_consistency(plane, ref) == (0, [])
    plane.check()
    typ = T.TYPES.index(case) if case != "mix" else None
    if typ is not None:
        assert seen[typ] > 0
    outs = [(t, r.outputs[i]) for txns, r in results
            for i, t in enumerate(txns)]
    if case == "new_order":
        assert any(o[2] for t, o in outs)                      # rollback
        assert any(t.inp[5] == 0 for t, _ in outs)            # remote item
    if case == "payment":
        assert any(t.args["c_w"] != t.args["w"] for t, _ in outs)
        assert any("c_last" in t.args for t, _ in outs)       # by name
    if case == "delivery":
        assert any(any(o[:SC.districts]) for t, o in outs if t.typ == T.DEL)
    if case == "mix":
        assert sum(r.counters["restarts"] for _, r in results) > 0


def test_population_matches_reference():
    plane, ref = _plane(), _reference()
    image = _image(plane)
    rows = 0
    for line in range(SC.base["end"]):
        for (table, key), got in T.decode(SC, line, image[line]).items():
            if table == "H":
                continue
            if table == "O":
                w, d, slot = key
                want = ref.row("O", (w, d, slot + 1))
                if want is None:
                    assert got["o_id"] == 0
                    continue
                assert got["no_o_id"] == (slot + 1 if slot + 1 in
                                          ref.undelivered(w, d) else 0)
                olc = want["o_ol_cnt"]
                assert got["lines"][:olc] == want["lines"]
                got = {k: v for k, v in got.items() if k in want
                       and k != "lines"}
                want = {k: v for k, v in want.items() if k != "lines"}
            else:
                want = ref.row(table, key)
                got = {k: got[k] for k in want}
            assert got == want, (table, key)
            rows += 1
    assert rows > 300


def _tuple_batch(glines, wmask_lines):
    """A 2PL batch over tuple-header lines (one tuple a line)."""
    glines = np.asarray(glines, np.int32)
    b, g = glines.shape
    w = np.zeros((b, g, 1), np.int32)
    r = np.zeros((b, g, 1), np.int32)
    for i, row in enumerate(wmask_lines):
        for j in range(g):
            if glines[i, j] >= 0:
                (w if row[j] else r)[i, j, 0] = 1
    return glines, r, w, np.arange(b, dtype=np.int32)


def _tuple_plane():
    from repro.core.rounds.txn import txn_payload_width
    return rp.DevicePlane.open(rp.make_state(
        2, 8, write_back=True, payload_width=txn_payload_width(1)),
        n_nodes=2)


@pytest.mark.parametrize("writes, want_retries, want_steps", [
    ((0, 0), [0, 0], [0, 0]),       # S + S on line 0: granted together
    ((0, 1), [0, 1], [0, 1]),       # S (slot 0) then X: the X restarts
    ((1, 0), [0, 1], [0, 1]),       # X (slot 0) then S: the S restarts
    ((1, 1), [0, 1], [0, 1]),       # X + X: the higher slot restarts
], ids=["s_s", "s_x", "x_s", "x_x"])
def test_shared_exclusive_no_wait(writes, want_retries, want_steps):
    plane = _tuple_plane()
    batch = _tuple_batch([[0, -1], [0, 3]], [[writes[0], 0],
                                              [writes[1], 1]])
    res = plane.txn(np.array([0, 1], np.int32), *batch, algo="2pl")
    assert res.retries.tolist() == want_retries
    assert res.exec_step.tolist() == want_steps
    assert res.decision.all()
    c = res.counters
    assert c["restarts"] == sum(want_retries)
    assert c["attempts"] == 2 + sum(want_retries)
    granted_x = sum(writes) + 1                 # line 3 is written too
    assert c["x_grants"] == granted_x
    # every lock word is free after the batch, and each write landed
    image = np.asarray(plane.ops(np.zeros(8, np.int32),
                                 np.arange(8, dtype=np.int32),
                                 np.zeros(8, np.int32)).data)
    assert (image[:, 0] == 0).all()
    assert image[0, 1] == sum(writes) and image[3, 1] == 1
    plane.check()


def test_shared_readers_counted_in_the_lock_word():
    """Three readers of one line, on two nodes, are granted together
    in one iteration, and their releases return the word to 0."""
    plane = _tuple_plane()
    glines = np.array([[0, -1], [0, -1], [0, -1]], np.int32)
    batch = _tuple_batch(glines, [[0, 0]] * 3)
    res = plane.txn(np.array([0, 1, 0], np.int32), *batch, algo="2pl")
    assert res.retries.tolist() == [0, 0, 0]
    assert res.exec_step.tolist() == [0, 0, 0]
    assert res.counters["s_grants"] == 3
    image = np.asarray(plane.ops(np.zeros(1, np.int32),
                                 np.zeros(1, np.int32),
                                 np.zeros(1, np.int32)).data)
    assert image[0, 0] == 0


def _lanes_for(plane, lines):
    """[1, M, W] lanes of ``lines`` (entry order) read from the plane."""
    lines = np.asarray(lines, np.int32)
    out = np.zeros((1, T.ENTRIES, T.WIDTH), np.int32)
    ok = lines >= 0
    out[0, ok] = np.asarray(plane.ops(
        np.zeros(int(ok.sum()), np.int32), lines[ok],
        np.zeros(int(ok.sum()), np.int32)).data)
    return out


def _plan(plane, term_args, phase):
    typ, inp, lines = term_args
    mode = np.where(lines >= 0, SHARED, 0).astype(np.int32)
    lanes = _lanes_for(plane, lines)
    jnp = jax.numpy
    nl, _ = T.program(SC).plan(
        jnp.int32([phase]), jnp.int32([typ]), jnp.asarray(inp[None]),
        jnp.asarray(lines[None]), jnp.asarray(mode[None]),
        jnp.asarray(lanes))
    return np.asarray(nl)[0], lanes


def test_phase_functions_hand_computed():
    plane, ref = _plane(), _reference()
    base = np.full(T.ENTRIES, -1, np.int32)
    inp = np.zeros(T.INPUTS, np.int32)
    # NewOrder, w=2 d=3: its ORDER slot at d_next_o_id (21)
    lines = base.copy()
    lines[:3] = [T.line_w(SC, 1), T.line_d(SC, 1, 2),
                 T.line_c(SC, 1, 2, 4)]
    inp[:6] = [2, 3, 5, 1, 9, 1]
    inp[6:9] = [7, 2, 3]
    lines[3] = T.line_s(SC, 1, 6)
    lines[18] = T.line_i(SC, 6)
    nl, _ = _plan(plane, (T.NO, inp, lines), 0)
    assert nl[33] == T.line_o(SC, 1, 2, SC.orders + 1)
    # ...and none when an item is unused (the rollback)
    inp2 = inp.copy()
    inp2[6] = SC.items + 1
    lines2 = lines.copy()
    lines2[3], lines2[18] = -1, T.line_i(SC, SC.items)
    nl, _ = _plan(plane, (T.NO, inp2, lines2), 0)
    assert nl[33] == -1
    # Payment, w=1 d=2: the history line at the district's cursor
    lines = base.copy()
    lines[:3] = [T.line_w(SC, 0), T.line_d(SC, 0, 1), T.line_c(SC, 0, 1, 0)]
    nl, _ = _plan(plane, (T.PAY, np.r_[[1, 2, 1, 2, 1, 100, 9],
                                       np.zeros(57, int)].astype(np.int32),
                          lines), 0)
    assert nl[3] == T.line_h(SC, 0, 1, SC.customers)
    # OrderStatus, customer 7 of (1, 1): its initial order
    lines = base.copy()
    lines[0] = T.line_c(SC, 0, 0, 6)
    nl, _ = _plan(plane, (T.OS, np.r_[[1, 1, 7], np.zeros(61, int)]
                          .astype(np.int32), lines), 0)
    o = ref._last_order(1, 1, 7)
    assert nl[1] == T.line_o(SC, 0, 0, o)
    # Delivery, w=1: each district's oldest undelivered order, then its
    # customer
    lines = base.copy()
    lines[:SC.districts] = [T.line_d(SC, 0, d) for d in range(SC.districts)]
    dinp = np.r_[[1, 4, 9], np.zeros(61, int)].astype(np.int32)
    nl, _ = _plan(plane, (T.DEL, dinp, lines), 0)
    first = SC.orders - SC.new_orders + 1
    assert nl[10:10 + SC.districts].tolist() == [
        T.line_o(SC, 0, d, first) for d in range(SC.districts)]
    nl2, _ = _plan(plane, (T.DEL, dinp, nl), 1)
    assert nl2[20:20 + SC.districts].tolist() == [
        T.line_c(SC, 0, d, ref.row("O", (1, d + 1, first))["o_c_id"] - 1)
        for d in range(SC.districts)]
    # StockLevel, w=2 d=1: the last 20 orders, then their distinct items
    lines = base.copy()
    lines[0] = T.line_d(SC, 1, 0)
    sinp = np.r_[[2, 1, 15], np.zeros(61, int)].astype(np.int32)
    nl, _ = _plan(plane, (T.SL, sinp, lines), 0)
    nxt = SC.orders + 1
    assert nl[1:21].tolist() == [T.line_o(SC, 1, 0, o)
                                 for o in range(nxt - 20, nxt)]
    nl2, _ = _plan(plane, (T.SL, sinp, nl), 1)
    items = {ol["ol_i_id"] for o in range(nxt - 20, nxt)
             for ol in ref.row("O", (2, 1, o))["lines"]}
    got = sorted(x for x in nl2[21:] if x >= 0)
    assert got == sorted(T.line_s(SC, 1, i - 1) for i in items)


def test_tpcc_compiles_once_per_shape():
    plane = _plane()
    terms = T.Terminals(SC, SEED, n_nodes=N, per_warehouse=4)
    prog = T.program(SC)

    def keys():
        return {k: v for k, v in TRACE_COUNTS.items()
                if k[0] == "txn" and k[1] == "tpcc"}
    plane.txn(*terms.next()[1], algo=prog)
    after_one = keys()
    assert sum(after_one.values()) >= 1
    plane.txn(*terms.next()[1], algo=prog)
    plane.txn(*terms.next()[1], algo=prog)
    assert keys() == after_one


def test_consistency_rejects_planted_faults():
    from bench.drivers import tpcc as drv
    plane = _plane()
    image = _image(plane)
    assert drv._consistency(T, SC, image) == []
    d_ytd = T.D_F["d_ytd"][0] + 1
    bad = dict(image)
    line = T.line_d(SC, 0, 1)
    bad[line] = image[line].copy()
    bad[line][d_ytd] += 1                          # W_YTD != sum(D_YTD)
    assert [c for c, _ in drv._consistency(T, SC, bad)] == ["3.3.2.1"]
    bad = dict(image)
    line = T.line_d(SC, 1, 2)
    bad[line] = image[line].copy()
    bad[line][T.D_F["d_next_o_id"][0]] += 1        # no order at next - 1
    assert [c for c, _ in drv._consistency(T, SC, bad)] == ["3.3.2.2"]
    bad = dict(image)
    line = T.line_o(SC, 0, 0, SC.orders - 2)
    bad[line] = image[line].copy()
    bad[line][T.O_F["no_o_id"][0]] = 0             # a hole in NEW-ORDER
    assert [c for c, _ in drv._consistency(T, SC, bad)] == ["3.3.2.3"]
    bad = dict(image)
    line = T.line_o(SC, 1, 0, 5)
    bad[line] = image[line].copy()
    bad[line][T.O_F["o_ol_cnt"][0]] += 1           # a missing order line
    assert [c for c, _ in drv._consistency(T, SC, bad)] == ["3.3.2.4"]


def test_reference_copies_agree():
    """The benchmark keeps its own copy of the reference; only the
    module docstring may differ."""
    def body(p):
        text = p.read_text()
        return text[text.index('"""', 3) + 3:]
    assert body(ROOT / "src/repro/apps/tpcc_ref.py") == body(
        ROOT / "bench/drivers/tpcc_reference.py")


def test_flat_vs_sharded_tpcc_subprocess():
    """The 4-shard plane runs the TPC-C mix exactly as the flat one
    (decisions, order, retries, outputs, final state) and both match
    the reference."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
        import jax, numpy as np
        from repro.apps import tpcc_device as T
        from repro.apps import tpcc_ref
        from repro.core import rounds as rp
        SC = T.Scale(warehouses=2, districts=3, customers=20, items=50,
                     orders=20, new_orders=6, ring=30, history_lines=1,
                     n_lines=512)
        seed, n = {SEED}, 3
        mesh = jax.make_mesh((4,), ("shards",))
        flat = rp.DevicePlane.open(T.build_state(SC, seed, n_nodes=n),
                                   n_nodes=n)
        st = T.build_state(SC, seed, n_nodes=n)
        shd = rp.DevicePlane.open(rp.shard_state(st, mesh), mesh,
                                  n_nodes=n)
        ref = tpcc_ref.TpccReference(seed, warehouses=2, districts=3,
                                     customers=20, items=50, new_orders=6)
        terms = T.Terminals(SC, seed, n_nodes=n, per_warehouse=5,
                            mix=(0.3, 0.3, 0.1, 0.15, 0.15))
        prog = T.program(SC)
        bad = restarts = 0
        for _ in range(3):
            txns, arrays = terms.next()
            r1 = flat.txn(*arrays, algo=prog)
            r2 = shd.txn(*arrays, algo=prog)
            for f in ("decision", "exec_step", "retries", "outputs",
                      "lines", "modes"):
                np.testing.assert_array_equal(getattr(r1, f),
                                              getattr(r2, f), err_msg=f)
            assert r1.counters == r2.counters
            assert r1.rounds == r2.rounds
            restarts += r1.counters["restarts"]
            for i in sorted(range(len(txns)),
                            key=lambda i: (int(r2.exec_step[i]), i)):
                t = txns[i]
                bad += ref.run(T.TYPES[t.typ], **t.args) != T.outputs(
                    SC, t.typ, r2.outputs[i])
        for k, v in flat.state.items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(shd.flat_state()[k]), err_msg=k)
        shd.check()
        assert bad == 0 and restarts > 0
        print("TPCC_SHARDED_PARITY_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=str(ROOT),
                         capture_output=True, text=True, timeout=900)
    assert "TPCC_SHARDED_PARITY_OK" in out.stdout, out.stderr[-3000:]
