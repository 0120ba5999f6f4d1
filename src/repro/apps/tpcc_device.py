"""TPC-C at its own schema, cardinalities and mix on ``DevicePlane.txn``.

TPC-C rev. 5.11 (tpc.org): schema clause 1.3, initial population clause
4.3, transaction profiles clauses 2.4 to 2.8, mix clause 5.2.3.  Every
transaction runs under 2PL no-wait at serializable isolation through
the fused device loop (:mod:`repro.core.rounds.txn`): this module is its
:class:`TpccProgram` (line layout, phase and effect functions), the
population built on the device from the seed (:func:`build_state`), and
the terminals that draw the inputs (:class:`Terminals`).

Line layout, one 1 KiB line (256 int32 lanes, lane 0 the lock word):

* WAREHOUSE, DISTRICT, CUSTOMER and STOCK: one row per line;
* ORDER with its NEW-ORDER flag (``no_o_id``) and its up to 15
  ORDER-LINEs (9 + 15 x 15 lanes) share one line, in a ring of ``ring``
  slots per district (slot ``(o_id - 1) % ring``);
* ITEM: 12 rows per line, read without a lock (nobody writes it);
* HISTORY: 22 rows per line, a ring per district.

Numbers take one lane (money in cents, rates in 1e-4), year-to-date sums
two (``hi * 10**8 + lo`` cents); strings are their bytes, four to a
lane.  Three fields stand in for indexes the spec leaves to the
implementation: the district's oldest undelivered order
(``d_next_deliver``) for NEW-ORDER's minimum, its history cursor
(``d_hist_next``), and the customer's last order (``c_last_o_id``),
which NewOrder maintains (so NewOrder locks the customer exclusive).

Population is a pure function of ``(seed, table, row)`` written once,
generic over the array module: :func:`build_state` runs it with
``jax.numpy`` in one jitted call on the device; the terminals run parts
of it with numpy (the last-name index).  The plain reference
(:mod:`repro.apps.tpcc_ref`) has its own copy of the formulas.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from ..core.rounds.txn import EXCL, SHARED, TxnProgram

NO, PAY, OS, DEL, SL = range(5)
TYPES = ("new_order", "payment", "order_status", "delivery",
         "stock_level")
MIX = (0.45, 0.43, 0.04, 0.04, 0.04)          # clause 5.2.3

WIDTH = 256                  # lanes of a 1 KiB line
MAX_OL = 15
ITEMS_PER_LINE = 12
HIST_PER_LINE = 22
SL_ORDERS = 20               # StockLevel's last 20 orders
ENTRIES = 1 + SL_ORDERS + SL_ORDERS * MAX_OL       # 321, StockLevel's
INPUTS = 64
OUTPUTS = 96
YTD_BASE = 10 ** 8
ALNUM = (b"0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ"
         b"abcdefghijklmnopqrstuvwxyz")
SYLLABLES = (b"BAR", b"OUGHT", b"ABLE", b"PRI", b"PRES", b"ESE", b"ANTI",
             b"CALLY", b"ATION", b"EING")
ORIGINAL = b"ORIGINAL"
LOAD_DATE = 1


def _layout(fields):
    off, at = {}, 1
    for name, n in fields:
        off[name] = (at, n)
        at += n
    return off, at


W_F, _ = _layout([("w_id", 1), ("w_tax", 1), ("w_ytd", 2), ("w_name", 3),
                  ("w_street_1", 5), ("w_street_2", 5), ("w_city", 5),
                  ("w_state", 1), ("w_zip", 3)])
D_F, _ = _layout([("d_id", 1), ("d_w_id", 1), ("d_tax", 1), ("d_ytd", 2),
                  ("d_next_o_id", 1), ("d_name", 3), ("d_street_1", 5),
                  ("d_street_2", 5), ("d_city", 5), ("d_state", 1),
                  ("d_zip", 3), ("d_next_deliver", 1), ("d_hist_next", 1)])
C_F, _ = _layout([("c_id", 1), ("c_d_id", 1), ("c_w_id", 1),
                  ("c_first", 4), ("c_middle", 1), ("c_last", 4),
                  ("c_street_1", 5), ("c_street_2", 5), ("c_city", 5),
                  ("c_state", 1), ("c_zip", 3), ("c_phone", 4),
                  ("c_since", 1), ("c_credit", 1), ("c_credit_lim", 1),
                  ("c_discount", 1), ("c_balance", 1),
                  ("c_ytd_payment", 2), ("c_payment_cnt", 1),
                  ("c_delivery_cnt", 1), ("c_data", 125),
                  ("c_last_o_id", 1)])
S_F, _ = _layout([("s_i_id", 1), ("s_w_id", 1), ("s_quantity", 1)]
                 + [(f"s_dist_{k:02d}", 6) for k in range(1, 11)]
                 + [("s_ytd", 1), ("s_order_cnt", 1), ("s_remote_cnt", 1),
                    ("s_data", 13)])
I_F, I_LANES = _layout([("i_im_id", 1), ("i_price", 1), ("i_name", 6),
                        ("i_data", 13)])
I_LANES -= 1                                        # 21 lanes an item
O_F, O_HDR = _layout([("o_id", 1), ("o_d_id", 1), ("o_w_id", 1),
                      ("o_c_id", 1), ("o_entry_d", 1), ("o_carrier_id", 1),
                      ("o_ol_cnt", 1), ("o_all_local", 1), ("no_o_id", 1)])
OL_F, OL_LANES = _layout([("ol_o_id", 1), ("ol_d_id", 1), ("ol_w_id", 1),
                          ("ol_number", 1), ("ol_i_id", 1),
                          ("ol_supply_w_id", 1), ("ol_delivery_d", 1),
                          ("ol_quantity", 1), ("ol_amount", 1),
                          ("ol_dist_info", 6)])
OL_LANES -= 1                                       # 15 lanes an ol
H_F, H_LANES = _layout([("h_c_id", 1), ("h_c_wd", 1), ("h_wd", 1),
                        ("h_date", 1), ("h_amount", 1), ("h_data", 6)])
H_LANES -= 1                                        # 11 lanes a row
assert O_HDR + MAX_OL * OL_LANES <= WIDTH
assert 1 + ITEMS_PER_LINE * I_LANES <= WIDTH
assert 1 + HIST_PER_LINE * H_LANES <= WIDTH


@dataclass(frozen=True)
class Scale:
    """Cardinalities (clause 4.3 at its defaults) and the ring sizes."""

    warehouses: int = 3
    districts: int = 10
    customers: int = 3000            # per district
    items: int = 100_000             # ITEM rows; STOCK rows a warehouse
    orders: int = 3000               # initial orders per district
    new_orders: int = 900            # of them undelivered
    ring: int = 4000                 # order slots per district
    history_lines: int = 137         # history lines per district
    n_lines: int = 2 ** 19           # lines of the plane

    @property
    def wd(self) -> int:
        return self.warehouses * self.districts

    @property
    def base(self) -> dict:
        """First line of each table's region."""
        w, wd = self.warehouses, self.wd
        b = {"W": 0, "D": w}
        b["C"] = b["D"] + wd
        b["S"] = b["C"] + wd * self.customers
        b["H"] = b["S"] + w * self.items
        b["I"] = b["H"] + wd * self.history_lines
        b["O"] = b["I"] + self.items // ITEMS_PER_LINE + 1
        b["end"] = b["O"] + wd * self.ring
        return b

    def check(self) -> None:
        if self.base["end"] > self.n_lines:
            raise ValueError(f"{self.base['end']} lines do not fit "
                             f"n_lines={self.n_lines}")
        if self.orders < SL_ORDERS or self.ring < self.orders:
            raise ValueError("need orders >= 20 and ring >= orders")
        if self.orders != self.customers:
            raise ValueError("clause 4.3.3.1: one order per customer")
        if self.history_lines * HIST_PER_LINE < self.customers:
            raise ValueError("the history ring must hold a row per "
                             "customer")

    @classmethod
    def from_config(cls, config: dict) -> "Scale":
        names = {f for f in cls.__dataclass_fields__}
        return cls(**{k: int(v) for k, v in config.items() if k in names})


# ------------------------------------------------- addresses (0-based ids)

def line_w(sc, w):
    return sc.base["W"] + w


def line_d(sc, w, d):
    return sc.base["D"] + w * sc.districts + d


def line_c(sc, w, d, c):
    return sc.base["C"] + (w * sc.districts + d) * sc.customers + c


def line_s(sc, w, i):
    return sc.base["S"] + w * sc.items + i


def line_i(sc, i):
    return sc.base["I"] + i // ITEMS_PER_LINE


def line_o(sc, w, d, o_id):
    return sc.base["O"] + (w * sc.districts + d) * sc.ring \
        + (o_id - 1) % sc.ring


def line_h(sc, w, d, r):
    return sc.base["H"] + (w * sc.districts + d) * sc.history_lines \
        + (r % (sc.history_lines * HIST_PER_LINE)) // HIST_PER_LINE


# ------------------------------------------------------------- population
# One hash, generic over the array module ``xp`` (numpy or jax.numpy):
# every value is a function of (seed, table tag, row, field tag, k).

T_W, T_D, T_C, T_S, T_I, T_O, T_H, T_PERM = range(1, 9)


def _fmix(xp, x):
    x = x ^ (x >> 16)
    x = x * xp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * xp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _hash(xp, seed, table, row, field, k=0):
    u = xp.uint32
    with np.errstate(over="ignore"):
        x = _fmix(xp, xp.asarray(seed).astype(u)
                  ^ u((table * 0x9E3779B1) & 0xFFFFFFFF))
        x = _fmix(xp, x ^ xp.asarray(row).astype(u) * u(0x85EBCA77))
        x = _fmix(xp, x ^ u((field * 0xC2B2AE3D) & 0xFFFFFFFF)
                  ^ xp.asarray(k).astype(u) * u(0x27D4EB2F))
    return x


def _uni(xp, seed, table, row, field, lo, hi, k=0):
    h = _hash(xp, seed, table, row, field, k)
    return (h % xp.uint32(hi - lo + 1)).astype(xp.int32) + xp.int32(lo)


def _pack(xp, chars):
    """[..., 4n] byte values -> [..., n] int32 lanes (little-endian)."""
    c = chars.astype(xp.int32).reshape(chars.shape[:-1] + (-1, 4))
    return c[..., 0] | (c[..., 1] << 8) | (c[..., 2] << 16) \
        | (c[..., 3] << 24)


def _astr(xp, seed, table, row, field, lo, hi, lanes, original=False):
    """A clause 4.3.2.2 random a-string (alphanumeric here), its length
    uniform in [lo, hi]; with ``original``, 10% hold ORIGINAL at a random
    position (clause 4.3.3.1)."""
    shape = xp.asarray(row).shape
    if len(shape) > 1:        # one flat axis of rows compiles far faster
        flat = _astr(xp, seed, table, xp.reshape(row, (-1,)), field, lo,
                     hi, lanes, original)
        return xp.reshape(flat, shape + (lanes,))
    row = xp.asarray(row)[..., None]
    pos = xp.arange(4 * lanes, dtype=xp.int32)
    n = _uni(xp, seed, table, row, field, lo, hi, 0xFFFF)
    ch = xp.asarray(np.frombuffer(ALNUM, np.uint8).astype(np.int32))[
        (_hash(xp, seed, table, row, field, pos) % xp.uint32(62))
        .astype(xp.int32)]
    if original:
        has = _uni(xp, seed, table, row, field, 1, 100, 0xFFFE) <= 10
        at = (_hash(xp, seed, table, row, field, 0xFFFD)
              % (n - 7).astype(xp.uint32)).astype(xp.int32)
        orig = xp.asarray(np.frombuffer(ORIGINAL, np.uint8)
                          .astype(np.int32))
        rel = pos - at
        inside = has & (rel >= 0) & (rel < 8)
        ch = xp.where(inside, orig[xp.clip(rel, 0, 7)], ch)
    return _pack(xp, xp.where(pos < n, ch, 0))


def _nstr(xp, seed, table, row, field, digits, lanes, suffix=b""):
    """``digits`` random decimal digits, then ``suffix``."""
    row = xp.asarray(row)[..., None]
    pos = xp.arange(4 * lanes, dtype=xp.int32)
    d = (_hash(xp, seed, table, row, field, pos) % xp.uint32(10)) \
        .astype(xp.int32) + 48
    suf = np.zeros(4 * lanes, np.int32)
    suf[digits:digits + len(suffix)] = np.frombuffer(suffix, np.uint8)
    return _pack(xp, xp.where(pos < digits, d, xp.asarray(suf)))


def _const_str(xp, s: bytes, lanes, shape):
    b = np.zeros(4 * lanes, np.int32)
    b[:len(s)] = np.frombuffer(s, np.uint8)
    return xp.broadcast_to(_pack(xp, xp.asarray(b)), tuple(shape) + (lanes,))


@functools.lru_cache(maxsize=None)
def last_names() -> np.ndarray:
    """[1000, 16] bytes of clause 4.3.2.3's last names."""
    out = np.zeros((1000, 16), np.int32)
    for n in range(1000):
        s = (SYLLABLES[n // 100] + SYLLABLES[n // 10 % 10]
             + SYLLABLES[n % 10])
        out[n, :len(s)] = np.frombuffer(s, np.uint8)
    return out


def c_last_load(xp, seed):
    """Clause 2.1.6's C for ``c_last`` at load, from the seed."""
    return (_hash(xp, seed, 99, 0, 1) % xp.uint32(256)).astype(xp.int32)


def nurand_consts(seed32: int) -> dict:
    """Clause 2.1.6's C values: ``c_last`` at load and at run (their
    difference in [65, 119], not 96 or 112), ``c_id``, ``ol_i_id``."""
    h = int(_hash(np, seed32, 99, 0, 2))
    load = int(c_last_load(np, seed32))
    delta = [d for d in range(65, 120) if d not in (96, 112)][h % 53]
    return {"c_last_load": load, "c_last_run": (load + delta) % 256,
            "c_id": (h >> 8) % 1024, "ol_i_id": (h >> 18) % 8192}


def _nurand(u_a, u_xy, a, x, y, c):
    return ((u_a | u_xy) + c) % (y - x + 1) + x


def customer_last(xp, sc, seed, rows, c):
    """Last-name number of customer ``c`` (0-based) at hash row
    ``rows``: the first 1,000 by id, the rest NURand(255, 0, 999)."""
    cl = c_last_load(xp, seed)
    nu = _nurand(_uni(xp, seed, T_C, rows, 21, 0, 255),
                 _uni(xp, seed, T_C, rows, 22, 0, 999), 255, 0, 999, cl)
    return xp.where(c < 1000, c, nu)


def customer_first(xp, seed, rows):
    return _astr(xp, seed, T_C, rows, 3, 8, 16, 4)


def _rows(sc, w, d, c):
    return (w * sc.districts + d) * sc.customers + c


def _perm_keys(xp, seed, wd, n):
    return _hash(xp, seed, T_PERM, xp.arange(wd)[:, None],
                 0, xp.arange(n)[None, :])


def _put(xp, shape, width, field_off, value):
    """Rows [*shape, width] holding the fields of ``value`` (name ->
    values [*shape] or [*shape, n]) at their lanes, zeros elsewhere
    (lane 0 and fields not given included), as one concatenation."""
    blocks, at = [], 0
    for name, (lane, n) in sorted(field_off.items(), key=lambda f: f[1]):
        if lane > at:
            blocks.append(xp.zeros(tuple(shape) + (lane - at,), xp.int32))
        v = xp.asarray(value.get(name, 0), xp.int32)
        if v.ndim == len(shape):
            v = v[..., None]
        blocks.append(xp.broadcast_to(v, tuple(shape) + (n,)))
        at = lane + n
    blocks.append(xp.zeros(tuple(shape) + (width - at,), xp.int32))
    return xp.concatenate(blocks, axis=-1)


def _ytd(xp, cents):
    cents = xp.asarray(cents, xp.int32)
    return xp.stack([cents // YTD_BASE, cents % YTD_BASE], axis=-1)


def _address(xp, seed, table, rows, prefix):
    return {f"{prefix}_street_1": _astr(xp, seed, table, rows, 40, 10, 20,
                                        5),
            f"{prefix}_street_2": _astr(xp, seed, table, rows, 41, 10, 20,
                                        5),
            f"{prefix}_city": _astr(xp, seed, table, rows, 42, 10, 20, 5),
            f"{prefix}_state": _astr(xp, seed, table, rows, 43, 2, 2, 1),
            f"{prefix}_zip": _nstr(xp, seed, table, rows, 44, 4, 3,
                                   b"11111")}


def warehouse_lanes(xp, sc, seed):
    w = xp.arange(sc.warehouses)
    return _put(xp, w.shape, WIDTH, W_F, {
        "w_id": w + 1, "w_tax": _uni(xp, seed, T_W, w, 2, 0, 2000),
        "w_ytd": _ytd(xp, xp.full(w.shape, 3_000_000 * sc.districts)),
        "w_name": _astr(xp, seed, T_W, w, 4, 6, 10, 3),
        **_address(xp, seed, T_W, w, "w")})


def district_lanes(xp, sc, seed):
    r = xp.arange(sc.wd)
    return _put(xp, r.shape, WIDTH, D_F, {
        "d_id": r % sc.districts + 1, "d_w_id": r // sc.districts + 1,
        "d_tax": _uni(xp, seed, T_D, r, 3, 0, 2000),
        "d_ytd": _ytd(xp, xp.full(r.shape, 3_000_000)),
        "d_next_o_id": xp.full(r.shape, sc.orders + 1),
        "d_name": _astr(xp, seed, T_D, r, 6, 6, 10, 3),
        **_address(xp, seed, T_D, r, "d"),
        "d_next_deliver": xp.full(r.shape, sc.orders - sc.new_orders + 1),
        "d_hist_next": xp.full(r.shape, sc.customers)})


def _order_customer(xp, sc, seed):
    """[wd, orders] 0-based customer of each initial order (clause
    4.3.3.1: a random permutation per district), and [wd, customers]
    each customer's initial order id."""
    keys = _perm_keys(xp, seed, sc.wd, sc.customers)
    perm = xp.argsort(keys, axis=1, stable=True)
    inv = xp.argsort(perm, axis=1, stable=True)
    return perm[:, :sc.orders], inv + 1


def customer_lanes(xp, sc, seed):
    n = sc.wd * sc.customers
    r = xp.arange(n)
    c = r % sc.customers
    wd = r // sc.customers
    _, last_o = _order_customer(xp, sc, seed)
    ln = xp.asarray(last_names())[customer_last(xp, sc, seed, r, c)]
    bc = _uni(xp, seed, T_C, r, 14, 1, 100) <= 10
    return _put(xp, r.shape, WIDTH, C_F, {
        "c_id": c + 1, "c_d_id": wd % sc.districts + 1,
        "c_w_id": wd // sc.districts + 1,
        "c_first": customer_first(xp, seed, r),
        "c_middle": _const_str(xp, b"OE", 1, r.shape),
        "c_last": _pack(xp, ln),
        **_address(xp, seed, T_C, r, "c"),
        "c_phone": _nstr(xp, seed, T_C, r, 12, 16, 4),
        "c_since": xp.full(r.shape, LOAD_DATE),
        "c_credit": xp.where(bc, _pack(xp, xp.asarray(
            np.frombuffer(b"BC\0\0", np.uint8)))[0],
            _pack(xp, xp.asarray(np.frombuffer(b"GC\0\0", np.uint8)))[0]),
        "c_credit_lim": xp.full(r.shape, 5_000_000),
        "c_discount": _uni(xp, seed, T_C, r, 16, 0, 5000),
        "c_balance": xp.full(r.shape, -1000),
        "c_ytd_payment": _ytd(xp, xp.full(r.shape, 1000)),
        "c_payment_cnt": xp.full(r.shape, 1),
        "c_delivery_cnt": xp.zeros(r.shape, xp.int32),
        "c_data": _astr(xp, seed, T_C, r, 20, 300, 500, 125),
        "c_last_o_id": last_o.reshape(-1)})


def stock_lanes(xp, sc, seed, w):
    """STOCK rows of warehouse ``w`` (0-based)."""
    i = xp.arange(sc.items)
    r = w * sc.items + i
    dists = {f"s_dist_{k:02d}": _astr(xp, seed, T_S, r, 3 + k, 24, 24, 6)
             for k in range(1, 11)}
    return _put(xp, i.shape, WIDTH, S_F, {
        "s_i_id": i + 1, "s_w_id": xp.full(i.shape, w + 1),
        "s_quantity": _uni(xp, seed, T_S, r, 3, 10, 100), **dists,
        "s_data": _astr(xp, seed, T_S, r, 17, 26, 50, 13,
                        original=True)})


def item_fields(xp, sc, seed, i):
    """ITEM fields of 0-based items ``i``."""
    return {"i_im_id": _uni(xp, seed, T_I, i, 2, 1, 10000),
            "i_price": _uni(xp, seed, T_I, i, 4, 100, 10000),
            "i_name": _astr(xp, seed, T_I, i, 3, 14, 24, 6),
            "i_data": _astr(xp, seed, T_I, i, 5, 26, 50, 13,
                            original=True)}


def item_lanes(xp, sc, seed):
    n = sc.items // ITEMS_PER_LINE + 1
    i = xp.arange(n * ITEMS_PER_LINE)
    rows = _put(xp, i.shape, I_LANES + 1, I_F,
                item_fields(xp, sc, seed, i))[:, 1:]
    rows = xp.where((i < sc.items)[:, None], rows, 0)
    lanes = rows.reshape(n, ITEMS_PER_LINE * I_LANES)
    return xp.concatenate([xp.zeros((n, 1), xp.int32), lanes,
                           xp.zeros((n, WIDTH - 1 - lanes.shape[1]),
                                    xp.int32)], axis=1)


def order_lanes(xp, sc, seed):
    """[wd * ring, W] lanes of the order rings."""
    perm, _ = _order_customer(xp, sc, seed)
    wd = xp.arange(sc.wd)[:, None]
    o = xp.arange(sc.orders)[None, :]                  # o_id - 1
    row = wd * sc.orders + o
    delivered = o < sc.orders - sc.new_orders
    olc = _uni(xp, seed, T_O, row, 7, 5, MAX_OL)
    blocks = [_put(xp, row.shape, O_HDR, O_F, {
        "o_id": o + 1, "o_d_id": wd % sc.districts + 1,
        "o_w_id": wd // sc.districts + 1, "o_c_id": perm + 1,
        "o_entry_d": xp.full(row.shape, LOAD_DATE),
        "o_carrier_id": xp.where(delivered,
                                 _uni(xp, seed, T_O, row, 6, 1, 10), 0),
        "o_ol_cnt": olc, "o_all_local": xp.ones(row.shape, xp.int32),
        "no_o_id": xp.where(delivered, 0, o + 1)})]
    for k in range(MAX_OL):
        f = 16 + 8 * k
        ols = _put(xp, row.shape, OL_LANES + 1, OL_F, {
            "ol_o_id": o + 1, "ol_d_id": wd % sc.districts + 1,
            "ol_w_id": wd // sc.districts + 1,
            "ol_number": xp.full(row.shape, k + 1),
            "ol_i_id": _uni(xp, seed, T_O, row, f, 1, sc.items),
            "ol_supply_w_id": wd // sc.districts + 1,
            "ol_delivery_d": xp.where(delivered, LOAD_DATE, 0),
            "ol_quantity": xp.full(row.shape, 5),
            "ol_amount": xp.where(delivered, 0, _uni(
                xp, seed, T_O, row, f + 1, 1, 999_999)),
            "ol_dist_info": _astr(xp, seed, T_O, row, f + 2, 24, 24, 6)})
        blocks.append(xp.where((k < olc)[..., None], ols[..., 1:], 0))
    blocks.append(xp.zeros(row.shape + (WIDTH - O_HDR - MAX_OL * OL_LANES,),
                           xp.int32))
    lanes = xp.concatenate(
        [xp.concatenate(blocks, axis=-1),
         xp.zeros((sc.wd, sc.ring - sc.orders, WIDTH), xp.int32)], axis=1)
    return lanes.reshape(sc.wd * sc.ring, WIDTH)


def history_lanes(xp, sc, seed):
    """[wd * history_lines, W]: one row per customer, clause 4.3.3.1."""
    nrow = sc.history_lines * HIST_PER_LINE
    wd = xp.arange(sc.wd)[:, None]
    r = xp.arange(nrow)[None, :]
    row = wd * sc.customers + r
    w1, d1 = wd // sc.districts + 1, wd % sc.districts + 1
    rows = _put(xp, row.shape, H_LANES + 1, H_F, {
        "h_c_id": r + 1, "h_c_wd": (w1 << 16) | d1, "h_wd": (w1 << 16) | d1,
        "h_date": xp.full(row.shape, LOAD_DATE),
        "h_amount": xp.full(row.shape, 1000),
        "h_data": _astr(xp, seed, T_H, row, 8, 12, 24, 6)})[..., 1:]
    rows = xp.where((r < sc.customers)[..., None], rows, 0)
    lanes = rows.reshape(sc.wd * sc.history_lines,
                         HIST_PER_LINE * H_LANES)
    return xp.concatenate(
        [xp.zeros((lanes.shape[0], 1), xp.int32), lanes,
         xp.zeros((lanes.shape[0], WIDTH - 1 - lanes.shape[1]), xp.int32)],
        axis=1)


def population(xp, sc, seed):
    """Every table's lanes, in line order, as ``(first_line, lanes)``."""
    b = sc.base
    out = [(b["W"], warehouse_lanes(xp, sc, seed)),
           (b["D"], district_lanes(xp, sc, seed)),
           (b["C"], customer_lanes(xp, sc, seed))]
    out += [(b["S"] + w * sc.items, stock_lanes(xp, sc, seed, w))
            for w in range(sc.warehouses)]
    out += [(b["H"], history_lanes(xp, sc, seed)),
            (b["I"], item_lanes(xp, sc, seed)),
            (b["O"], order_lanes(xp, sc, seed))]
    return out


@functools.lru_cache(maxsize=None)
def build_fn(sc: Scale, *, n_nodes: int, write_back: bool = True):
    """The jitted state build: seed (uint32) -> a fresh plane state
    holding the whole population (node caches empty)."""
    import jax
    import jax.numpy as jnp

    from ..core.rounds import make_state
    sc.check()

    def build(s):
        st = dict(make_state(n_nodes, sc.n_lines, write_back=write_back,
                             payload_width=WIDTH))
        mem = st["mem_data"]
        for first, lanes in population(jnp, sc, s):
            mem = jax.lax.dynamic_update_slice(mem, lanes, (first, 0))
        st["mem_data"] = mem
        return st
    return jax.jit(build)


def build_state(sc: Scale, seed32: int, *, n_nodes: int,
                write_back: bool = True, device=None):
    """A fresh plane state built on ``device`` in one jitted call."""
    import jax
    import jax.numpy as jnp
    s = jnp.uint32(seed32)
    if device is not None:
        s = jax.device_put(s, device)
    return build_fn(sc, n_nodes=n_nodes, write_back=write_back)(s)


# ------------------------------------------------------- decoding lanes

def unpack(lanes) -> bytes:
    """Lanes of a string field -> its bytes (zero padding dropped)."""
    return np.asarray(lanes, np.int32).astype("<i4").tobytes().rstrip(
        b"\0")


MONEY2 = ("w_ytd", "d_ytd", "c_ytd_payment")
STR1 = ("w_state", "d_state", "c_state", "c_middle", "c_credit")


def _fields(lanes, off, start=0):
    row = {}
    for name, (at, n) in off.items():
        v = lanes[start + at:start + at + n]
        if name in MONEY2:
            row[name] = int(v[0]) * YTD_BASE + int(v[1])
        elif n == 1 and name not in STR1:
            row[name] = int(v[0])
        else:
            row[name] = unpack(v)
    return row


def decode(sc: Scale, line: int, lanes) -> dict:
    """The rows one line holds, as the reference keys them: ``{(table,
    key): {field: value}}`` (strings as bytes, money in cents)."""
    lanes = np.asarray(lanes)
    b, nd = sc.base, sc.districts
    if line < b["D"]:
        return {("W", (line + 1,)): _fields(lanes, W_F)}
    if line < b["C"]:
        k = line - b["D"]
        return {("D", (k // nd + 1, k % nd + 1)): _fields(lanes, D_F)}
    if line < b["S"]:
        wd, c = divmod(line - b["C"], sc.customers)
        return {("C", (wd // nd + 1, wd % nd + 1, c + 1)):
                _fields(lanes, C_F)}
    if line < b["H"]:
        r = _fields(lanes, S_F)
        k = line - b["S"]
        return {("S", (k // sc.items + 1, k % sc.items + 1)): r}
    if line < b["I"]:
        k = line - b["H"]
        wd, hl = divmod(k, sc.history_lines)
        out = {}
        for s in range(HIST_PER_LINE):
            r = _fields(lanes, H_F, s * H_LANES)
            cwd, hwd = r.pop("h_c_wd"), r.pop("h_wd")
            r.update(h_c_w_id=cwd >> 16, h_c_d_id=cwd & 0xFFFF,
                     h_w_id=hwd >> 16, h_d_id=hwd & 0xFFFF)
            out[("H", (wd // sc.districts + 1, wd % sc.districts + 1,
                       hl * HIST_PER_LINE + s))] = r
        return out
    if line < b["O"]:
        out = {}
        for s in range(ITEMS_PER_LINE):
            i = (line - b["I"]) * ITEMS_PER_LINE + s
            if i < sc.items:
                out[("I", (i + 1,))] = _fields(lanes, I_F, s * I_LANES)
        return out
    k = line - b["O"]
    wd, slot = divmod(k, sc.ring)
    r = _fields(lanes, O_F)
    r["lines"] = [_fields(lanes, OL_F, O_HDR - 1 + j * OL_LANES)
                  for j in range(MAX_OL)]
    return {("O", (wd // sc.districts + 1, wd % sc.districts + 1,
                   slot)): r}


# --------------------------------------------------------- the program

def _get(lanes, e, off, name):
    at, n = off[name]
    v = lanes[:, e, at:at + n]
    return v[:, 0] if n == 1 else v


def _set(new, e, off, name, v, where):
    """``new[:, e, field] = v`` on the rows where ``where``."""
    import jax.numpy as jnp
    at, n = off[name]
    v = jnp.asarray(v, jnp.int32)
    if v.ndim == 1:
        v = v[:, None]
    cur = new[:, e, at:at + n]
    return new.at[:, e, at:at + n].set(jnp.where(where[:, None], v, cur))


def _add_ytd(v, amount):
    import jax.numpy as jnp
    lo = v[:, 1] + amount
    return jnp.stack([v[:, 0] + lo // YTD_BASE, lo % YTD_BASE], axis=1)


def _scale_by(a, k):
    """floor(a * k / 10**4) for a < 2**31 / 10**4 * 10**4, k <= 2 * 10**4,
    without leaving int32."""
    return (a // 10000) * k + ((a % 10000) * k) // 10000


class TpccProgram(TxnProgram):
    """The five TPC-C transaction types as a :class:`TxnProgram`.

    Entries (M = 321) per type, by phase:

    * NewOrder (depth 2): 0 W (S), 1 D (X), 2 C (X), 3..17 STOCK (X),
      18..32 the distinct ITEM lines (no lock); then 33 its ORDER slot
      at ``d_next_o_id`` (X), unless an item is unused (rollback);
    * Payment (2): 0 W, 1 D, 2 C (X); then 3 the HISTORY line at the
      district's cursor (X);
    * OrderStatus (2): 0 C (S); then 1 the customer's last ORDER (S);
    * Delivery (3): 0..9 the districts (X); then 10..19 each one's
      oldest undelivered ORDER (X); then 20..29 their customers (X);
    * StockLevel (3): 0 D (S); then 1..20 its last 20 ORDERs (S); then
      21..320 the distinct STOCK rows of their lines (S).

    Inputs (``inp``, ids 1-based): NewOrder ``w, d, c, ol_cnt, date,
    all_local, (i_id, supply_w, qty) x 15``; Payment ``w, d, c_w, c_d,
    c_id, amount, date``; OrderStatus ``w, d, c_id``; Delivery ``w,
    carrier, date``; StockLevel ``w, d, threshold``.  Outputs:
    NewOrder ``o_id, total, rolled_back, ring_overrun``; Payment
    ``c_id, c_balance``; OrderStatus ``c_id, c_balance, o_id, entry_d,
    carrier, ol_cnt, (i_id, supply_w, qty, amount, delivery_d) x 15``;
    Delivery the delivered ``o_id`` per district (0: none);
    StockLevel the low-stock count."""

    name = "tpcc"
    entries = ENTRIES
    inputs = INPUTS
    outputs = OUTPUTS
    depths = (2, 2, 2, 3, 3)
    width = WIDTH

    def __init__(self, scale: Scale):
        scale.check()
        self.scale = scale
        if scale.districts * 3 > ENTRIES:
            raise ValueError("too many districts for Delivery's entries")

    def encode(self, typ, inp, lines, mode):
        typ, inp = np.asarray(typ, np.int32), np.asarray(inp, np.int32)
        lines, mode = np.asarray(lines, np.int32), np.asarray(mode, np.int32)
        B = typ.shape[0]
        if inp.shape != (B, INPUTS) or lines.shape != (B, ENTRIES) \
                or mode.shape != lines.shape:
            raise ValueError("TPC-C batch arrays have the wrong shape")
        return typ, inp, lines, mode

    # ---------------------------------------------------------- phases
    def plan(self, phase, typ, inp, lines, mode, lanes):
        import jax.numpy as jnp
        sc = self.scale
        w0, d0 = inp[:, 0] - 1, inp[:, 1] - 1
        t_lines, t_mode = lines, mode

        def fill(cond, e0, ln, md):
            nonlocal t_lines, t_mode
            k = ln.shape[1]
            ok = cond[:, None] & (ln >= 0)
            t_lines = t_lines.at[:, e0:e0 + k].set(
                jnp.where(ok, ln, t_lines[:, e0:e0 + k]))
            t_mode = t_mode.at[:, e0:e0 + k].set(
                jnp.where(ok, md, t_mode[:, e0:e0 + k]))

        p0 = phase == 0
        # NewOrder: its ORDER slot, unless it rolls back
        o_id = _get(lanes, 1, D_F, "d_next_o_id")
        fill((typ == NO) & p0 & ~self._rollback(inp, lines, lanes), 33,
             line_o(sc, w0, d0, o_id)[:, None], EXCL)
        # Payment: the history line at the district's cursor
        cur = _get(lanes, 1, D_F, "d_hist_next")
        fill((typ == PAY) & p0, 3, line_h(sc, w0, d0, cur)[:, None], EXCL)
        # OrderStatus: the customer's last order
        fill((typ == OS) & p0, 1, line_o(
            sc, w0, d0, _get(lanes, 0, C_F, "c_last_o_id"))[:, None],
            SHARED)
        # Delivery: each district's oldest undelivered order, then its
        # customer
        dd = jnp.arange(sc.districts)
        nd = lanes[:, :sc.districts, D_F["d_next_deliver"][0]]
        nxt = lanes[:, :sc.districts, D_F["d_next_o_id"][0]]
        fill((typ == DEL) & p0, 10, jnp.where(
            nd < nxt, line_o(sc, w0[:, None], dd[None, :], nd), -1), EXCL)
        oc = lanes[:, 10:10 + sc.districts, O_F["o_c_id"][0]]
        fill((typ == DEL) & (phase == 1), 20, jnp.where(
            lines[:, 10:10 + sc.districts] >= 0,
            line_c(sc, w0[:, None], dd[None, :], oc - 1), -1), EXCL)
        # StockLevel: the last 20 orders, then their items' stock
        sl_next = _get(lanes, 0, D_F, "d_next_o_id")
        ol = sl_next[:, None] - SL_ORDERS + jnp.arange(SL_ORDERS)[None, :]
        fill((typ == SL) & p0, 1, jnp.where(
            ol >= 1, line_o(sc, w0[:, None], d0[:, None], ol), -1), SHARED)
        fill((typ == SL) & (phase == 1), 21, self._sl_stock(lines, lanes,
                                                            w0), SHARED)
        return t_lines, t_mode

    def _item_lanes(self, inp, lines, lanes):
        """Per order line k: its item found [B, 15], and its price."""
        import jax.numpy as jnp
        sc = self.scale
        iid = inp[:, 6:6 + 3 * MAX_OL:3] - 1                  # [B, 15]
        want = line_i(sc, iid)
        ent = jnp.argmax(lines[:, None, 18:33] == want[:, :, None], axis=2)
        il = jnp.take_along_axis(lanes[:, 18:33, :],
                                 ent[:, :, None], axis=1)      # [B,15,W]
        base = 1 + (iid % ITEMS_PER_LINE) * I_LANES
        price = jnp.take_along_axis(il, (base + I_F["i_price"][0] - 1)
                                    [:, :, None], axis=2)[:, :, 0]
        im = jnp.take_along_axis(il, (base + I_F["i_im_id"][0] - 1)
                                 [:, :, None], axis=2)[:, :, 0]
        return im > 0, price

    def _rollback(self, inp, lines, lanes):
        import jax.numpy as jnp
        found, _ = self._item_lanes(inp, lines, lanes)
        valid = jnp.arange(MAX_OL)[None, :] < inp[:, 3:4]
        return jnp.any(valid & ~found, axis=1)

    def _sl_stock(self, lines, lanes, w0):
        import jax.numpy as jnp
        sc = self.scale
        ords = lanes[:, 1:1 + SL_ORDERS, :]
        iid = jnp.stack([ords[:, :, O_HDR + k * OL_LANES
                              + OL_F["ol_i_id"][0] - 1]
                         for k in range(MAX_OL)], axis=2)
        iid = jnp.where(lines[:, 1:1 + SL_ORDERS, None] >= 0, iid, 0)
        iid = jnp.sort(iid.reshape(iid.shape[0], -1), axis=1)
        dup = jnp.concatenate([jnp.zeros((iid.shape[0], 1), bool),
                               iid[:, 1:] == iid[:, :-1]], axis=1)
        return jnp.where((iid > 0) & ~dup, line_s(sc, w0[:, None], iid - 1),
                         -1)

    # ---------------------------------------------------------- effects
    def effect(self, typ, inp, lines, mode, lanes):
        import jax.numpy as jnp
        B = typ.shape[0]
        out = jnp.zeros((B, OUTPUTS), jnp.int32)
        new = lanes
        commit = jnp.ones(B, bool)
        for t, fn in ((NO, self._new_order), (PAY, self._payment),
                      (OS, self._order_status), (DEL, self._delivery),
                      (SL, self._stock_level)):
            on = typ == t
            c_t, new, o_t = fn(on, inp, lines, lanes, new)
            commit = jnp.where(on, c_t, commit)
            out = jnp.where(on[:, None], o_t, out)
        return commit, new, out

    @staticmethod
    def _out(*cols):
        import jax.numpy as jnp
        cols = [jnp.asarray(c, jnp.int32) for c in cols]
        cols = [c[:, None] if c.ndim == 1 else c for c in cols]
        o = jnp.concatenate(cols, axis=1)
        return jnp.pad(o, ((0, 0), (0, OUTPUTS - o.shape[1])))

    def _new_order(self, on, inp, lines, lanes, new):
        import jax.numpy as jnp
        sc = self.scale
        B = on.shape[0]
        w, d, c, olc, date = (inp[:, k] for k in range(5))
        k15 = jnp.arange(MAX_OL)[None, :]
        valid = k15 < olc[:, None]
        iid = inp[:, 6:6 + 3 * MAX_OL:3]
        sw = inp[:, 7:7 + 3 * MAX_OL:3]
        qty = inp[:, 8:8 + 3 * MAX_OL:3]
        found, price = self._item_lanes(inp, lines, lanes)
        rb = jnp.any(valid & ~found, axis=1)
        go = on & ~rb
        o_id = _get(lanes, 1, D_F, "d_next_o_id")
        new = _set(new, 1, D_F, "d_next_o_id", o_id + 1, go)
        new = _set(new, 2, C_F, "c_last_o_id", o_id, go)
        amount = jnp.where(valid, qty * price, 0)
        dist_at = S_F["s_dist_01"][0] + 6 * (d - 1)
        order = jnp.zeros((B, lanes.shape[2]), jnp.int32)
        hdr = {"o_id": o_id, "o_d_id": d, "o_w_id": w, "o_c_id": c,
               "o_entry_d": date, "o_carrier_id": 0, "o_ol_cnt": olc,
               "o_all_local": inp[:, 5], "no_o_id": o_id}
        for name, v in hdr.items():
            order = order.at[:, O_F[name][0]].set(v)
        for k in range(MAX_OL):
            e = 3 + k
            vk = go & valid[:, k]
            sq = _get(lanes, e, S_F, "s_quantity")
            nq = jnp.where(sq - qty[:, k] >= 10, sq - qty[:, k],
                           sq - qty[:, k] + 91)
            new = _set(new, e, S_F, "s_quantity", nq, vk)
            new = _set(new, e, S_F, "s_ytd",
                       _get(lanes, e, S_F, "s_ytd") + qty[:, k], vk)
            new = _set(new, e, S_F, "s_order_cnt",
                       _get(lanes, e, S_F, "s_order_cnt") + 1, vk)
            new = _set(new, e, S_F, "s_remote_cnt",
                       _get(lanes, e, S_F, "s_remote_cnt")
                       + (sw[:, k] != w), vk)
            dist = jnp.take_along_axis(
                lanes[:, e, :], dist_at[:, None] + jnp.arange(6)[None, :],
                axis=1)
            at = O_HDR + k * OL_LANES - 1
            vals = {"ol_o_id": o_id, "ol_d_id": d, "ol_w_id": w,
                    "ol_number": jnp.full(B, k + 1), "ol_i_id": iid[:, k],
                    "ol_supply_w_id": sw[:, k], "ol_delivery_d": 0,
                    "ol_quantity": qty[:, k], "ol_amount": amount[:, k]}
            for name, v in vals.items():
                order = order.at[:, at + OL_F[name][0]].set(
                    jnp.where(valid[:, k], v, 0))
            a0 = at + OL_F["ol_dist_info"][0]
            order = order.at[:, a0:a0 + 6].set(
                jnp.where(valid[:, k:k + 1], dist, 0))
        overrun = _get(lanes, 33, O_F, "no_o_id") != 0
        new = new.at[:, 33, 1:].set(jnp.where(go[:, None], order[:, 1:],
                                              new[:, 33, 1:]))
        total = jnp.sum(amount, axis=1)
        total = _scale_by(total, 10000 - _get(lanes, 2, C_F, "c_discount"))
        total = _scale_by(total, 10000 + _get(lanes, 0, W_F, "w_tax")
                          + _get(lanes, 1, D_F, "d_tax"))
        return ~rb, new, self._out(o_id, jnp.where(rb, 0, total), rb,
                                   go & overrun)

    def _payment(self, on, inp, lines, lanes, new):
        import jax.numpy as jnp
        w, d, c_w, c_d, c_id, amt, date = (inp[:, k] for k in range(7))
        new = _set(new, 0, W_F, "w_ytd",
                   _add_ytd(_get(lanes, 0, W_F, "w_ytd"), amt), on)
        new = _set(new, 1, D_F, "d_ytd",
                   _add_ytd(_get(lanes, 1, D_F, "d_ytd"), amt), on)
        cur = _get(lanes, 1, D_F, "d_hist_next")
        new = _set(new, 1, D_F, "d_hist_next", cur + 1, on)
        bal = _get(lanes, 2, C_F, "c_balance") - amt
        new = _set(new, 2, C_F, "c_balance", bal, on)
        new = _set(new, 2, C_F, "c_ytd_payment",
                   _add_ytd(_get(lanes, 2, C_F, "c_ytd_payment"), amt), on)
        new = _set(new, 2, C_F, "c_payment_cnt",
                   _get(lanes, 2, C_F, "c_payment_cnt") + 1, on)
        bc = _get(lanes, 2, C_F, "c_credit") == 0x4342    # b"BC"
        old = _get(lanes, 2, C_F, "c_data")
        head = jnp.stack([c_id, c_d, c_w, d, w, amt], axis=1)
        new = _set(new, 2, C_F, "c_data",
                   jnp.concatenate([head, old[:, :-6]], axis=1), on & bc)
        slot = cur % HIST_PER_LINE
        row = jnp.concatenate([jnp.stack(
            [c_id, (c_w << 16) | c_d, (w << 16) | d, date, amt], axis=1),
            _get(lanes, 0, W_F, "w_name"), _get(lanes, 1, D_F, "d_name")],
            axis=1)                                        # [B, 11]
        lane = jnp.arange(lanes.shape[2])[None, :]
        rel = lane - 1 - slot[:, None] * H_LANES
        inside = (rel >= 0) & (rel < H_LANES)
        hist = jnp.where(inside, jnp.take_along_axis(
            row, jnp.clip(rel, 0, H_LANES - 1), axis=1), new[:, 3, :])
        new = new.at[:, 3, :].set(jnp.where(on[:, None], hist, new[:, 3, :]))
        return jnp.ones_like(on), new, self._out(c_id, bal)

    def _order_lines(self, order):
        """[B, W] ORDER lanes -> [B, 15, 5] (i_id, supply_w, qty, amount,
        delivery_d)."""
        import jax.numpy as jnp
        cols = ("ol_i_id", "ol_supply_w_id", "ol_quantity", "ol_amount",
                "ol_delivery_d")
        return jnp.stack([jnp.stack(
            [order[:, O_HDR + k * OL_LANES + OL_F[n][0] - 1] for n in cols],
            axis=1) for k in range(MAX_OL)], axis=1)

    def _order_status(self, on, inp, lines, lanes, new):
        import jax.numpy as jnp
        o = lanes[:, 1, :]
        hdr = [o[:, O_F[n][0]] for n in ("o_id", "o_entry_d",
                                          "o_carrier_id", "o_ol_cnt")]
        return jnp.ones_like(on), new, self._out(
            inp[:, 2], _get(lanes, 0, C_F, "c_balance"), *hdr,
            self._order_lines(o).reshape(o.shape[0], -1))

    def _delivery(self, on, inp, lines, lanes, new):
        import jax.numpy as jnp
        sc = self.scale
        carrier, date = inp[:, 1], inp[:, 2]
        o_ids = []
        for k in range(sc.districts):
            did = on & (lines[:, 10 + k] >= 0)
            eo, ec = 10 + k, 20 + k
            o = lanes[:, eo, :]
            o_id = o[:, O_F["o_id"][0]]
            o_ids.append(jnp.where(did, o_id, 0))
            new = _set(new, k, D_F, "d_next_deliver", o_id + 1, did)
            new = _set(new, eo, O_F, "o_carrier_id", carrier, did)
            new = _set(new, eo, O_F, "no_o_id", 0, did)
            olc = o[:, O_F["o_ol_cnt"][0]]
            total = jnp.zeros_like(o_id)
            for j in range(MAX_OL):
                at = O_HDR + j * OL_LANES + OL_F["ol_delivery_d"][0] - 1
                vj = did & (j < olc)
                new = new.at[:, eo, at].set(jnp.where(vj, date,
                                                      new[:, eo, at]))
                total = total + jnp.where(
                    vj, o[:, O_HDR + j * OL_LANES + OL_F["ol_amount"][0]
                          - 1], 0)
            new = _set(new, ec, C_F, "c_balance",
                       _get(lanes, ec, C_F, "c_balance") + total, did)
            new = _set(new, ec, C_F, "c_delivery_cnt",
                       _get(lanes, ec, C_F, "c_delivery_cnt") + 1, did)
        return jnp.ones_like(on), new, self._out(jnp.stack(o_ids, axis=1))

    def _stock_level(self, on, inp, lines, lanes, new):
        import jax.numpy as jnp
        ent = slice(21, ENTRIES)
        q = lanes[:, ent, S_F["s_quantity"][0]]
        low = (lines[:, ent] >= 0) & (q < inp[:, 2:3])
        return jnp.ones_like(on), new, self._out(
            jnp.sum(low.astype(jnp.int32), axis=1))


@functools.lru_cache(maxsize=None)
def program(scale: Scale) -> TpccProgram:
    """The (cached, so jit-stable) program of ``scale``."""
    return TpccProgram(scale)


# ------------------------------------------------------------ terminals

@dataclass
class Txn:
    """One drawn transaction: its type, node, inputs and phase-0
    entries, plus what the reference needs (``args``)."""

    typ: int
    node: int
    inp: np.ndarray
    lines: np.ndarray
    mode: np.ndarray
    args: dict


class Terminals:
    """Clause 4.2's terminals, ``per_warehouse`` for each warehouse:
    terminal ``t`` has home warehouse ``t // per_warehouse``, a fixed
    StockLevel district, and runs on node ``t % n_nodes``; each batch
    draws one transaction per terminal by clause 5.2.3's mix, with the
    profiles' input rules (clauses 2.4.1, 2.5.1, 2.6.1, 2.7.1, 2.8.1),
    whose percentages are the keyword arguments.  The same seed gives the
    same batches."""

    def __init__(self, scale: Scale, seed32: int, *, n_nodes: int,
                 per_warehouse: int = 10, mix=MIX, remote_item_pct=1,
                 remote_payment_pct=15, by_last_name_pct=60,
                 rollback_pct=1, ol_cnt=(5, MAX_OL), stream: int = 0):
        self.sc = scale
        self.cum_mix = np.cumsum(np.asarray(mix, np.float64) / sum(mix))
        self.pct = dict(remote_item=remote_item_pct,
                        remote_payment=remote_payment_pct,
                        by_last_name=by_last_name_pct,
                        rollback=rollback_pct)
        self.ol_cnt = tuple(ol_cnt)
        self.seed = int(seed32)
        self.n_nodes = n_nodes
        self.n = scale.warehouses * per_warehouse
        self.per_wh = per_warehouse
        self.rng = np.random.default_rng([self.seed, stream])
        self.c = nurand_consts(self.seed)
        self.batches = 0
        self._index = None

    # last-name index: (w, d, name number) -> c_ids sorted by c_first
    def by_name(self, w, d, n):
        if self._index is None:
            sc = self.sc
            rows = np.arange(sc.wd * sc.customers)
            c = rows % sc.customers
            last = customer_last(np, sc, self.seed, rows, c)
            first = customer_first(np, self.seed, rows)
            key = [unpack(f) for f in first]
            idx: dict = {}
            for r in sorted(rows, key=lambda r: (key[r], c[r])):
                wd = r // sc.customers
                idx.setdefault((wd // sc.districts, wd % sc.districts,
                                int(last[r])), []).append(int(c[r]) + 1)
            self._index = idx
        return self._index.get((w, d, n), [])

    def _u(self, lo, hi):
        return int(self.rng.integers(lo, hi + 1))

    def _nurand(self, a, x, y, c):
        return _nurand(self._u(0, a), self._u(x, y), a, x, y, c)

    def _customer(self, w, d):
        """``(c_id, who)``: 60% by last name (clause 2.5.1.2), ``who``
        the reference's arguments naming the customer."""
        sc = self.sc
        if self._u(1, 100) <= self.pct["by_last_name"]:
            names = min(1000, sc.customers)
            n = self._nurand(255, 0, 999, self.c["c_last_run"]) % names
            ids = self.by_name(w, d, n)
            return ids[(len(ids) + 1) // 2 - 1], {"c_last": bytes(
                last_names()[n][last_names()[n] > 0].astype(np.uint8))}
        c = self._nurand(self._a(1023, sc.customers), 1, sc.customers,
                         self.c["c_id"])
        return c, {"c": c}

    @staticmethod
    def _a(a, n):
        """NURand's A, cut with a table smaller than the spec's."""
        return min(a, (1 << (n.bit_length() - 2)) - 1)

    def _other_wh(self, w):
        o = self._u(0, self.sc.warehouses - 2)
        return o + (o >= w)

    def next(self):
        """``(txns, (node, typ, inp, lines, mode))`` of the next batch."""
        txns = [self._draw(t) for t in range(self.n)]
        self.batches += 1
        node = np.array([t.node for t in txns], np.int32)
        arrays = (node, np.array([t.typ for t in txns], np.int32),
                  np.stack([t.inp for t in txns]),
                  np.stack([t.lines for t in txns]),
                  np.stack([t.mode for t in txns]))
        return txns, arrays

    def _draw(self, term):
        sc = self.sc
        w = term // self.per_wh
        typ = int(np.searchsorted(self.cum_mix, self.rng.random(),
                                  side="right"))
        typ = min(typ, SL)
        inp = np.zeros(INPUTS, np.int32)
        lines = np.full(ENTRIES, -1, np.int32)
        mode = np.zeros(ENTRIES, np.int32)
        date = self.batches * self.n + term + 2
        multi = sc.warehouses > 1
        if typ == NO:
            d = self._u(0, sc.districts - 1)
            c = self._nurand(self._a(1023, sc.customers), 1, sc.customers,
                             self.c["c_id"])
            olc = self._u(*self.ol_cnt)
            rbk = self._u(1, 100) <= self.pct["rollback"]
            items, sws, qtys = [], [], []
            for k in range(olc):
                while True:
                    i = self._nurand(self._a(8191, sc.items), 1, sc.items,
                                     self.c["ol_i_id"])
                    if i not in items:
                        break
                if rbk and k == olc - 1:
                    i = sc.items + 1                   # an unused item
                items.append(i)
                sws.append(self._other_wh(w) if multi and self._u(1, 100)
                           <= self.pct["remote_item"] else w)
                qtys.append(self._u(1, 10))
            all_local = int(all(s == w for s in sws))
            inp[:6] = [w + 1, d + 1, c, olc, date, all_local]
            for k in range(olc):
                inp[6 + 3 * k:9 + 3 * k] = [items[k], sws[k] + 1, qtys[k]]
            lines[:3] = [line_w(sc, w), line_d(sc, w, d),
                         line_c(sc, w, d, c - 1)]
            mode[:3] = [SHARED, EXCL, EXCL]
            for k in range(olc):
                if items[k] <= sc.items:
                    lines[3 + k] = line_s(sc, sws[k], items[k] - 1)
                    mode[3 + k] = EXCL
            il = sorted({line_i(sc, i - 1) for i in items})
            lines[18:18 + len(il)] = il
            args = dict(w=w + 1, d=d + 1, c=c, date=date,
                        items=[(items[k], sws[k] + 1, qtys[k])
                               for k in range(olc)])
        elif typ == PAY:
            d = self._u(0, sc.districts - 1)
            if multi and self._u(1, 100) <= self.pct["remote_payment"]:
                cw, cd = self._other_wh(w), self._u(0, sc.districts - 1)
            else:
                cw, cd = w, d
            c, who = self._customer(cw, cd)
            amt = self._u(100, 500_000)
            inp[:7] = [w + 1, d + 1, cw + 1, cd + 1, c, amt, date]
            lines[:3] = [line_w(sc, w), line_d(sc, w, d),
                         line_c(sc, cw, cd, c - 1)]
            mode[:3] = EXCL
            args = dict(w=w + 1, d=d + 1, c_w=cw + 1, c_d=cd + 1,
                        amount=amt, date=date, **who)
        elif typ == OS:
            d = self._u(0, sc.districts - 1)
            c, who = self._customer(w, d)
            inp[:3] = [w + 1, d + 1, c]
            lines[0], mode[0] = line_c(sc, w, d, c - 1), SHARED
            args = dict(w=w + 1, d=d + 1, **who)
        elif typ == DEL:
            carrier = self._u(1, 10)
            inp[:3] = [w + 1, carrier, date]
            lines[:sc.districts] = [line_d(sc, w, d)
                                    for d in range(sc.districts)]
            mode[:sc.districts] = EXCL
            args = dict(w=w + 1, carrier=carrier, date=date)
        else:
            d = term % self.per_wh % sc.districts
            thr = self._u(10, 20)
            inp[:3] = [w + 1, d + 1, thr]
            lines[0], mode[0] = line_d(sc, w, d), SHARED
            args = dict(w=w + 1, d=d + 1, threshold=thr)
        return Txn(typ, term % self.n_nodes, inp, lines, mode, args)


def outputs(sc: Scale, typ: int, out) -> dict:
    """A transaction's output vector as the reference returns it."""
    o = [int(x) for x in out]
    if typ == NO:
        return {"o_id": o[0], "total": o[1], "rolled_back": o[2]}
    if typ == PAY:
        return {"c_id": o[0], "c_balance": o[1]}
    if typ == OS:
        return {"c_id": o[0], "c_balance": o[1], "o_id": o[2],
                "o_entry_d": o[3], "o_carrier_id": o[4], "o_ol_cnt": o[5],
                "lines": [tuple(o[6 + 5 * k:11 + 5 * k])
                          for k in range(o[5])]}
    if typ == DEL:
        return {"delivered": o[:sc.districts]}
    return {"low_stock": o[0]}
