"""A plain TPC-C, row by row in Python dicts: the reference the device
TPC-C (``apps/tpcc_device.py``) is compared with.

It knows nothing of lines, lanes, locks or schedulers, and imports
nothing of the program.  Tables are dicts keyed by the spec's primary
keys; a row is made from the seed the first time it is touched, by the
same population formulas as the device's (clause 4.3, written here a
second time: every value is a hash of ``(seed, table, row, field, k)``).
Transactions run one at a time, in the order they are given, by the
profiles of clauses 2.4 to 2.8:

    ref = TpccReference(seed32, warehouses=3)
    out = ref.run("payment", w=1, d=2, c_w=1, c_d=2, c=17,
                  amount=250000, date=9)

Money is in cents and rates in units of 1e-4, so every result is exact;
the NewOrder total floors to the cent after the discount and after the
tax.  Three representation choices follow the device's layout and are
stated, not hidden: a bad-credit Payment prepends the six numbers of
clause 2.5.2.2 as 4-byte little-endian integers to ``c_data`` (500
bytes kept), ``h_data`` is ``w_name`` and ``d_name`` each in a 12-byte
zero-padded field, and a rolled-back NewOrder returns the ``o_id`` it
would have taken.  :func:`consistency` checks clause 3.3.2.1 to 3.3.2.4
over a database image.
"""

from __future__ import annotations

import struct

import numpy as np

ALNUM = (b"0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ"
         b"abcdefghijklmnopqrstuvwxyz")
SYLLABLES = (b"BAR", b"OUGHT", b"ABLE", b"PRI", b"PRES", b"ESE", b"ANTI",
             b"CALLY", b"ATION", b"EING")
T_W, T_D, T_C, T_S, T_I, T_O, T_H, T_PERM = range(1, 9)
MASK = 0xFFFFFFFF


def _fmix(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def hash32(seed, table, row, field, k=0):
    """The population hash (uint32), vectorized over ``row`` and ``k``."""
    u = np.uint32
    with np.errstate(over="ignore"):
        x = _fmix(np.asarray(seed).astype(u)
                  ^ u((table * 0x9E3779B1) & MASK))
        x = _fmix(x ^ np.asarray(row).astype(u) * u(0x85EBCA77))
        x = _fmix(x ^ u((field * 0xC2B2AE3D) & MASK)
                  ^ np.asarray(k).astype(u) * u(0x27D4EB2F))
    return x


class _Gen:
    """Field values of one row of one table."""

    def __init__(self, seed, table, row):
        self.seed, self.table, self.row = seed, table, row

    def h(self, field, k=0):
        return hash32(self.seed, self.table, self.row, field, k)

    def uni(self, field, lo, hi, k=0):
        return int(self.h(field, k) % np.uint32(hi - lo + 1)) + lo

    def astr(self, field, lo, hi, original=False) -> bytes:
        n = self.uni(field, lo, hi, 0xFFFF)
        pos = np.arange(n)
        ch = bytearray(np.frombuffer(ALNUM, np.uint8)[
            (self.h(field, pos) % np.uint32(62)).astype(np.int64)])
        if original and self.uni(field, 1, 100, 0xFFFE) <= 10:
            at = int(self.h(field, 0xFFFD) % np.uint32(n - 7))
            ch[at:at + 8] = b"ORIGINAL"
        return bytes(ch)

    def nstr(self, field, digits, suffix=b"") -> bytes:
        d = self.h(field, np.arange(digits)) % np.uint32(10) + 48
        return bytes(d.astype(np.uint8)) + suffix


def _address(g, prefix):
    return {f"{prefix}_street_1": g.astr(40, 10, 20),
            f"{prefix}_street_2": g.astr(41, 10, 20),
            f"{prefix}_city": g.astr(42, 10, 20),
            f"{prefix}_state": g.astr(43, 2, 2),
            f"{prefix}_zip": g.nstr(44, 4, b"11111")}


def _nurand(a_val, xy_val, a, x, y, c):
    return ((a_val | xy_val) + c) % (y - x + 1) + x


def last_name(n: int) -> bytes:
    return SYLLABLES[n // 100] + SYLLABLES[n // 10 % 10] + SYLLABLES[n % 10]


class TpccReference:
    """TPC-C tables in dicts, populated lazily from ``seed``, at the
    given cardinalities (clause 4.3's by default)."""

    def __init__(self, seed, *, warehouses=3, districts=10,
                 customers=3000, items=100_000, new_orders=900):
        self.seed = int(seed)
        self.nw, self.nd, self.nc = warehouses, districts, customers
        self.ni, self.n_no = items, new_orders
        self.rows: dict = {}          # (table, key) -> row dict
        self._no: dict = {}           # (w, d) -> set of undelivered o_id
        self.last_order: dict = {}    # (w, d, c) -> the customer's last o_id
        self.history: dict = {}       # (w, d) -> rows appended by the run
        self._perm: dict = {}
        self._names: dict = {}
        self.c_last_load = int(hash32(self.seed, 99, 0, 1) % np.uint32(256))

    # ------------------------------------------------------ population
    def _wd(self, w, d):
        return (w - 1) * self.nd + (d - 1)

    def perm(self, w, d):
        """0-based customer of each initial order of district (w, d)."""
        k = (w, d)
        if k not in self._perm:
            keys = hash32(self.seed, T_PERM, self._wd(w, d), 0,
                          np.arange(self.nc))
            self._perm[k] = np.argsort(keys, kind="stable")
        return self._perm[k]

    def c_last(self, w, d, c) -> bytes:
        g = _Gen(self.seed, T_C, self._wd(w, d) * self.nc + c - 1)
        if c - 1 < 1000:
            return last_name(c - 1)
        return last_name(_nurand(g.uni(21, 0, 255), g.uni(22, 0, 999),
                                 255, 0, 999, self.c_last_load))

    def _make(self, table, key):
        s = self.seed
        if table == "W":
            (w,) = key
            g = _Gen(s, T_W, w - 1)
            return {"w_id": w, "w_tax": g.uni(2, 0, 2000),
                    "w_ytd": 3_000_000 * self.nd, "w_name": g.astr(4, 6, 10),
                    **_address(g, "w")}
        if table == "D":
            w, d = key
            g = _Gen(s, T_D, self._wd(w, d))
            return {"d_id": d, "d_w_id": w, "d_tax": g.uni(3, 0, 2000),
                    "d_ytd": 3_000_000, "d_next_o_id": self.nc + 1,
                    "d_name": g.astr(6, 6, 10), **_address(g, "d")}
        if table == "C":
            w, d, c = key
            g = _Gen(s, T_C, self._wd(w, d) * self.nc + c - 1)
            return {"c_id": c, "c_d_id": d, "c_w_id": w,
                    "c_first": g.astr(3, 8, 16), "c_middle": b"OE",
                    "c_last": self.c_last(w, d, c), **_address(g, "c"),
                    "c_phone": g.nstr(12, 16), "c_since": 1,
                    "c_credit": b"BC" if g.uni(14, 1, 100) <= 10 else b"GC",
                    "c_credit_lim": 5_000_000,
                    "c_discount": g.uni(16, 0, 5000), "c_balance": -1000,
                    "c_ytd_payment": 1000, "c_payment_cnt": 1,
                    "c_delivery_cnt": 0, "c_data": g.astr(20, 300, 500)}
        if table == "S":
            w, i = key
            g = _Gen(s, T_S, (w - 1) * self.ni + i - 1)
            row = {"s_i_id": i, "s_w_id": w, "s_quantity": g.uni(3, 10, 100)}
            row.update({f"s_dist_{k:02d}": g.astr(3 + k, 24, 24)
                        for k in range(1, 11)})
            row.update({"s_ytd": 0, "s_order_cnt": 0, "s_remote_cnt": 0,
                        "s_data": g.astr(17, 26, 50, original=True)})
            return row
        if table == "I":
            (i,) = key
            if not 1 <= i <= self.ni:
                return None
            g = _Gen(s, T_I, i - 1)
            return {"i_im_id": g.uni(2, 1, 10000),
                    "i_price": g.uni(4, 100, 10000),
                    "i_name": g.astr(3, 14, 24),
                    "i_data": g.astr(5, 26, 50, original=True)}
        if table == "O":
            w, d, o = key
            if o > self.nc:
                return None
            g = _Gen(s, T_O, self._wd(w, d) * self.nc + o - 1)
            delivered = o <= self.nc - self.n_no
            olc = g.uni(7, 5, 15)
            lines = []
            for k in range(olc):
                f = 16 + 8 * k
                lines.append({
                    "ol_o_id": o, "ol_d_id": d, "ol_w_id": w,
                    "ol_number": k + 1, "ol_i_id": g.uni(f, 1, self.ni),
                    "ol_supply_w_id": w,
                    "ol_delivery_d": 1 if delivered else 0,
                    "ol_quantity": 5,
                    "ol_amount": 0 if delivered else g.uni(f + 1, 1,
                                                           999_999),
                    "ol_dist_info": g.astr(f + 2, 24, 24)})
            return {"o_id": o, "o_d_id": d, "o_w_id": w,
                    "o_c_id": int(self.perm(w, d)[o - 1]) + 1,
                    "o_entry_d": 1,
                    "o_carrier_id": g.uni(6, 1, 10) if delivered else 0,
                    "o_ol_cnt": olc, "o_all_local": 1, "lines": lines}
        raise KeyError(table)

    def row(self, table, key):
        """The row, made from the seed on first touch (None: no row)."""
        k = (table, tuple(key))
        if k not in self.rows:
            self.rows[k] = self._make(table, tuple(key))
        return self.rows[k]

    def undelivered(self, w, d) -> set:
        """NEW-ORDER of district (w, d): its undelivered o_ids."""
        if (w, d) not in self._no:
            self._no[(w, d)] = set(range(self.nc - self.n_no + 1,
                                               self.nc + 1))
        return self._no[(w, d)]

    def _last_order(self, w, d, c):
        if (w, d, c) not in self.last_order:
            inv = np.argsort(self.perm(w, d), kind="stable")
            self.last_order[(w, d, c)] = int(inv[c - 1]) + 1
        return self.last_order[(w, d, c)]

    def by_last_name(self, w, d, name: bytes) -> int:
        """Clause 2.5.2.2: the customers of (w, d) called ``name``, by
        ``c_first``; the one at position n / 2 rounded up."""
        if (w, d) not in self._names:
            idx: dict = {}
            for c in range(1, self.nc + 1):
                g = _Gen(self.seed, T_C, self._wd(w, d) * self.nc + c - 1)
                idx.setdefault(self.c_last(w, d, c), []).append(
                    (g.astr(3, 8, 16), c))
            self._names[(w, d)] = {k: [c for _, c in sorted(v)]
                                   for k, v in idx.items()}
        ids = self._names[(w, d)][name]
        return ids[(len(ids) + 1) // 2 - 1]

    # ---------------------------------------------------- transactions
    def run(self, kind: str, **args) -> dict:
        return getattr(self, kind)(**args)

    def new_order(self, w, d, c, date, items):
        """Clause 2.4.2: ``items`` is ``[(i_id, supply_w, qty)]``."""
        wh, di, cu = self.row("W", (w,)), self.row("D", (w, d)), \
            self.row("C", (w, d, c))
        o_id = di["d_next_o_id"]
        found = [self.row("I", (i,)) for i, _, _ in items]
        if any(it is None for it in found):
            return {"o_id": o_id, "total": 0, "rolled_back": 1}
        di["d_next_o_id"] = o_id + 1
        lines, total = [], 0
        for k, ((i, sw, q), it) in enumerate(zip(items, found)):
            st = self.row("S", (sw, i))
            left = st["s_quantity"] - q
            st["s_quantity"] = left if left >= 10 else left + 91
            st["s_ytd"] += q
            st["s_order_cnt"] += 1
            st["s_remote_cnt"] += sw != w
            amount = q * it["i_price"]
            total += amount
            lines.append({"ol_o_id": o_id, "ol_d_id": d, "ol_w_id": w,
                          "ol_number": k + 1, "ol_i_id": i,
                          "ol_supply_w_id": sw, "ol_delivery_d": 0,
                          "ol_quantity": q, "ol_amount": amount,
                          "ol_dist_info": st[f"s_dist_{d:02d}"]})
        self.rows[("O", (w, d, o_id))] = {
            "o_id": o_id, "o_d_id": d, "o_w_id": w, "o_c_id": c,
            "o_entry_d": date, "o_carrier_id": 0, "o_ol_cnt": len(items),
            "o_all_local": int(all(sw == w for _, sw, _ in items)),
            "lines": lines}
        self.undelivered(w, d).add(o_id)
        self._last_order(w, d, c)
        self.last_order[(w, d, c)] = o_id
        total = total * (10000 - cu["c_discount"]) // 10000
        total = total * (10000 + wh["w_tax"] + di["d_tax"]) // 10000
        return {"o_id": o_id, "total": total, "rolled_back": 0}

    def payment(self, w, d, c_w, c_d, amount, date, c=None, c_last=None):
        """Clause 2.5.2: the customer by id ``c`` or by ``c_last``."""
        if c is None:
            c = self.by_last_name(c_w, c_d, c_last)
        wh, di = self.row("W", (w,)), self.row("D", (w, d))
        cu = self.row("C", (c_w, c_d, c))
        wh["w_ytd"] += amount
        di["d_ytd"] += amount
        cu["c_balance"] -= amount
        cu["c_ytd_payment"] += amount
        cu["c_payment_cnt"] += 1
        if cu["c_credit"] == b"BC":
            head = struct.pack("<6i", c, c_d, c_w, d, w, amount)
            cu["c_data"] = (head + cu["c_data"])[:500].rstrip(b"\0")
        h_data = (wh["w_name"].ljust(12, b"\0")
                  + di["d_name"].ljust(12, b"\0")).rstrip(b"\0")
        self.history.setdefault((w, d), []).append({
            "h_c_id": c, "h_c_d_id": c_d, "h_c_w_id": c_w, "h_d_id": d,
            "h_w_id": w, "h_date": date, "h_amount": amount,
            "h_data": h_data})
        return {"c_id": c, "c_balance": cu["c_balance"]}

    def order_status(self, w, d, c=None, c_last=None):
        """Clause 2.6.2: the customer by id ``c`` or by ``c_last``."""
        if c is None:
            c = self.by_last_name(w, d, c_last)
        cu = self.row("C", (w, d, c))
        o = self.row("O", (w, d, self._last_order(w, d, c)))
        return {"c_id": c, "c_balance": cu["c_balance"], "o_id": o["o_id"],
                "o_entry_d": o["o_entry_d"],
                "o_carrier_id": o["o_carrier_id"], "o_ol_cnt": o["o_ol_cnt"],
                "lines": [(ol["ol_i_id"], ol["ol_supply_w_id"],
                           ol["ol_quantity"], ol["ol_amount"],
                           ol["ol_delivery_d"]) for ol in o["lines"]]}

    def delivery(self, w, carrier, date):
        """Clause 2.7.4: the oldest undelivered order of each district."""
        out = []
        for d in range(1, self.nd + 1):
            und = self.undelivered(w, d)
            if not und:
                out.append(0)
                continue
            o_id = min(und)
            und.remove(o_id)
            o = self.row("O", (w, d, o_id))
            o["o_carrier_id"] = carrier
            total = 0
            for ol in o["lines"]:
                ol["ol_delivery_d"] = date
                total += ol["ol_amount"]
            cu = self.row("C", (w, d, o["o_c_id"]))
            cu["c_balance"] += total
            cu["c_delivery_cnt"] += 1
            out.append(o_id)
        return {"delivered": out}

    def stock_level(self, w, d, threshold):
        """Clause 2.8.2: distinct items of the district's last 20 orders
        whose stock is under ``threshold``."""
        nxt = self.row("D", (w, d))["d_next_o_id"]
        items = set()
        for o_id in range(max(1, nxt - 20), nxt):
            items.update(ol["ol_i_id"]
                         for ol in self.row("O", (w, d, o_id))["lines"])
        return {"low_stock": sum(
            self.row("S", (w, i))["s_quantity"] < threshold
            for i in items)}


def consistency(warehouses: dict, districts: dict, orders: dict) -> list:
    """Clause 3.3.2.1 to 3.3.2.4 over a database image: ``warehouses``
    ``{w: row}``, ``districts`` ``{(w, d): row}``, ``orders`` ``{(w, d):
    [(o_id, o_ol_cnt, is_new_order, order_line_rows)]}``.  Returns the
    violated conditions, as ``(condition, key)`` pairs."""
    bad = []
    for w, row in warehouses.items():
        ytd = sum(r["d_ytd"] for (dw, _), r in districts.items() if dw == w)
        if row["w_ytd"] != ytd:
            bad.append(("3.3.2.1", w))
    for key, d in districts.items():
        ords = orders.get(key, [])
        o_max = max((o[0] for o in ords), default=0)
        no = [o[0] for o in ords if o[2]]
        if d["d_next_o_id"] - 1 != o_max or (no and max(no) != o_max):
            bad.append(("3.3.2.2", key))
        if no and max(no) - min(no) + 1 != len(no):
            bad.append(("3.3.2.3", key))
        if sum(o[1] for o in ords) != sum(o[3] for o in ords):
            bad.append(("3.3.2.4", key))
    return bad
