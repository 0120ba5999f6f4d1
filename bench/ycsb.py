"""YCSB traffic for the coherence plane, and the bytes every record holds.

The key chooser follows YCSB core's ``ScrambledZipfianGenerator``
(Cooper et al., SoCC 2010; ``site.ycsb.generator``): a zipfian draw over
10^10 ranks with YCSB's precomputed zeta, hashed by ``fnvhash64`` onto
the record ids.  So the hottest records are the same for every seed and
scattered over the table, as YCSB's are; the seed only draws which ops
come in which order.

A batch is one op per client slot: ``clients_per_node`` slots per node,
slot ``i`` on node ``i // clients_per_node``, each a read or an update
drawn by the mix's proportions.  An update writes a whole line of
``payload_width`` int32 lanes whose bytes are a hash of the seed and a
write tag, so the reference can recompute them instead of storing them.
Every record starts at version 0 holding a hash of the seed and its id,
built on the device by :func:`init_lanes` and on the host by
:func:`init_bytes` from one formula.
"""

from __future__ import annotations

import numpy as np

# YCSB ScrambledZipfianGenerator: ITEM_COUNT and its zeta at theta 0.99
ITEM_COUNT = 10_000_000_000
YCSB_THETA = 0.99
YCSB_ZETAN = 26.46902820178302
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211

# domain tags, so initial bytes and written bytes never share a hash input
_INIT_TAG = 0x243F6A88
_WRITE_TAG = 0x85A308D3


def zeta(n: int, theta: float) -> float:
    """sum_{i=1..n} i^-theta: exact over the first 10^6 terms, and the
    Euler-Maclaurin tail beyond (YCSB sums the whole series)."""
    head = min(n, 1_000_000)
    s = float(np.sum(np.arange(1, head + 1, dtype=np.float64) ** -theta))
    if n > head:
        a, b = head + 1.0, float(n)
        s += ((b ** (1 - theta) - a ** (1 - theta)) / (1 - theta)
              + (a ** -theta + b ** -theta) / 2
              + theta * (a ** (-theta - 1) - b ** (-theta - 1)) / 12)
    return s


class ScrambledZipfian:
    """Vectorized YCSB scrambled zipfian over ``n_records`` ids."""

    def __init__(self, n_records: int, theta: float = YCSB_THETA):
        self.n_records = int(n_records)
        self.theta = float(theta)
        self.items = ITEM_COUNT + 1          # ZipfianGenerator(0, ITEM_COUNT)
        self.zetan = (YCSB_ZETAN if theta == YCSB_THETA
                      else zeta(self.items, theta))
        zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = ((1 - (2.0 / self.items) ** (1 - theta))
                    / (1 - zeta2 / self.zetan))

    def ranks(self, u: np.ndarray) -> np.ndarray:
        """ZipfianGenerator.nextLong for uniform draws ``u`` in [0, 1)."""
        uz = u * self.zetan
        far = np.floor(self.items * np.power(
            self.eta * u - self.eta + 1, self.alpha))
        r = np.where(uz < 1.0 + 0.5 ** self.theta, 1.0, far)
        return np.where(uz < 1.0, 0.0, r).astype(np.int64)

    def sample(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return scramble(self.ranks(rng.random(size)), self.n_records)


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1a over the 8 low-first octets,
    then ``Math.abs`` of the signed result (int64)."""
    v = np.asarray(v, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def scramble(ranks: np.ndarray, n_records: int) -> np.ndarray:
    """Rank -> record id, as ``min + fnvhash64(rank) % itemcount``."""
    return np.remainder(fnvhash64(ranks), n_records).astype(np.int32)


# ------------------------------------------------------------- record bytes

def _fmix32(x):
    """murmur3's 32-bit finalizer; works on numpy and jax uint32 arrays."""
    u32 = x.dtype.type
    x = x ^ (x >> 16)
    x = x * u32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * u32(0xC2B2AE35)
    return x ^ (x >> 16)


def seed32(seed: int) -> int:
    """The 32-bit data seed of a run's ``--seed``."""
    return int(np.random.SeedSequence(int(seed) % (1 << 64))
               .generate_state(1)[0])


def init_bytes(seed: int, lines: np.ndarray, width: int) -> np.ndarray:
    """Version-0 lanes [len(lines), width] int32 of records ``lines``."""
    s = np.uint32(seed32(seed) ^ _INIT_TAG)
    ln = np.asarray(lines, np.int64).astype(np.uint32)[:, None]
    lane = np.arange(width, dtype=np.uint32)[None, :]
    with np.errstate(over="ignore"):
        x = _fmix32(ln * np.uint32(0x9E3779B1) ^ s)
        x = _fmix32(x ^ lane * np.uint32(0x7FEB352D))
    return x.view(np.int32)


def init_lanes(seed_u32, rows, width: int):
    """The same formula as :func:`init_bytes`, traced with ``jax``:
    ``rows`` [L] record ids, ``seed_u32`` the run's seed32 as uint32."""
    import jax
    import jax.numpy as jnp
    s = seed_u32 ^ jnp.uint32(_INIT_TAG)
    lane = jnp.arange(width, dtype=jnp.uint32)[None, :]
    x = _fmix32(rows.astype(jnp.uint32)[:, None] * jnp.uint32(0x9E3779B1)
                ^ s)
    x = _fmix32(x ^ lane * jnp.uint32(0x7FEB352D))
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def write_bytes(seed: int, tags: np.ndarray, width: int) -> np.ndarray:
    """Lanes [len(tags), width] int32 an update with write tag ``tag``
    carries (tags are unique per write within a run)."""
    s = np.uint32(seed32(seed) ^ _WRITE_TAG)
    t = np.asarray(tags, np.int64).astype(np.uint32)[:, None]
    lane = np.arange(width, dtype=np.uint32)[None, :]
    with np.errstate(over="ignore"):
        x = _fmix32(t * np.uint32(0x9E3779B1) ^ s)
        x = _fmix32(x ^ lane * np.uint32(0x7FEB352D))
    return x.view(np.int32)


# ---------------------------------------------------------------- batches

class Batches:
    """One client per slot, one batch in flight: ``next()`` draws the
    next batch of the mix ``traffic`` over ``n_records`` records.

    Returns ``(node, line, is_write, wdata, tags)``: int32 [R] each,
    ``wdata`` [R, W] int32 (zeros on reads) and ``tags`` int64 [R]
    (0 on reads, else the write tag the bytes were made from)."""

    def __init__(self, traffic: dict, *, n_records: int, n_nodes: int,
                 clients_per_node: int, width: int, seed: int):
        self.read_p = float(traffic["read_proportion"])
        self.update_p = float(traffic["update_proportion"])
        if abs(self.read_p + self.update_p - 1.0) > 1e-9:
            raise ValueError("read and update proportions must sum to 1")
        dist = traffic["request_distribution"]
        if dist == "zipfian":
            self.keys = ScrambledZipfian(n_records,
                                         float(traffic["zipfian_constant"]))
        elif dist == "uniform":
            self.keys = None
        else:
            raise ValueError(f"request_distribution {dist!r} unknown")
        self.n_records = int(n_records)
        self.r = int(n_nodes) * int(clients_per_node)
        self.node = (np.arange(self.r) // int(clients_per_node)) \
            .astype(np.int32)
        self.width = int(width)
        self.seed = int(seed)
        self.rng = np.random.default_rng(int(seed) % (1 << 64))
        self.count = 0

    def next(self):
        if self.keys is None:
            line = self.rng.integers(0, self.n_records, self.r) \
                .astype(np.int32)
        else:
            line = self.keys.sample(self.rng, self.r)
        is_w = (self.rng.random(self.r) < self.update_p).astype(np.int32)
        tags = np.where(is_w == 1,
                        self.count * self.r + np.arange(self.r) + 1, 0) \
            .astype(np.int64)
        self.count += 1
        wdata = np.zeros((self.r, self.width), np.int32)
        w = np.flatnonzero(is_w)
        wdata[w] = write_bytes(self.seed, tags[w], self.width)
        return self.node, line, is_w, wdata, tags
