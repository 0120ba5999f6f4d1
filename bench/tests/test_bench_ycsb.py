"""The YCSB generator: seeded, the mix's shares, YCSB's key skew."""

import numpy as np
import pytest

from bench import ycsb

MIX = {"read_proportion": 0.5, "update_proportion": 0.5,
       "request_distribution": "zipfian", "zipfian_constant": 0.99}


def batches(seed, n=20, mix=MIX, records=1 << 19):
    g = ycsb.Batches(mix, n_records=records, n_nodes=8,
                     clients_per_node=32, width=256, seed=seed)
    return [g.next() for _ in range(n)]


def test_same_seed_same_batches():
    a, b = batches(2**33 + 5), batches(2**33 + 5)
    for x, y in zip(a, b):
        for u, v in zip(x, y):
            np.testing.assert_array_equal(u, v)
    c = batches(2**33 + 6)
    assert any(not np.array_equal(x[1], y[1]) for x, y in zip(a, c))


def test_slots_nodes_and_shares():
    bs = batches(7, n=200)
    node, line, isw, wdata, tags = bs[0]
    assert line.shape == (256,) and wdata.shape == (256, 256)
    np.testing.assert_array_equal(node, np.repeat(np.arange(8), 32))
    share = np.mean([b[2].mean() for b in bs])
    assert abs(share - 0.5) < 0.02
    # reads carry no bytes, every update its own tag and bytes
    for _, _, w, d, t in bs:
        assert not d[w == 0].any() and (t[w == 0] == 0).all()
        assert (t[w == 1] > 0).all()
    all_tags = np.concatenate([b[4][b[2] == 1] for b in bs])
    assert np.unique(all_tags).size == all_tags.size


def test_update_only_and_uniform_mixes():
    mix = dict(MIX, read_proportion=0.0, update_proportion=1.0,
               request_distribution="uniform")
    bs = batches(3, n=50, mix=mix, records=1000)
    assert all(b[2].all() for b in bs)
    lines = np.concatenate([b[1] for b in bs])
    assert lines.min() >= 0 and lines.max() < 1000
    with pytest.raises(ValueError):
        ycsb.Batches(dict(MIX, read_proportion=0.7), n_records=10,
                     n_nodes=1, clients_per_node=1, width=1, seed=0)


def test_scrambled_zipfian_head_share():
    z = ycsb.ScrambledZipfian(1 << 19)
    ids = z.sample(np.random.default_rng(1), 400_000)
    uniq, counts = np.unique(ids, return_counts=True)
    top = uniq[np.argmax(counts)]
    # YCSB: rank 0 carries 1 / zeta(10^10, 0.99) of the draws, and the
    # scramble puts it where fnvhash64(0) lands
    assert top == ycsb.scramble(np.array([0]), 1 << 19)[0]
    assert abs(counts.max() / ids.size - 1 / ycsb.YCSB_ZETAN) < 0.002
    # the hot set is scattered, not the lowest ids
    hottest = uniq[np.argsort(-counts)[:10]]
    assert hottest.max() > 1 << 16
    # the zeta the constant stands for
    assert ycsb.zeta(ycsb.ITEM_COUNT + 1, 0.99) == pytest.approx(
        ycsb.YCSB_ZETAN, rel=1e-9)


def test_fnvhash64_is_ycsb_fnv1a():
    # FNV-1a over the 8 octets of 1, low first, as YCSB's Utils does
    h = 0xCBF29CE484222325
    v = 1
    for _ in range(8):
        h ^= v & 0xFF
        h = (h * 1099511628211) % 2**64
        v >>= 8
    signed = h - 2**64 if h >= 2**63 else h
    assert int(ycsb.fnvhash64(np.array([1]))[0]) == abs(signed)


def test_device_and_host_bytes_agree():
    import jax
    import jax.numpy as jnp
    seed = 2**32 + 99
    rows = jnp.arange(64)
    dev = jax.jit(lambda s: ycsb.init_lanes(s, rows, 256))(
        jnp.uint32(ycsb.seed32(seed)))
    np.testing.assert_array_equal(np.asarray(dev),
                                  ycsb.init_bytes(seed, np.arange(64), 256))
    w = ycsb.write_bytes(seed, np.array([1, 2]), 256)
    assert not np.array_equal(w[0], w[1])
    assert not np.array_equal(w[0], ycsb.init_bytes(seed, [1], 256)[0])
