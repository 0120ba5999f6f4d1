"""Pallas kernel validation: the kernels (interpreted on the CPU, see
kernels/platform.py) vs pure-jnp oracles, swept over shapes/dtypes (+
hypothesis sweeps for the latch kernel)."""

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.ops import attention
from repro.kernels.gcl_fetch.ops import fetch
from repro.kernels.latch_ops.ops import apply_batch
from repro.kernels.paged_attention.ops import decode_paged


@pytest.mark.parametrize("b,s,hq,hkv,hd,causal,dtype", [
    (2, 256, 4, 2, 64, True, jnp.float32),
    (1, 512, 8, 8, 128, True, jnp.float32),
    (2, 256, 4, 1, 128, False, jnp.float32),
    (1, 256, 8, 4, 64, True, jnp.bfloat16),
    (1, 128, 2, 2, 256, True, jnp.float32),
])
def test_flash_attention_matches_ref(b, s, hq, hkv, hd, causal, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, s, hq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), dtype)
    ref = attention(q, k, v, causal=causal, backend="ref")
    pal = attention(q, k, v, causal=causal, backend="pallas",
                    block_q=128, block_k=128)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                 - pal.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("b,hq,hkv,hd,page,mp,pool,dtype", [
    (4, 8, 2, 64, 16, 8, 64, jnp.float32),
    (2, 4, 4, 128, 32, 4, 32, jnp.float32),
    (3, 8, 1, 128, 16, 6, 128, jnp.float32),
    (2, 16, 8, 64, 8, 4, 32, jnp.bfloat16),
])
def test_paged_attention_matches_ref(b, hq, hkv, hd, page, mp, pool,
                                     dtype):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(b, hq, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(pool, page, hkv, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(pool, page, hkv, hd)), dtype)
    tbl = jnp.asarray(rng.integers(0, pool, (b, mp)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, mp * page, b), jnp.int32)
    ref = decode_paged(q, kp, vp, tbl, lens, backend="ref")
    pal = decode_paged(q, kp, vp, tbl, lens, backend="pallas")
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    assert float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                 - pal.astype(jnp.float32)))) < tol


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), r=st.integers(1, 64))
def test_latch_ops_match_ref(seed, r):
    rng = np.random.default_rng(seed)
    n = 2048
    words = jnp.asarray(rng.integers(0, 2 ** 20, (n, 2)), jnp.int32)
    line = rng.integers(-1, n, r).astype(np.int32)
    req = {
        "line": jnp.asarray(line),
        "op": jnp.asarray(rng.integers(0, 2, r), jnp.int32),
        "arg_hi": jnp.asarray(rng.integers(-4, 4, r), jnp.int32),
        "arg_lo": jnp.asarray(rng.integers(0, 2 ** 16, r), jnp.int32),
        "cmp_hi": jnp.asarray(rng.integers(0, 4, r), jnp.int32),
        "cmp_lo": jnp.asarray(
            np.asarray(words)[np.maximum(line, 0), 1], jnp.int32),
    }
    ref = apply_batch(words, req, backend="ref")
    pal = apply_batch(words, req, backend="pallas")
    for a, b in zip(ref, pal):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latch_ops_same_line_serialization():
    # 3 FAAs to the same line must serialize: old values chain
    words = jnp.zeros((1024, 2), jnp.int32)
    req = {
        "line": jnp.asarray([5, 5, 5], jnp.int32),
        "op": jnp.asarray([1, 1, 1], jnp.int32),
        "arg_hi": jnp.zeros(3, jnp.int32),
        "arg_lo": jnp.asarray([1, 2, 4], jnp.int32),
        "cmp_hi": jnp.zeros(3, jnp.int32),
        "cmp_lo": jnp.zeros(3, jnp.int32),
    }
    for backend in ("ref", "pallas"):
        new_w, old_hi, old_lo, ok = apply_batch(words, req,
                                                backend=backend)
        assert list(np.asarray(old_lo)) == [0, 1, 3]
        assert int(np.asarray(new_w)[5, 1]) == 7


def _lanes64(value):
    """64-bit int -> (hi, lo) int32 lanes (two's complement)."""
    v = value & ((1 << 64) - 1)
    return (np.int32(np.uint32(v >> 32)), np.int32(np.uint32(v & 0xFFFFFFFF)))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_latch_cas_sees_transient_reader_bits(backend):
    """An S->X upgrade CAS compares the WHOLE 64-bit word: a transient
    reader bit (or a writer byte) alongside the upgrader's own bit must
    fail the swap; the exact expected word must succeed."""
    from repro.core import coherence as co
    n = 1024
    # line 3: node 5's reader bit + a transient bit from node 40 (hi lane)
    # line 7: writer byte of node 3 + node 5's reader bit
    w3 = co.pack(None, [5, 40])
    w7 = co.pack(3, [5])
    words = np.zeros((n, 2), np.int32)
    words[3] = _lanes64(w3)
    words[7] = _lanes64(w7)
    want = _lanes64(co.pack(5, []))           # node 5's writer field
    have = _lanes64(co.reader_bit(5))         # what an upgrader expects
    req = {
        "line": jnp.asarray([3, 7, 3], jnp.int32),
        "op": jnp.zeros(3, jnp.int32),        # CAS
        "arg_hi": jnp.asarray([want[0]] * 3, jnp.int32),
        "arg_lo": jnp.asarray([want[1]] * 3, jnp.int32),
        # slots 0/1: expect sole readership -> must fail on both lines;
        # slot 2: expect the TRUE word (incl. transient bit) -> succeeds
        "cmp_hi": jnp.asarray([have[0], have[0], _lanes64(w3)[0]],
                              jnp.int32),
        "cmp_lo": jnp.asarray([have[1], have[1], _lanes64(w3)[1]],
                              jnp.int32),
    }
    new_w, old_hi, old_lo, ok = apply_batch(jnp.asarray(words), req,
                                            backend=backend)
    assert list(np.asarray(ok)) == [0, 0, 1]
    assert tuple(np.asarray(new_w)[3]) == want   # slot 2 won line 3
    assert tuple(np.asarray(new_w)[7]) == tuple(words[7])  # untouched
    # the returned old word IS the directory ride-back
    assert (old_hi[0], old_lo[0]) == _lanes64(w3)
    assert (old_hi[1], old_lo[1]) == _lanes64(w7)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("node", [5, 40])     # lo-lane and hi-lane bits
def test_latch_faa_underflow_on_double_release(backend, node):
    """A double FAA-release must wrap exactly like the NIC's 64-bit
    atomic (latchword.faa), including the borrow across the two int32
    lanes — not saturate or corrupt neighbouring fields."""
    from repro.core import coherence as co
    n = 1024
    bit = co.reader_bit(node)
    words = np.zeros((n, 2), np.int32)
    words[2] = _lanes64(bit)                  # one registered reader
    delta = _lanes64(-bit)                    # release = FAA(-bit)
    req = {
        "line": jnp.asarray([2, 2], jnp.int32),
        "op": jnp.ones(2, jnp.int32),         # FAA
        "arg_hi": jnp.asarray([delta[0]] * 2, jnp.int32),
        "arg_lo": jnp.asarray([delta[1]] * 2, jnp.int32),
        "cmp_hi": jnp.zeros(2, jnp.int32),
        "cmp_lo": jnp.zeros(2, jnp.int32),
    }
    new_w, old_hi, old_lo, ok = apply_batch(jnp.asarray(words), req,
                                            backend=backend)
    # first release frees the word; the second underflows 64-bit-wrapped
    assert (old_hi[0], old_lo[0]) == _lanes64(bit)
    assert (old_hi[1], old_lo[1]) == _lanes64(0)
    expect = co.faa(0, -bit)                  # (0 - bit) mod 2**64
    got = co.from_lanes(int(np.uint32(np.asarray(new_w)[2, 0])),
                        int(np.uint32(np.asarray(new_w)[2, 1])))
    assert got == expect, f"{got:#018x} != {expect:#018x}"
    # the wrapped word is garbage the protocol would misread as holders:
    # a third FAA(+bit) must restore the free word exactly
    readd = _lanes64(bit)
    req2 = {
        "line": jnp.asarray([2], jnp.int32),
        "op": jnp.ones(1, jnp.int32),
        "arg_hi": jnp.asarray([readd[0]], jnp.int32),
        "arg_lo": jnp.asarray([readd[1]], jnp.int32),
        "cmp_hi": jnp.zeros(1, jnp.int32),
        "cmp_lo": jnp.zeros(1, jnp.int32),
    }
    new_w2, _, _, _ = apply_batch(new_w, req2, backend=backend)
    assert tuple(np.asarray(new_w2)[2]) == (0, 0)


@pytest.mark.parametrize("pool,elems,r", [(32, 128, 16), (64, 256, 8)])
def test_gcl_fetch_matches_ref(pool, elems, r):
    rng = np.random.default_rng(2)
    pages = jnp.asarray(rng.normal(size=(pool, elems)), jnp.float32)
    words = np.zeros((pool, 2), np.int32)
    words[1, 0] = 3 << 24
    words = jnp.asarray(words)
    req_page = jnp.asarray(rng.integers(-1, pool, r), jnp.int32)
    bit_hi = jnp.zeros((r,), jnp.int32)
    bit_lo = jnp.asarray(rng.integers(1, 2 ** 8, r), jnp.int32)
    ref = fetch(pages, words, req_page, bit_hi, bit_lo, backend="ref")
    pal = fetch(pages, words, req_page, bit_hi, bit_lo, backend="pallas")
    np.testing.assert_allclose(np.asarray(ref[0]), np.asarray(pal[0]))
    np.testing.assert_array_equal(np.asarray(ref[3]), np.asarray(pal[3]))
    np.testing.assert_array_equal(np.asarray(ref[4]), np.asarray(pal[4]))


@pytest.mark.parametrize("b,q,h,p,dtype", [
    (2, 32, 4, 16, jnp.float32),
    (1, 64, 8, 64, jnp.float32),
    (3, 16, 2, 32, jnp.bfloat16),
])
def test_ssd_intra_matches_ref(b, q, h, p, dtype):
    from repro.kernels.ssd_intra.ops import intra_chunk
    rng = np.random.default_rng(4)
    cb = jnp.asarray(rng.normal(size=(b, q, q)) * 0.3, dtype)
    # decaying cumsums (dA < 0): realistic magnitudes keep exp() sane
    cs = jnp.asarray(-np.abs(rng.normal(size=(b, q, h))).cumsum(axis=1)
                     * 0.1, dtype)
    win = jnp.asarray(rng.normal(size=(b, q, h, p)), dtype)
    ref = intra_chunk(cb, cs, win, backend="ref")
    pal = intra_chunk(cb, cs, win, backend="pallas")
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                - pal.astype(jnp.float32))))
    assert err < tol, err


def test_ssd_intra_matches_model_branch():
    """The kernel must agree with models/ssm.ssd_chunked's intra branch:
    feed identical (cb, cs, dt*x) and compare against the model's einsum."""
    from repro.kernels.ssd_intra.ops import intra_chunk
    rng = np.random.default_rng(5)
    b, q, h, p, n = 2, 32, 4, 16, 8
    dt = jnp.asarray(np.abs(rng.normal(size=(b, q, h))) * 0.1, jnp.float32)
    a = -jnp.exp(jnp.asarray(rng.normal(size=(h,)), jnp.float32) * 0.1)
    cs = jnp.cumsum(dt * a, axis=1)
    bmat = jnp.asarray(rng.normal(size=(b, q, n)), jnp.float32)
    cmat = jnp.asarray(rng.normal(size=(b, q, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, q, h, p)), jnp.float32)
    cb = jnp.einsum("bqn,bkn->bqk", cmat, bmat)
    win = dt[..., None] * x
    got = intra_chunk(cb, cs, win, backend="pallas")
    # the model's einsum form
    seg = cs[:, :, None, :] - cs[:, None, :, :]
    l_mat = jnp.where(jnp.tril(jnp.ones((q, q), bool))[None, :, :, None],
                      jnp.exp(seg), 0.0)
    ref = jnp.einsum("bqk,bqkh,bkhp->bqhp", cb, l_mat, win)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
