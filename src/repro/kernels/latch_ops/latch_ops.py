"""Pallas TPU kernel: batched latch-word CAS/FAA merge at the home shard.

TPU adaptation of RDMA atomics (DESIGN.md Sec. 2): every GCL's 64-bit
latch word is owned by its home shard; a coherence round delivers up to R
requests to the shard, and this kernel applies them *sequentially* (the
serialization that the NIC atomic unit provides in the paper), returning
the pre-op word per request (exactly what RDMA_CAS/RDMA_FAA return — the
directory ride-back trick).

Latch words are carried as 2 x int32 lanes (TPUs are 32-bit machines):
    hi = (writer_id+1) << 24 | readers[55:32]   lo = readers[31:0]

Request encoding (int32):
    req_line[R]            line index, -1 = empty slot
    req_op[R]              0 = CAS, 1 = FAA
    req_arg_hi/lo[R]       swap value (CAS) or addend (FAA)
    req_cmp_hi/lo[R]       compare value (CAS only)

The atomic unit is the TPU's scalar core: the R requests, and the R
words they name, sit in SMEM and one scalar loop applies them in request
order.  The wrapper gathers each request's word from the [N, 2] plane
and names its LEAD slot (the first request to the same line); requests
to one line read and update the lead's running word, which is how they
serialize.  The final words scatter back in one pass (every slot of a
line carries the same final value, so the scatter is order-free).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import compiled_on_tpu

SIGN_BIT = -2147483648     # int32 0x80000000: unsigned compare via signed


def _kernel(line_ref, op_ref, arg_hi_ref, arg_lo_ref, cmp_hi_ref,
            cmp_lo_ref, lead_ref, word_hi_ref, word_lo_ref, old_hi_ref,
            old_lo_ref, ok_ref, fin_hi_ref, fin_lo_ref):
    r = line_ref.shape[0]

    def load(i, c):
        fin_hi_ref[i] = word_hi_ref[i]
        fin_lo_ref[i] = word_lo_ref[i]
        return c

    def apply(i, c):
        valid = line_ref[i] >= 0
        j = lead_ref[i]
        hi = fin_hi_ref[j]
        lo = fin_lo_ref[j]
        is_cas = op_ref[i] == 0
        # CAS: whole-64-bit compare
        hit = jnp.logical_and(hi == cmp_hi_ref[i], lo == cmp_lo_ref[i])
        # FAA: 64-bit add, carry out of the low lane = unsigned overflow
        sum_lo = lo + arg_lo_ref[i]
        carry = ((sum_lo ^ SIGN_BIT) < (lo ^ SIGN_BIT)).astype(jnp.int32)
        new_hi = jnp.where(is_cas, jnp.where(hit, arg_hi_ref[i], hi),
                           hi + arg_hi_ref[i] + carry)
        new_lo = jnp.where(is_cas, jnp.where(hit, arg_lo_ref[i], lo),
                           sum_lo)
        fin_hi_ref[j] = jnp.where(valid, new_hi, hi)
        fin_lo_ref[j] = jnp.where(valid, new_lo, lo)
        old_hi_ref[i] = jnp.where(valid, hi, 0)
        old_lo_ref[i] = jnp.where(valid, lo, 0)
        ok_ref[i] = jnp.where(valid,
                              jnp.where(is_cas, hit.astype(jnp.int32), 1),
                              0)
        return c

    jax.lax.fori_loop(0, r, load, 0)
    jax.lax.fori_loop(0, r, apply, 0)


def _serialize(*reqs, interpret: bool):
    r = reqs[0].shape[0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        in_specs=[smem] * len(reqs),
        out_specs=[smem] * 5,
        out_shape=[jax.ShapeDtypeStruct((r,), jnp.int32)] * 5,
        interpret=interpret,
    )(*reqs)


def latch_apply(words, line, op, arg_hi, arg_lo, cmp_hi, cmp_lo):
    """words: [N, 2] int32; request arrays [R] int32 (line = -1 for empty).
    Returns (new_words [N,2], old_hi [R], old_lo [R], ok [R])."""
    n = words.shape[0]
    valid = line >= 0
    idx = jnp.maximum(line, 0)
    same = jnp.logical_and(line[:, None] == line[None, :], valid[None, :])
    lead = jnp.argmax(same, axis=1).astype(jnp.int32)
    word = words[idx]
    old_hi, old_lo, ok, fin_hi, fin_lo = compiled_on_tpu(
        _serialize, line, op, arg_hi, arg_lo, cmp_hi, cmp_lo, lead,
        word[:, 0], word[:, 1])
    final = jnp.stack([fin_hi[lead], fin_lo[lead]], axis=1)
    new_words = words.at[jnp.where(valid, idx, n)].set(final, mode="drop")
    return new_words, old_hi, old_lo, ok
