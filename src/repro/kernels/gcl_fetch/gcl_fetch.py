"""Pallas TPU kernel: fused latch-verdict + GCL payload gather.

The paper's key data-path saving is the COMBINED one-sided op: latch
CAS/FAA and cache-line read in a single round trip (Sec. 3, Sec. 6.1).
On the TPU home shard this becomes one kernel pass: for each request,
read the latch word (2 x int32 lanes), compute the shared-acquire verdict
(no exclusive holder), and copy the page payload — one sweep instead of
two (latch pass + gather pass).

pages:    [P, page_elems]    payload pool (any 32-bit dtype)
words:    [P, 2] int32       latch words (hi lane carries writer byte)
req_page: [R] int32          page index per request (-1 = empty)
req_bit_hi/lo: [R] int32     requester's reader-bit lanes

Returns (payload [R, page_elems], old_hi [R], old_lo [R], granted [R],
new_words [P, 2]).  Grant rule == SELCC shared acquire: writer byte of
the OLD word must be zero; the reader bit is merged in regardless and the
caller (rounds engine) reverts it on failure — identical to the
FAA-then-reset dance in the paper's Sec. 4.3(b).

Grid: one step per request.  The TPU moves memory in (8, 128) tiles, so
each step's BlockSpec fetches the aligned 8-row window that holds the
requested page (and its word), selects the row, and places it into the
8-row output window of its request slot; the window stays resident over
the 8 consecutive steps that fill it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import compiled_on_tpu

WRITER_MASK_HI = -16777216   # int32 view of 0xFF000000 (plain int: pallas kernels cannot capture traced constants)
SUB = 8                      # sublanes per (8, 128) tile of a 32-bit array


def _kernel(req_page_ref, pages_ref, words_ref, payload_ref, old_ref,
            granted_ref):
    r = pl.program_id(0)
    page = req_page_ref[r]
    valid = page >= 0
    src = jnp.maximum(page, 0) % SUB
    dst = r % SUB

    @pl.when(dst == 0)
    def _clear():
        payload_ref[...] = jnp.zeros_like(payload_ref)
        old_ref[...] = jnp.zeros_like(old_ref)
        granted_ref[...] = jnp.zeros_like(granted_ref)

    def pick(window):
        # select row ``src`` bit-exactly (selects, no arithmetic)
        row = window[0:1]
        for t in range(1, SUB):
            row = jnp.where(src == t, window[t:t + 1], row)
        return jnp.where(valid, row, jnp.zeros_like(row))

    def place(ref, row):
        rows = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0)
        ref[...] = jnp.where(rows == dst, row, ref[...])

    word = pick(words_ref[...])                             # [1, 2]
    place(payload_ref, pick(pages_ref[...]))
    place(old_ref, word)
    no_writer = (word[:, :1] & WRITER_MASK_HI) == 0
    place(granted_ref, jnp.logical_and(valid, no_writer).astype(jnp.int32))


def _gather(pages, words, req_page, *, interpret: bool):
    elems = pages.shape[1]
    r = req_page.shape[0]
    window = lambda i, pg: (jnp.maximum(pg[i], 0) // SUB, 0)   # noqa: E731
    slot = lambda i, pg: (i // SUB, 0)                          # noqa: E731
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r,),
            in_specs=[pl.BlockSpec((SUB, elems), window),
                      pl.BlockSpec((SUB, 2), window)],
            out_specs=[pl.BlockSpec((SUB, elems), slot),
                       pl.BlockSpec((SUB, 2), slot),
                       pl.BlockSpec((SUB, 1), slot)],
        ),
        out_shape=[jax.ShapeDtypeStruct((r, elems), pages.dtype),
                   jax.ShapeDtypeStruct((r, 2), jnp.int32),
                   jax.ShapeDtypeStruct((r, 1), jnp.int32)],
        # consecutive steps revisit one output window: run them in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(req_page, pages, words)


def gcl_fetch(pages, words, req_page, bit_hi, bit_lo):
    if pages.dtype.itemsize != 4:
        raise ValueError(f"gcl_fetch gathers 32-bit payloads, got "
                         f"{pages.dtype}")
    r = req_page.shape[0]
    padded = jnp.pad(req_page, (0, (-r) % SUB), constant_values=-1)
    payload, old, granted = compiled_on_tpu(_gather, pages, words, padded)
    # directory merge (reader bits) — one scatter, same round semantics as
    # the paper's combined FAA+read; kept outside the kernel because
    # multiple grid steps may not partially write one aliased block
    valid = req_page >= 0
    idx = jnp.maximum(req_page, 0)
    new_words = words
    new_words = new_words.at[idx, 0].set(
        jnp.where(valid, new_words[idx, 0] | bit_hi, new_words[idx, 0]))
    new_words = new_words.at[idx, 1].set(
        jnp.where(valid, new_words[idx, 1] | bit_lo, new_words[idx, 1]))
    return (payload[:r], old[:r, 0], old[:r, 1], granted[:r, 0],
            new_words)
