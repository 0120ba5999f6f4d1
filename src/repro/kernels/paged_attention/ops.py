"""jit'd wrapper for paged decode attention."""

from __future__ import annotations

import functools

import jax

from ..platform import compiled_on_tpu
from .paged_attention import paged_attention
from .ref import paged_attention_ref


@functools.partial(jax.jit, static_argnames=("backend",))
def decode_paged(q, k_pages, v_pages, page_tbl, lens, *,
                 backend: str = "ref"):
    if backend == "pallas":
        return compiled_on_tpu(paged_attention, q, k_pages, v_pages,
                               page_tbl, lens)
    return paged_attention_ref(q, k_pages, v_pages, page_tbl, lens)
