"""jit'd wrapper for gcl_fetch."""

from __future__ import annotations

import functools

import jax

from .gcl_fetch import gcl_fetch
from .ref import gcl_fetch_ref


@functools.partial(jax.jit, static_argnames=("backend",))
def fetch(pages, words, req_page, bit_hi, bit_lo, *, backend: str = "ref"):
    fn = gcl_fetch if backend == "pallas" else gcl_fetch_ref
    return fn(pages, words, req_page, bit_hi, bit_lo)
