"""jit'd wrapper for the SSD intra-chunk kernel."""

from __future__ import annotations

import functools

import jax

from ..platform import compiled_on_tpu
from .ref import ssd_intra_ref
from .ssd_intra import ssd_intra


@functools.partial(jax.jit, static_argnames=("backend",))
def intra_chunk(cb, cs, win, *, backend: str = "ref"):
    if backend == "pallas":
        return compiled_on_tpu(ssd_intra, cb, cs, win)
    return ssd_intra_ref(cb, cs, win)
