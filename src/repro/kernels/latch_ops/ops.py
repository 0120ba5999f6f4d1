"""jit'd public wrapper for the latch_ops kernel.

``backend='pallas'`` runs the kernel (compiled on a TPU, interpreted
elsewhere — see ``kernels/platform.py``); ``backend='ref'`` is the jnp
oracle.
"""

from __future__ import annotations

import functools

import jax

from .latch_ops import latch_apply
from .ref import latch_apply_ref

OP_CAS = 0
OP_FAA = 1


@functools.partial(jax.jit, static_argnames=("backend",))
def apply_batch(words, requests, backend: str = "ref"):
    """words: [N,2] int32.  requests: dict with line/op/arg_hi/arg_lo/
    cmp_hi/cmp_lo int32 [R].  Returns (new_words, old_hi, old_lo, ok)."""
    r = requests
    fn = latch_apply if backend == "pallas" else latch_apply_ref
    return fn(words, r["line"], r["op"], r["arg_hi"], r["arg_lo"],
              r["cmp_hi"], r["cmp_lo"])
