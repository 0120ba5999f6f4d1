"""jit'd wrapper for flash_attention (layout: [B, S, H, hd] like the model
code; transposes to the kernel's [B, H, S, hd])."""

from __future__ import annotations

import functools

import jax

from ..platform import compiled_on_tpu
from .flash_attention import flash_attention
from .ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "backend",
                                             "block_q", "block_k"))
def attention(q, k, v, *, causal: bool = True, backend: str = "ref",
              block_q: int = 256, block_k: int = 256):
    """q: [B,S,Hq,hd]; k,v: [B,S,Hkv,hd] -> [B,S,Hq,hd]."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if backend == "pallas":
        kernel = functools.partial(flash_attention, causal=causal,
                                   block_q=block_q, block_k=block_k)
        o = compiled_on_tpu(kernel, qt, kt, vt)
    else:
        o = flash_attention_ref(qt, kt, vt, causal=causal)
    return o.transpose(0, 2, 1, 3)
