"""Pipeline parallelism: GPipe-style microbatch pipelining over a `pipe`
mesh axis with `shard_map` + `collective_permute`.

The production 2-axis v5e mesh doesn't allocate a pipe axis (ICI-rich
TP+FSDP wins there — DESIGN.md §5), but a 1000+-node DCN-connected fleet
does; this module supplies the schedule, and `tests/test_pipeline.py`
verifies numerics against the unpipelined reference on a subprocess mesh.

Schedule (GPipe, S stages, M microbatches, M >= S):
  step t in [0, M+S-2]:  stage s works on microbatch (t - s) when
  0 <= t - s < M; activations hop stage s -> s+1 through a
  collective_permute each step.  Bubble fraction = (S-1)/(M+S-1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, params_stacked, x_micro, *, mesh,
                     axis: str = "pipe"):
    """Run microbatches through pipeline stages.

    stage_fn: (stage_params, x) -> y — the per-stage body (a slice of the
              layer stack is each stage's params).
    params_stacked: pytree with leading dim = n_stages (stage-major).
    x_micro: [M, mb, ...] microbatched input (M >= n_stages).
    Returns [M, mb, ...] outputs (microbatch order preserved).
    """
    n_stages = mesh.shape[axis]
    m = x_micro.shape[0]
    assert m >= n_stages, "need at least one microbatch per stage"

    def body(params_local, xs_local):
        params_local = jax.tree.map(lambda p: p[0], params_local)
        xs = xs_local[0]                         # [M, mb, ...] replicated
        sid = jax.lax.axis_index(axis)
        carry = jnp.zeros(xs.shape[1:], xs.dtype)
        outs = jnp.zeros_like(xs)

        def step(t, state):
            carry, outs = state
            # stage 0 injects microbatch t; later stages use the carry
            mb_idx = jnp.clip(t, 0, m - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                  keepdims=False)
            x_in = jnp.where(sid == 0, inject, carry)
            active = jnp.logical_and(t - sid >= 0, t - sid < m)
            y = stage_fn(params_local, x_in)
            y = jnp.where(active, y, carry)
            # the last stage collects finished microbatches
            done_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            is_done = jnp.logical_and(
                sid == n_stages - 1,
                jnp.logical_and(t - (n_stages - 1) >= 0,
                                t - (n_stages - 1) < m))
            outs = jax.lax.cond(
                is_done,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, done_idx, 0),
                lambda o: o, outs)
            # hop activations stage s -> s+1 (ring permute; the wrap edge
            # into stage 0 is overwritten by the next injection)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            carry = jax.lax.ppermute(y, axis, perm)
            return carry, outs

        carry, outs = jax.lax.fori_loop(0, m + n_stages - 1, step,
                                        (carry, outs))
        # broadcast results from the last stage to all (bijection-safe:
        # zero elsewhere + psum over the pipe axis)
        outs = jnp.where(sid == n_stages - 1, outs, 0)
        return jax.lax.psum(outs, axis)

    spec_params = jax.tree.map(lambda _: P(axis), params_stacked)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_params, P(axis)),
        out_specs=P(),
        check_vma=False,
    )(params_stacked,
      jnp.broadcast_to(x_micro[None], (n_stages,) + x_micro.shape))


def split_stages(stacked_params, n_stages: int):
    """[L, ...] layer-stacked params -> [S, L/S, ...] stage-major."""
    def split(p):
        l = p.shape[0]
        assert l % n_stages == 0, f"layers {l} % stages {n_stages}"
        return p.reshape(n_stages, l // n_stages, *p.shape[1:])
    return jax.tree.map(split, stacked_params)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
