"""Mixture-of-Experts FFN with expert parallelism (EP).

Dispatch is capacity-based (Switch-style, capacity_factor over the local
token count) and EP moves expert groups between model-axis shards with
``jax.lax.all_to_all`` inside ``shard_map`` — the MaxText-style dropless-ish
pipeline, with static shapes throughout so the 512-device dry-run lowers.

Layout contract:
  tokens x        : [B, S, d]   sharded P(data, model, None) in EP mode
  router          : [d, E]      replicated
  routed experts  : [E, d, ff]  sharded P(expert=model, ...)
  shared experts  : dense ffn params, ff_total = n_shared * d_ff

With no mesh (CPU smoke tests) the same local function runs with a single
shard and an identity all_to_all.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import layers


def _capacity(tokens: int, k: int, n_experts: int, cf: float) -> int:
    c = int(tokens * k * cf / n_experts) + 1
    return max(4, (c + 3) // 4 * 4)


def _dispatch(x_tok, logits, k: int, n_experts: int, capacity: int):
    """Token -> (expert, slot) scatter.  x_tok:[T,d] logits fp32 [T,E]."""
    t = x_tok.shape[0]
    gates = jax.nn.softmax(logits, axis=-1)                     # [T,E]
    top_w, top_e = jax.lax.top_k(gates, k)                      # [T,K]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    flat_e = top_e.reshape(-1)                                  # [T*K]
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    # slot index of each assignment within its expert (stable order)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)  # [T*K,E]
    pos_all = jnp.cumsum(onehot, axis=0) - 1                     # [T*K,E]
    slot = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]
    keep = slot < capacity
    # scatter tokens into [E, C, d]
    buf = jnp.zeros((n_experts, capacity, x_tok.shape[1]), x_tok.dtype)
    e_idx = jnp.where(keep, flat_e, 0)
    s_idx = jnp.where(keep, slot, capacity - 1)
    src = jnp.where(keep[:, None], x_tok[flat_tok], 0).astype(x_tok.dtype)
    buf = buf.at[e_idx, s_idx].add(src, mode="drop")
    # load-balance aux (Switch): E * sum_e f_e * p_e
    f = jnp.mean(jax.nn.one_hot(flat_e, n_experts, dtype=jnp.float32),
                 axis=0) * k
    p_mean = jnp.mean(gates, axis=0)
    aux = n_experts * jnp.sum(f * p_mean) / k
    return buf, (flat_tok, e_idx, s_idx, flat_w, keep), aux


def _combine(y_buf, route, t: int):
    flat_tok, e_idx, s_idx, flat_w, keep = route
    vals = y_buf[e_idx, s_idx]                                  # [T*K,d]
    vals = vals * jnp.where(keep, flat_w, 0.0)[:, None].astype(vals.dtype)
    out = jnp.zeros((t, y_buf.shape[-1]), y_buf.dtype)
    return out.at[flat_tok].add(vals)


def _expert_ffn(xin, pg, pu, pd, ffn_type):
    if ffn_type in ("swiglu", "geglu"):
        act = jax.nn.silu if ffn_type == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", xin, pg)) \
            * jnp.einsum("ecd,edf->ecf", xin, pu)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin, pu))
    return jnp.einsum("ecf,efd->ecd", h, pd)


def _moe_local(x, p, cfg, n_shards: int, a2a):
    """Per-shard body. x:[b_l, s_l, d]; routed experts in p are the LOCAL
    slice [E_loc, d, ff] when sharded; a2a exchanges expert groups."""
    b_l, s_l, d = x.shape
    t = b_l * s_l
    xt = x.reshape(t, d)
    e_total = cfg.n_experts
    cap = _capacity(t, cfg.top_k, e_total, cfg.capacity_factor)
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    buf, route, aux = _dispatch(xt, logits, cfg.top_k, e_total, cap)
    # exchange: [E, C, d] -> [n, E_loc, C, d] -> recv [n_src, E_loc, C, d]
    e_loc = e_total // n_shards
    send = buf.reshape(n_shards, e_loc, cap, d)
    recv = a2a(send)
    xin = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_shards * cap, d)
    y = _expert_ffn(xin, p.get("we_g"), p.get("we_u"), p["we_d"],
                    cfg.ffn_type)
    back = y.reshape(e_loc, n_shards, cap, d).transpose(1, 0, 2, 3)
    y_buf = a2a(back).reshape(e_total, cap, d)
    out = _combine(y_buf, route, t)
    return out.reshape(b_l, s_l, d), aux


def moe_ffn(x, p, cfg, parallel=None):
    """x: [B,S,d] global.  parallel: ParallelCtx or None (single shard)."""
    if parallel is not None and parallel.ep > 1:
        mesh, axis = parallel.mesh, parallel.ep_axis
        n = parallel.ep
        dp = parallel.dp_axis
        dp_size = int(np.prod([mesh.shape[a] for a in
                               (dp if isinstance(dp, tuple) else (dp,))]))
        b_ax = dp if x.shape[0] % dp_size == 0 else None
        # shard the sequence over the EP axis too when it divides (training/
        # prefill); decode has S=1 and replicates it (tiny, recomputed)
        s_ax = axis if x.shape[1] % n == 0 else None
        xspec = P(b_ax, s_ax, None)

        def body(x_l, pr_l):
            a2a = partial(jax.lax.all_to_all, axis_name=axis, split_axis=0,
                          concat_axis=0, tiled=False)
            y, aux = _moe_local(x_l, pr_l, cfg, n, a2a)
            # aux is declared replicated in out_specs: average over EVERY
            # mesh axis so that is actually true
            return y, jax.lax.pmean(aux, tuple(mesh.axis_names))

        in_specs = (xspec,
                    {"router": P(),
                     **{k: P(axis, None, None) for k in
                        ("we_g", "we_u", "we_d") if k in p}})
        routed = {k: p[k] for k in ("router", "we_g", "we_u", "we_d")
                  if k in p}
        y, aux = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs,
            out_specs=(xspec, P()),
            check_vma=False)(x, routed)
    else:
        routed = {k: p[k] for k in ("router", "we_g", "we_u", "we_d")
                  if k in p}
        y, aux = _moe_local(x, routed, cfg, 1, lambda z: z)
    if cfg.n_shared_experts:
        shared = {k.replace("s_", ""): v for k, v in p.items()
                  if k.startswith("s_")}
        y = y + layers.ffn(x, shared, cfg.ffn_type)
    return y, aux


def init_moe(key, cfg, dtype, stack=()):
    import numpy as np
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    s = tuple(stack)
    p = {"router": (jax.random.normal(ks[0], s + (d, e), jnp.float32)
                    * 0.02).astype(jnp.float32)}
    def he(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan)).astype(dtype)
    if cfg.ffn_type in ("swiglu", "geglu"):
        p["we_g"] = he(ks[1], s + (e, d, ff), d)
        p["we_u"] = he(ks[2], s + (e, d, ff), d)
    else:
        p["we_u"] = he(ks[2], s + (e, d, ff), d)
    p["we_d"] = he(ks[3], s + (e, ff, d), ff)
    if cfg.n_shared_experts:
        sh = layers.init_ffn(ks[4], d, ff * cfg.n_shared_experts,
                             cfg.ffn_type, cfg.use_bias, dtype, stack=stack)
        p.update({f"s_{k}": v for k, v in sh.items()})
    return p
