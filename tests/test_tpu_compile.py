"""Compile the device plane's main path for a described TPU v5e.

No chip is needed: the TPU compiler builds for a ``v5e:2x2`` topology
that is described, not attached.  This catches what the Pallas
interpreter cannot (tiling, memory spaces, scalar access rules) and
what does not fit the chip, at the deployment widths ``chip_smoke.py``
runs: N = 8 nodes, L = 2^18 lines, W = 256 payload lanes, R = 256 op
slots.  Each compile whose program runs a Pallas kernel must contain a
Mosaic ``tpu_custom_call`` — an interpreted kernel would not.

The topology is described only inside the module fixture below (never
at import), so every test worker collects the same tests and only the
worker given this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import rounds as rp
from repro.core.rounds.sharded import _state_specs
from repro.kernels.gcl_fetch.ops import fetch
from repro.kernels.latch_ops.ops import apply_batch
from repro.kernels.paged_attention.ops import decode_paged

N_NODES, N_LINES, WIDTH, R_SLOTS = 8, 1 << 18, 256, 256
HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(write_back, shardings):
    shapes = jax.eval_shape(lambda: rp.make_state(
        N_NODES, N_LINES, write_back=write_back, payload_width=WIDTH))
    return {k: _sds(v.shape, v.dtype, shardings(k)) for k, v in
            shapes.items()}


def test_latch_ops_compiles(one_chip):
    req = {k: _sds((R_SLOTS,), jnp.int32, one_chip)
           for k in ("line", "op", "arg_hi", "arg_lo", "cmp_hi", "cmp_lo")}
    words = _sds((N_LINES, 2), jnp.int32, one_chip)
    text = apply_batch.lower(words, req, backend="pallas").compile() \
        .as_text()
    assert "tpu_custom_call" in text


def test_gcl_fetch_compiles(one_chip):
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)     # noqa: E731
    text = fetch.lower(i32(N_LINES, WIDTH), i32(N_LINES, 2), i32(R_SLOTS),
                       i32(R_SLOTS), i32(R_SLOTS), backend="pallas") \
        .compile().as_text()
    assert "tpu_custom_call" in text


def test_paged_attention_compiles(one_chip):
    b, hq, hkv, hd, page, pool, max_pages = 8, 16, 8, 128, 16, 1024, 64
    kv = _sds((pool, page, hkv, hd), jnp.bfloat16, one_chip)
    text = decode_paged.lower(
        _sds((b, hq, hd), jnp.bfloat16, one_chip), kv, kv,
        _sds((b, max_pages), jnp.int32, one_chip),
        _sds((b,), jnp.int32, one_chip), backend="pallas") \
        .compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("write_back", [False, True],
                         ids=["write_through", "write_back"])
def test_fused_run_rounds_compiles(one_chip, write_back):
    state = _state(write_back, lambda k: one_chip)
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)     # noqa: E731
    compiled = rp.run_rounds.lower(
        state, i32(R_SLOTS), i32(R_SLOTS), i32(R_SLOTS),
        i32(R_SLOTS, WIDTH), n_nodes=N_NODES, max_rounds=256,
        backend="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    state_bytes = (N_NODES + 1) * N_LINES * WIDTH * 4
    assert mem.argument_size_in_bytes >= state_bytes
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def test_sharded_run_rounds_compiles(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("shards",))
    specs = _state_specs(_state(True, lambda k: None), "shards")
    state = _state(True, lambda k: NamedSharding(mesh, specs[k]))
    slots = NamedSharding(mesh, P("shards"))
    i32 = lambda *s: _sds(s, jnp.int32, slots)         # noqa: E731
    text = rp.run_rounds_sharded.lower(
        state, i32(R_SLOTS), i32(R_SLOTS), i32(R_SLOTS),
        _sds((R_SLOTS, WIDTH), jnp.int32,
             NamedSharding(mesh, P("shards", None))),
        mesh=mesh, n_nodes=N_NODES, max_rounds=256, backend="pallas") \
        .compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text
