"""YCSB through ``DevicePlane.ops``: the key-value cells' driver.

A batch is one op per client slot (:class:`bench.ycsb.Batches`), served
by one ``plane.ops`` call.  The plane holds the configuration's records
on one chip, or striped over several, every record at its seed-derived
version-0 bytes and the node caches empty.  After the window the plain
reference (:mod:`bench.reference`) checks every reply of every batch,
and a seeded sample of the records written is read back through the
plane from a node other than its last writer.  See
:mod:`bench.drivers` for the contract.
"""

from __future__ import annotations

import numpy as np

from bench import roofline
from bench.reference import Reference, Served
from bench.ycsb import Batches, init_lanes, seed32


def _sizes(config: dict):
    return (int(config["n_nodes"]), int(config["recordcount"]),
            int(config["payload_width"]))


def _state_kw(config: dict) -> dict:
    return dict(write_back=bool(config["write_back"]),
                payload_width=int(config["payload_width"]))


def _mesh(devices: list):
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(devices), ("shards",),
                axis_types=(AxisType.Auto,))


def _stripes(mesh, shapes: dict, l: int) -> dict:
    """Every leaf striped over its line axis: the one of length L."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    return {k: NamedSharding(mesh, P(*[
        "shards" if d == s.shape.index(l) else None
        for d in range(len(s.shape))])) for k, s in shapes.items()}


def open_plane(config: dict, seed: int, devices: list):
    """A ``DevicePlane`` over a fresh state made on the device in one
    jitted call: every record holds its seed-derived version-0 bytes,
    the node caches are empty.  One chip holds a flat plane; more chips
    hold the plane striped over them by ``make_sharded_state``."""
    import jax
    import jax.numpy as jnp
    from repro.core.rounds import DevicePlane, make_sharded_state, make_state

    n, l, w = _sizes(config)
    kw = _state_kw(config)
    s32 = jnp.uint32(seed32(seed))
    if len(devices) == 1:
        def build(s):
            st = dict(make_state(n, l, **kw))
            st["mem_data"] = init_lanes(s, jnp.arange(l), w)
            return st
        state = jax.jit(build)(jax.device_put(s32, devices[0]))
        return DevicePlane.open(state, n_nodes=n)
    mesh = _mesh(devices)
    shards = len(devices)
    shapes = jax.eval_shape(lambda: make_sharded_state(n, l, mesh, **kw))

    def build(s):
        st = dict(make_sharded_state(n, l, mesh, **kw))
        # row r of the stripe holds line (r % (L/S)) * S + r // (L/S)
        r = jnp.arange(l)
        rows = (r % (l // shards)) * shards + r // (l // shards)
        st["mem_data"] = init_lanes(s, rows, w)
        return st
    state = jax.jit(build, out_shardings=_stripes(mesh, shapes, l))(s32)
    return DevicePlane.open(state, mesh, n_nodes=n)


def traffic(config: dict, mix: dict, seed: int) -> Batches:
    n, l, w = _sizes(config)
    return Batches(mix, n_records=l, n_nodes=n,
                   clients_per_node=int(config["clients_per_node"]),
                   width=w, seed=seed)


def serve(plane, batch):
    """One ``plane.ops`` call: its result, or None if it was refused."""
    node, line, isw, wdata, _ = batch
    try:
        return plane.ops(node, line, isw, wdata)
    except RuntimeError as e:
        if "not served" not in str(e):
            raise
        return None


def record(batch, result) -> Served:
    node, line, isw, _, tags = batch
    if result is None:
        return Served(node, line, isw, tags, failed=True)
    return Served(node, line, isw, tags, result.version, result.data,
                  rounds=result.rounds)


def check(config: dict, mix: dict, seed: int, records: list, plane):
    """Every reply against the reference, then the read-back."""
    n, l, w = _sizes(config)
    ref = Reference(l, w, seed)
    reply_bad = sum(ref.check_batch(b) for b in records)
    rng = np.random.default_rng([int(seed) % (1 << 64), 1])
    lines, nodes = ref.readback_plan(rng, int(mix["readback_records"]), n)
    r = n * int(config["clients_per_node"])
    rb_ver, rb_data = _read_back(plane, lines, nodes, r, w)
    readback_bad = ref.check_readback(lines, rb_ver, rb_data)
    compared = r * sum(not b.failed for b in records)
    checks = {"reply_mismatches": {"value": reply_bad, "limit": 0},
              "readback_mismatches": {"value": readback_bad, "limit": 0}}
    return checks, compared


def _read_back(plane, lines, nodes, r: int, width: int):
    """Read ``lines[i]`` from node ``nodes[i]`` through ``plane.ops``, in
    batches of the window's own shape."""
    vers, datas = [], []
    zeros_w = np.zeros((r, width), np.int32)
    zeros_i = np.zeros(r, np.int32)
    for s in range(0, lines.size, r):
        ln = np.full(r, -1, np.int32)
        nd = np.zeros(r, np.int32)
        k = min(r, lines.size - s)
        ln[:k], nd[:k] = lines[s:s + k], nodes[s:s + k]
        res = serve(plane, (nd, ln, zeros_i, zeros_w, None))
        if res is None:
            vers.append(np.full(k, -1, np.int32))
            datas.append(np.zeros((k, width), np.int32))
        else:
            vers.append(res.version[:k])
            datas.append(res.data[:k])
    if not vers:
        return np.zeros(0, np.int32), np.zeros((0, width), np.int32)
    return np.concatenate(vers), np.concatenate(datas)


def least_bytes(config: dict, records: list) -> int:
    return roofline.least_bytes(sum(b.ops for b in records),
                                int(config["payload_width"]))


# ---------------------------------------- compile hooks (no chip needed)

def _shapes(config: dict, devices: list):
    """The plane's state, op and payload shapes on ``devices``, as the
    harness's plane holds them, and the mesh (None on one chip)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P
    from repro.core.rounds import make_sharded_state, make_state

    n, l, w = _sizes(config)
    r = n * int(config["clients_per_node"])
    kw = _state_kw(config)
    if len(devices) == 1:
        one = SingleDeviceSharding(devices[0])
        shapes = jax.eval_shape(lambda: make_state(n, l, **kw))
        shard = {k: one for k in shapes}
        rs, mesh = one, None
    else:
        mesh = _mesh(devices)
        shapes = jax.eval_shape(
            lambda: make_sharded_state(n, l, mesh, **kw))
        shard = _stripes(mesh, shapes, l)
        rs = NamedSharding(mesh, P("shards"))
    state = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard[k])
             for k, s in shapes.items()}
    ops = [jax.ShapeDtypeStruct((r,), jnp.int32, sharding=rs)] * 3
    wd = jax.ShapeDtypeStruct((r, w), jnp.int32, sharding=rs)
    return state, ops, wd, mesh


def lower_verb(config: dict, devices: list):
    """The fused driver ``plane.ops`` runs (``run_rounds`` on one chip,
    ``run_rounds_sharded`` striped over more), lowered at the cell's
    shapes."""
    from repro.core.rounds import run_rounds, run_rounds_sharded
    state, ops, wd, mesh = _shapes(config, devices)
    n = int(config["n_nodes"])
    if mesh is None:
        return run_rounds.lower(state, *ops, wd, n_nodes=n)
    return run_rounds_sharded.lower(state, *ops, wd, mesh=mesh, n_nodes=n)


def lower_build(config: dict, devices: list):
    """The striped state build (``make_sharded_state``) over several
    ``devices``."""
    import jax
    from repro.core.rounds import make_sharded_state
    n, l, _ = _sizes(config)
    kw = _state_kw(config)
    mesh = _mesh(devices)
    shapes = jax.eval_shape(lambda: make_sharded_state(n, l, mesh, **kw))
    return jax.jit(lambda: make_sharded_state(n, l, mesh, **kw),
                   out_shardings=_stripes(mesh, shapes, l)).lower()
