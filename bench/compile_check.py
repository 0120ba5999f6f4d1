"""Compile each cell's verb for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 -m bench.compile_check [--cells a,b]

For every cell of ``BENCHMARK.json`` this builds the cell's state shapes
(the set-up's jitted build) and the fused driver ``DevicePlane.ops``
runs at the cell's size (``run_rounds`` on one chip, ``run_rounds_sharded``
over a 2x2 mesh of four), compiles both for a ``v5e:2x2`` topology, and
prints the bytes per device.  It also compiles the flat driver at the
four-chip cell's record count on one chip, which has to be refused: that
table does not fit one chip.  Nothing runs, so nothing here is a time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HBM = 16 * 2**30


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return {"argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "total_bytes": total, "fits": total <= HBM}


def check(cfg: dict, chips: int, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (AxisType, Mesh, NamedSharding,
                              SingleDeviceSharding)
    from jax.sharding import PartitionSpec as P
    from repro.core.rounds import (make_sharded_state, make_state,
                                   run_rounds, run_rounds_sharded)

    n, l, w = (int(cfg["n_nodes"]), int(cfg["recordcount"]),
               int(cfg["payload_width"]))
    r = n * int(cfg["clients_per_node"])
    kw = dict(write_back=bool(cfg["write_back"]), payload_width=w)
    if chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        shapes = jax.eval_shape(lambda: make_state(n, l, **kw))
        state = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
                 for k, s in shapes.items()}
        ops = [jax.ShapeDtypeStruct((r,), jnp.int32, sharding=one)] * 3
        wd = jax.ShapeDtypeStruct((r, w), jnp.int32, sharding=one)
        verb = jax.jit(run_rounds, static_argnames=("n_nodes",)).lower(
            state, *ops, wd, n_nodes=n).compile()
        return {"chips": 1, "verb": _mem(verb),
                "has_custom_call": "tpu_custom_call" in verb.as_text()}
    mesh = Mesh(np.array(topo.devices[:chips]), ("shards",),
                axis_types=(AxisType.Auto,))
    shapes = jax.eval_shape(lambda: make_sharded_state(n, l, mesh, **kw))
    shard = {k: NamedSharding(mesh, P(*[
        "shards" if d == s.shape.index(l) else None
        for d in range(len(s.shape))])) for k, s in shapes.items()}
    state = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard[k])
             for k, s in shapes.items()}
    rs = NamedSharding(mesh, P("shards"))
    ops = [jax.ShapeDtypeStruct((r,), jnp.int32, sharding=rs)] * 3
    wd = jax.ShapeDtypeStruct((r, w), jnp.int32,
                              sharding=NamedSharding(mesh, P("shards")))
    build = jax.jit(lambda: make_sharded_state(n, l, mesh, **kw),
                    out_shardings=shard).lower().compile()
    verb = run_rounds_sharded.lower(state, *ops, wd, mesh=mesh,
                                    n_nodes=n).compile()
    text = verb.as_text()
    return {"chips": chips, "build": _mem(build), "verb": _mem(verb),
            "all_to_all": text.count("all-to-all("),
            "all_reduce": text.count("all-reduce(")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax.experimental import topologies
    from bench import manifest
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    man = manifest.load()
    names = ([c for c in args.cells.split(",") if c]
             or [w["name"] for w in man["workloads"]])
    for name in names:
        cell = manifest.cell(name, man)
        print(json.dumps({"cell": name, "recordcount":
                          cell.config["recordcount"],
                          **check(cell.config, cell.chips, topo)}),
              flush=True)
        if cell.chips > 1:
            try:
                res = check(cell.config, 1, topo)
            except Exception as e:                 # the compiler refuses
                res = {"refused": f"{type(e).__name__}: {str(e)[:300]}"}
            print(json.dumps({"cell": name, "on_one_chip": res}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
