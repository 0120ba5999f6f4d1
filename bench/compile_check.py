"""Compile each cell's verb for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 -m bench.compile_check [--cells a,b]

For every cell of ``BENCHMARK.json`` this takes from the cell's driver
(:mod:`bench.drivers`) the program one batch runs, lowered at the
cell's shapes (for the YCSB driver the fused loop of ``DevicePlane.ops``:
``run_rounds`` on one chip, ``run_rounds_sharded`` over a 2x2 mesh of
four) and, over several chips, the state build; compiles both for a
``v5e:2x2`` topology, and prints the bytes per device.  It also compiles
a four-chip cell's verb on one chip, which has to be refused: that
table does not fit one chip.  A driver without the hooks is skipped
with a message.  Nothing runs, so nothing here is a time.
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


def check(cell, chips: int, topo) -> dict | None:
    """Compile the cell's verb (and, over several chips, its state
    build) from its driver's hooks for ``chips`` devices of ``topo``;
    None when the driver has no hook."""
    lower_verb = getattr(cell.driver, "lower_verb", None)
    if lower_verb is None:
        return None
    devices = topo.devices[:chips]
    verb = lower_verb(cell.config, devices).compile()
    text = verb.as_text()
    if chips == 1:
        return {"chips": 1, "verb": _mem(verb),
                "has_custom_call": "tpu_custom_call" in text}
    lower_build = getattr(cell.driver, "lower_build", None)
    build = lower_build(cell.config, devices) if lower_build else None
    return {"chips": chips,
            **({"build": _mem(build.compile())} if build else {}),
            "verb": _mem(verb),
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
        res = check(cell, cell.chips, topo)
        if res is None:
            print(f"compile_check: {name}: its driver "
                  f"{cell.driver.__name__} has no lower_verb; skipped",
                  file=sys.stderr, flush=True)
            continue
        print(json.dumps({"cell": name, "recordcount":
                          cell.config["recordcount"], **res}), flush=True)
        if cell.chips > 1:
            try:
                res = check(cell, 1, topo)
            except Exception as e:                 # the compiler refuses
                res = {"refused": f"{type(e).__name__}: {str(e)[:300]}"}
            print(json.dumps({"cell": name, "on_one_chip": res}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
