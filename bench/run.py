"""Run one benchmark cell once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number the
correctness check compared, beside its limit.  The same numbers are the
last lines of standard error.  Without a TPU, or with fewer chips than
the cell needs, it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache lives at one fixed path inside the
# checkout, whatever the environment says, so that only a checkout's
# first run compiles and two checkouts share nothing
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness, manifest
    from repro.compile_cache import enable_compile_cache

    cell = manifest.cell(args.workload)
    enable_compile_cache()
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, however fast it compiled, so set-up repeats
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = harness.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t0=T0)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
