"""The controls: the reference's store, in the plane's place, with one
guarantee broken, to show that the check catches a plane that breaks it.

``incoherent`` (:class:`IncoherentPlane`) keeps records and per-node
caches as the plain reference would, but never invalidates a peer's
cached copy when a node writes: a node keeps reading its own stale
copy.  That breaks the configurations' stated guarantee that a read sees
every write acknowledged before its batch.  A mix without writes cannot
show it.

``half_width`` (:class:`HalfWidthPlane`) keeps its caches coherent but
holds each cached lane in 16 bits, the low half of its int32, as a
change that halves the [N, L, W] node-cache images would: a read served
from a node's cached copy returns other bytes than memory holds.  That
breaks the coherent-cache guarantee on any mix, reads alone included.

Everything else runs as in a run of the cell: the same traffic from the
same seed, the same number of batches as a run of the cell holds, the
same reference and read-back.

    python3 -m bench.control --workload ycsb_a.l19 --seeds 1,2,3 \\
        --batches 250 [--control incoherent|half_width]

prints one JSON line per seed with the numbers compared.  It needs no
chip; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from .ycsb import init_bytes

ROOT = Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Reply:
    version: np.ndarray
    data: np.ndarray
    rounds: int = 1


class IncoherentPlane:
    """Write-through records with per-node caches that are never
    invalidated by a peer's write."""

    def __init__(self, config: dict, seed: int, devices=None):
        self.width = int(config["payload_width"])
        self.seed = seed
        self.memory: dict = {}                 # line -> (version, bytes)
        self.caches: dict = {}                 # (node, line) -> same

    def _memory(self, line: int):
        if line not in self.memory:
            self.memory[line] = (0, init_bytes(self.seed, [line],
                                               self.width)[0])
        return self.memory[line]

    def ops(self, node, line, is_write, wdata=None):
        r = len(line)
        ver = np.zeros(r, np.int32)
        data = np.zeros((r, self.width), np.int32)
        for i in range(r):
            ln, nd = int(line[i]), int(node[i])
            if ln < 0:
                continue
            if is_write[i]:
                entry = (self._memory(ln)[0] + 1, np.array(wdata[i]))
                self.memory[ln] = entry
                self.caches[(nd, ln)] = entry
            else:
                entry = self.caches.get((nd, ln)) or self._memory(ln)
                self.caches[(nd, ln)] = entry
            ver[i], data[i] = entry
        return Reply(ver, data)


class HalfWidthPlane(IncoherentPlane):
    """Coherent write-through caches (a write drops every peer's copy)
    that hold each lane's low 16 bits only."""

    def __init__(self, config: dict, seed: int, devices=None):
        super().__init__(config, seed, devices)
        self.holders: dict = {}                # line -> nodes caching it

    def _cache(self, node: int, line: int, entry) -> None:
        ver, data = entry
        self.caches[(node, line)] = (ver, np.asarray(data, np.int32)
                                     .astype(np.int16).astype(np.int32))
        self.holders.setdefault(line, set()).add(node)

    def ops(self, node, line, is_write, wdata=None):
        r = len(line)
        ver = np.zeros(r, np.int32)
        data = np.zeros((r, self.width), np.int32)
        for i in range(r):
            ln, nd = int(line[i]), int(node[i])
            if ln < 0:
                continue
            if is_write[i]:
                entry = (self._memory(ln)[0] + 1, np.array(wdata[i]))
                self.memory[ln] = entry
                for peer in self.holders.pop(ln, ()):
                    self.caches.pop((peer, ln), None)
                self._cache(nd, ln, entry)
            elif (nd, ln) in self.caches:
                entry = self.caches[(nd, ln)]
            else:
                entry = self._memory(ln)
                self._cache(nd, ln, entry)
            ver[i], data[i] = entry
        return Reply(ver, data)


CONTROLS = {"incoherent": IncoherentPlane, "half_width": HalfWidthPlane}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--batches", type=int, required=True,
                    help="window batches, as many as a run of the cell "
                         "holds")
    ap.add_argument("--control", choices=sorted(CONTROLS),
                    default="incoherent")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness, manifest
    cell = manifest.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(cell, seed=seed, seconds=0.0, trace=False,
                          t0=time.perf_counter(), require_tpu=False,
                          plane_factory=CONTROLS[args.control],
                          batches=args.batches, log=lambda _: None)
        print(json.dumps({"control": args.control,
                          "workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
