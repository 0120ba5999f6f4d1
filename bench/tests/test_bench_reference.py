"""The plain reference accepts a coherent store and rejects faults."""

import numpy as np

from bench.control import IncoherentPlane
from bench.reference import Reference, Served
from bench.ycsb import Batches, init_bytes

MIX = {"read_proportion": 0.5, "update_proportion": 0.5,
       "request_distribution": "zipfian", "zipfian_constant": 0.99}
L, W, SEED = 512, 16, 2**31 + 11


class SerialStore:
    """A coherent store that serves each batch's slots one by one."""

    def __init__(self):
        self.mem = {}

    def ops(self, node, line, isw, wdata):
        ver = np.zeros(len(line), np.int32)
        data = np.zeros((len(line), W), np.int32)
        for i, ln in enumerate(line.tolist()):
            v, d = self.mem.get(ln, (0, init_bytes(SEED, [ln], W)[0]))
            if isw[i]:
                v, d = v + 1, wdata[i]
                self.mem[ln] = (v, d)
            ver[i], data[i] = v, d
        return ver, data


def history(plane, n=30):
    gen = Batches(MIX, n_records=L, n_nodes=4, clients_per_node=8,
                  width=W, seed=SEED)
    out = []
    for _ in range(n):
        node, line, isw, wdata, tags = gen.next()
        res = plane.ops(node, line, isw, wdata)
        ver, data = (res.version, res.data) if hasattr(res, "data") \
            else res
        out.append(Served(node, line, isw, tags, ver.copy(), data.copy()))
    return out


def check(served):
    ref = Reference(L, W, SEED)
    return sum(ref.check_batch(b) for b in served), ref


def test_coherent_store_passes():
    store = SerialStore()
    bad, ref = check(history(store))
    assert bad == 0
    lines, nodes = ref.readback_plan(np.random.default_rng(0), 64, 4)
    assert lines.size == 64 and (nodes >= 0).all()
    ver = np.array([store.mem[int(x)][0] for x in lines])
    data = np.stack([store.mem[int(x)][1] for x in lines])
    assert ref.check_readback(lines, ver, data) == 0


def test_corrupted_reply_is_caught():
    served = history(SerialStore())
    b = served[10]
    i = int(np.flatnonzero(b.is_write == 0)[0])
    b.data[i, 3] ^= 1
    assert check(served)[0] >= 1


def test_wrong_version_is_caught():
    served = history(SerialStore())
    b = served[12]
    i = int(np.flatnonzero(b.is_write == 1)[0])
    b.version[i] += 1
    assert check(served)[0] >= 1


def test_lost_write_is_caught_on_read_back():
    store = SerialStore()
    bad, ref = check(history(store))
    assert bad == 0
    lines, _ = ref.readback_plan(np.random.default_rng(0), 64, 4)
    ver = np.array([store.mem[int(x)][0] for x in lines])
    data = np.stack([store.mem[int(x)][1] for x in lines])
    # the last write of one record never reached memory
    ver[5] -= 1
    assert ref.check_readback(lines, ver, data) == 1


def test_stale_caches_are_caught():
    plane = IncoherentPlane({"payload_width": W}, SEED)
    assert check(history(plane))[0] > 0
