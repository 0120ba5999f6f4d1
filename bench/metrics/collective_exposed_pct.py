"""Collective time with no other operation running beside it, as a
share of device busy time, mean over the cell's chips, in %.

Collectives are the device operations HLO names all-to-all, all-reduce
(the psum), all-gather, reduce-scatter or collective-permute.  Layer:
the sharded routing (two all_to_alls and a psum per round).  A trace
with no collective in it has nothing to read."""


def read(ctx):
    coll = ctx.trace.get("collective_s")
    if not coll or not any(coll.values()):
        return None
    busy = ctx.trace["busy_by_chip"]
    exposed = ctx.trace["collective_exposed_s"]
    shares = [exposed[c] / busy[c] for c in busy if busy[c] > 0]
    return 100.0 * sum(shares) / len(shares)
