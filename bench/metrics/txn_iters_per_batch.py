"""Scheduler iterations per ``plane.txn`` batch, mean over the traced
batches (the ``iters`` program counter of ``TxnBatchResult.counters``).
Each iteration is one read spin and one write spin, so this sets the
rounds a batch takes.  Layer: the transaction scheduler
(``core/rounds/txn.py``).  A result without the counter has nothing to
read."""


def read(ctx):
    vals = [r.counters["iters"] for r in ctx.traced_results
            if "iters" in getattr(r, "counters", {})]
    if not vals:
        return None
    return sum(vals) / len(vals)
