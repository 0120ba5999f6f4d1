"""Share of transaction attempts that lost a no-wait conflict and
restarted, in %: 100 x the ``restarts`` program counter over
``attempts`` (transactions started, restarts included), summed over the
traced batches (``TxnBatchResult.counters``).  Layer: the transaction
scheduler (``core/rounds/txn.py``).  A result without the counters has
nothing to read."""


def read(ctx):
    cs = [r.counters for r in ctx.traced_results
          if "attempts" in getattr(r, "counters", {})]
    attempts = sum(c["attempts"] for c in cs)
    if not attempts:
        return None
    return 100.0 * sum(c["restarts"] for c in cs) / attempts
