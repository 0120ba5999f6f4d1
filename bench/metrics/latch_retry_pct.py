"""Share of latch requests that failed and retried, in %.

100 x the sum of ``latch_failed`` over the sum of ``latch_requests``
(``PlaneTelemetry``, counted by the round body) over the traced
batches: a CAS lost to a peer or a read blocked by a writer comes back
next round, so this share drives the rounds per batch.  Layer: the
latch words (``apply_batch``).  A program without the counters, or a
window with no request, has nothing to read."""


def read(ctx):
    teles = [getattr(r, "telemetry", None) for r in ctx.traced_results]
    teles = [t for t in teles if hasattr(t, "latch_requests")]
    requests = sum(int(t.latch_requests) for t in teles)
    if not requests:
        return None
    return 100.0 * sum(int(t.latch_failed) for t in teles) / requests
