"""Share of served ops that hit their node's own cached copy, in %.

100 x the sum of ``local_hits`` (served slots whose group was served
from the node's cached copy with no latch request: SELCC's lazy latch)
over the ops served, over the traced batches (``PlaneTelemetry``).
Layer: the node caches.  A program without the counter, or a window
that served nothing, has nothing to read."""


def read(ctx):
    teles = [getattr(r, "telemetry", None) for r in ctx.traced_results]
    teles = [t for t in teles if hasattr(t, "local_hits")]
    served = sum(t.served for t in teles)
    if not served:
        return None
    return 100.0 * sum(int(t.local_hits) for t in teles) / served
