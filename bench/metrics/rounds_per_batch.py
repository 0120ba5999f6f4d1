"""Coherence rounds per batch: ``PlaneResult.rounds``, mean over the
window's batches.  Layer: the fused driver's spin loop."""


def read(ctx):
    if not ctx.rounds:
        return None
    return sum(ctx.rounds) / len(ctx.rounds)
