"""Device time per coherence round: device busy time of the traced
window (mean over the cell's chips) over the rounds the traced calls
completed, in us.  Layer: the round body."""


def read(ctx):
    busy = ctx.trace.get("busy_s")
    if not busy or not ctx.traced_rounds:
        return None
    return 1e6 * busy / ctx.traced_rounds
