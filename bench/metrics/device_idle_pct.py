"""Share of the traced window in which no operation ran on the device,
mean over the cell's chips, in %.  Layer: the device."""


def read(ctx):
    window = ctx.trace.get("window_s")
    if not window:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / window)
