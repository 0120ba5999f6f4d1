"""Share of the HBM-bandwidth roofline of the traced batches, in %.

The least bytes the batches served in the traced calls had to move (the
cell's driver counts them: ``bench.roofline`` for the key-value ops),
at the peak HBM bandwidth of the cell's chips (``bench.peaks``), over
the device busy time they took.  Layer: the kernels and device work of
the verb, whichever backend serves it."""

from bench.roofline import roofline_share


def read(ctx):
    busy = ctx.trace.get("busy_s")
    if not busy or not ctx.traced_bytes:
        return None
    return 100.0 * roofline_share(ctx.traced_bytes, busy,
                                  ctx.peak["hbm_bytes_per_s"],
                                  ctx.n_chips)
