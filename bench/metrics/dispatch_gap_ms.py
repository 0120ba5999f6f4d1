"""Host time of a ``plane.ops`` call that the device does not cover.

Per traced call: the ``bench.ops`` annotation's wall time minus the
device busy time inside it (mean over the cell's chips); the mean over
the traced calls, in ms.  Layer: the DevicePlane verb (padding,
dispatch, the blocking sync and the telemetry copy)."""


def read(ctx):
    calls = ctx.trace.get("calls")
    if not calls:
        return None
    return 1e3 * sum(c["wall_s"] - c["busy_s"] for c in calls) / len(calls)
