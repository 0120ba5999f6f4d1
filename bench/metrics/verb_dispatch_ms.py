"""Host time of the call into the jitted loop, per traced verb call.

The ``plane.dispatch`` child of ``DevicePlane``'s verb bracket (argument
handling, enqueue, any compile), as the program reports it in
``PlaneResult.host_s``; the mean over the traced calls, in ms.  Layer:
the DevicePlane verb.  A program without the span has nothing to read."""


def read(ctx):
    vals = [r.host_s["dispatch"] for r in ctx.traced_results
            if "dispatch" in getattr(r, "host_s", {})]
    if not vals:
        return None
    return 1e3 * sum(vals) / len(vals)
