"""Host time of the copies after the loop, per traced verb call.

The ``plane.telemetry`` (every counter leaf to the host) and
``plane.results`` (versions and data to the host) children of
``DevicePlane``'s verb bracket, as the program reports them in
``PlaneResult.host_s``; their sum, mean over the traced calls, in ms.
Layer: the DevicePlane verb.  A program without the spans has nothing
to read."""


def read(ctx):
    vals = [r.host_s["telemetry"] + r.host_s["results"]
            for r in ctx.traced_results
            if {"telemetry", "results"} <= set(getattr(r, "host_s", {}))]
    if not vals:
        return None
    return 1e3 * sum(vals) / len(vals)
