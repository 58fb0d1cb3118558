"""Server loop (``device_backend._consume_pending_scan``): the share of
the window the serving loop spent blocked in the health scan's device→host
readback (the ``scan_consume`` lane; a scan the ticker prefetched off the
loop costs the lane microseconds), window deltas."""

from benchmark.layers import lanes

snapshot = lanes.snapshot


def read(ctx):
    w = ctx.window
    blocked_s = lanes.seconds(w, ("scan_consume",))
    if blocked_s is None or w["t"] <= 0:
        return None
    return 100.0 * blocked_s / w["t"]
