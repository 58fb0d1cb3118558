"""Reads (``network_server._channel_read`` → ``_serve_reads``): how long
a REST read waits from being queued to its batch being taken (the
aggregation window plus the loop's own delay), mean per read of the
``read_wait`` lane, window deltas."""

from benchmark.layers import lanes

snapshot = lanes.snapshot


def read(ctx):
    w = ctx.window
    if w.get("lane_n.read_wait", 0) <= 0:
        return None
    return 1e3 * w["lane_s.read_wait"] / w["lane_n.read_wait"]
