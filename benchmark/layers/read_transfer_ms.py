"""Reads (``device_backend.read_transfer``, executor thread): the
device→host wait of one read batch, behind whatever steps the device has
queued, mean per gather of the ``read_transfer`` lane, window deltas."""

from benchmark.layers import lanes

snapshot = lanes.snapshot


def read(ctx):
    w = ctx.window
    if w.get("lane_n.read_transfer", 0) <= 0:
        return None
    return 1e3 * w["lane_s.read_transfer"] / w["lane_n.read_transfer"]
