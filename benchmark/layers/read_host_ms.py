"""Reads (``network_server._serve_reads``): the loop's own milliseconds
per device gather: the pipeline pump and device flush the batch forces
(``read_settle``, whole seconds: the flush is the read's), the gather
dispatch (``read_gather``) and the host split, text and JSON
(``read_finish``), window deltas."""

from benchmark.layers import lanes

snapshot = lanes.snapshot
LANES = ("read_settle", "read_gather", "read_finish")


def read(ctx):
    w = ctx.window
    host_s = lanes.seconds(w, LANES)
    if host_s is None or w.get("lane_n.read_gather", 0) <= 0:
        return None
    return 1e3 * host_s / w["lane_n.read_gather"]
