"""Reads, a table's grid (``device_backend.grid_from_state``): host
milliseconds per grid joined in the lane ``matrix_read`` (both axes'
handles read out of the gathered states, the cells of the gather's cut
looked up under them, the unreachable ones dropped), inside
``read_finish``, window deltas of the lane's seconds and of
``matrix_reads``."""

from benchmark.layers import matrix_counts

snapshot = matrix_counts.snapshot


def read(ctx):
    w = ctx.window
    if "lane_s.matrix_read" not in w or w.get("matrix.matrix_reads", 0) <= 0:
        return None
    return 1e3 * w["lane_s.matrix_read"] / w["matrix.matrix_reads"]
