"""Front door + pipeline stages, stage ``device_stage``, matrix channels
(``device_backend.enqueue_matrix``): host milliseconds per device dispatch
in the lane ``matrix_stage`` (one span an op of a matrix channel: an axis
op lowered to its kernel row and buffered for its axis's slot, or a cell
written to the host store), the lane's OWN seconds, window deltas. The
span opens inside ``device_stage``, whose own seconds leave it out."""

from benchmark.layers import matrix_counts

snapshot = matrix_counts.snapshot


def read(ctx):
    w = ctx.window
    if "lane_own_s.matrix_stage" not in w or w["pump_dispatches"] <= 0:
        return None
    if w["lane_n.matrix_stage"] <= 0:
        return None
    return 1e3 * w["lane_own_s.matrix_stage"] / w["pump_dispatches"]
