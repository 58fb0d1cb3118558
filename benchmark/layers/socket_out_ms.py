"""Front door + pipeline stages, the delivery sweep
(``network_server._drain_all``): host milliseconds per device dispatch in
the lane ``socket_out`` (every session's queue taken, every sequenced
message, signal and nack encoded and written to its socket), the lane's
OWN seconds, window deltas: the term of ``pipeline_host_ms`` that grows
with the number of sockets in a document."""

from benchmark.layers import lanes

snapshot = lanes.snapshot


def read(ctx):
    w = ctx.window
    if "lane_own_s.socket_out" not in w or w["pump_dispatches"] <= 0:
        return None
    return 1e3 * w["lane_own_s.socket_out"] / w["pump_dispatches"]
