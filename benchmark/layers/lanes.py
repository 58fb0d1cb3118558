"""What the lane readers share: the program's always-on lane totals
(``telemetry/profiler.totals()``: per lane a count, seconds, and own
seconds — less what spans opened inside it covered) as numbers a window
delta can subtract. A program without lane totals (the parent of the PR
that added them) gives no keys, and every reader of them reads nothing."""

# The served path's host stages, front door to sockets, in order.
PIPELINE = (
    "front_door", "deli", "scribe", "scriptorium", "broadcast",
    "device_stage", "socket_out",
)


def snapshot(srv) -> dict:
    from fluidframework_tpu.telemetry import profiler

    totals = getattr(profiler, "totals", None)
    if totals is None:
        return {}
    out = {}
    for lane, (n, seconds, own) in totals().items():
        out[f"lane_n.{lane}"] = n
        out[f"lane_s.{lane}"] = seconds
        out[f"lane_own_s.{lane}"] = own
    return out


def seconds(w: dict, lanes, own: bool = False):
    """Sum of the lanes' (own) seconds in a delta, None without totals."""
    key = "lane_own_s." if own else "lane_s."
    if any(key + lane not in w for lane in lanes):
        return None
    return sum(w[key + lane] for lane in lanes)
