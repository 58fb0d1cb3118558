"""Host staging + ring (``service/device_backend.py``): host milliseconds
per device dispatch, from the program's own ``flush_totals`` (staging,
dispatch and routing seconds) over ``pump_dispatches``, window deltas."""


def snapshot(srv) -> dict:
    dev = srv.service.device
    totals = dev.flush_totals
    return {
        "staging_s": totals["staging_s"], "dispatch_s": totals["dispatch_s"],
        "routing_s": totals["routing_s"],
        "pump_dispatches": dev.pump_dispatches,
    }


def read(ctx):
    w = ctx.window
    if w["pump_dispatches"] <= 0:
        return None
    host_s = w["staging_s"] + w["dispatch_s"] + w["routing_s"]
    return 1e3 * host_s / w["pump_dispatches"]
