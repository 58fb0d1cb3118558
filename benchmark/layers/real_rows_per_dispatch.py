"""Boxcar fill (``device_backend.pump_stage``): op rows per device
dispatch before padding to the [B, K] bucket, window deltas of
``flush_totals["real_rows"]`` and ``pump_dispatches``."""


def snapshot(srv) -> dict:
    dev = srv.service.device
    if "real_rows" not in dev.flush_totals:
        return {}
    return {
        "real_rows": dev.flush_totals["real_rows"],
        "pump_dispatches": dev.pump_dispatches,
    }


def read(ctx):
    w = ctx.window
    if "real_rows" not in w or w["pump_dispatches"] <= 0:
        return None
    return w["real_rows"] / w["pump_dispatches"]
