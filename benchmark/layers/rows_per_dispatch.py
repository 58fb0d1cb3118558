"""Boxcar fill (``device_backend.pump_feed``): op rows staged per device
dispatch, window deltas of ``flush_totals["staged_rows"]`` and
``pump_dispatches``."""


def snapshot(srv) -> dict:
    dev = srv.service.device
    return {
        "staged_rows": dev.flush_totals["staged_rows"],
        "pump_dispatches": dev.pump_dispatches,
    }


def read(ctx):
    w = ctx.window
    if w["pump_dispatches"] <= 0:
        return None
    return w["staged_rows"] / w["pump_dispatches"]
