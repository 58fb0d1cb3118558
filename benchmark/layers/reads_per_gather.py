"""Reads (``device_backend.read_*``): channel reads served per device
gather, window deltas of ``reads_served`` and ``read_gathers``."""


def snapshot(srv) -> dict:
    dev = srv.service.device
    return {"reads_served": dev.reads_served, "read_gathers": dev.read_gathers}


def read(ctx):
    w = ctx.window
    if w["read_gathers"] <= 0:
        return None
    return w["reads_served"] / w["read_gathers"]
