"""Server loop (``network_server._lag_sentinel``): how late the loop woke
a task that slept 25 ms, mean over the window's ticks: what anything
queued on the loop waits before its turn."""


def snapshot(srv) -> dict:
    if not hasattr(srv, "lag_sum_ms"):
        return {}
    return {"lag_sum_ms": srv.lag_sum_ms, "lag_ticks": srv.lag_ticks}


def read(ctx):
    w = ctx.window
    if w.get("lag_ticks", 0) <= 0:
        return None
    return w["lag_sum_ms"] / w["lag_ticks"]
