"""Device step (``parallel/fleet.py``, ``parallel/aot.py``): device busy
milliseconds per dispatch, both taken over the traced part of the window:
the trace's busy time over the ``pump_dispatches`` counted between the
trace's start and stop."""


def snapshot(srv) -> dict:
    return {"pump_dispatches": srv.service.device.pump_dispatches}


def read(ctx):
    n = ctx.trace["counters"]["pump_dispatches"]
    if n <= 0 or ctx.trace["busy_s"] <= 0:
        return None
    return 1e3 * ctx.trace["busy_s"] / n
