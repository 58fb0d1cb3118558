"""Load generator (the benchmark's own): how late the children sent their
frames against the schedule, 95th percentile over the window's frames. A
starved generator shows here, so that it is not read as a fast server."""


def read(ctx):
    return ctx.result.get("layer", {}).get("gen_late_p95_ms")
