"""The service as a reader sees it (the benchmark's own clock): the 95th
percentile of a REST channel read from its due instant, over the window's
reads. Until PR 28 an end-to-end metric: a thousand reads a window leave
50 in this tail, and its runs spread by more than half of the widest bound
the contract admits (PERF.md section 2)."""


def read(ctx):
    return ctx.result.get("metrics", {}).get("read_p95_ms")
