"""The service as a writer sees it (the benchmark's own clock): the median
time from a frame's due instant to its writer receiving its own ops back
sequenced, over all frames due in the window. Until PR 28 an end-to-end
metric; on the check's shared host its runs spread by more than half of
the widest bound the contract admits, so it stands here, beside the tail
that stayed (PERF.md section 2)."""


def read(ctx):
    return ctx.result.get("metrics", {}).get("ack_p50_ms")
