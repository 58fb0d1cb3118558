"""The service as a reader sees it (the benchmark's own clock): the median
REST channel read from its due instant, over the window's reads; the
steadier statistic beside ``read_p95_ms.ws``."""


def read(ctx):
    return ctx.result.get("notes", {}).get("read_p50_ms")
