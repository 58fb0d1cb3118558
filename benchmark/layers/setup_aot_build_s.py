"""Start-up (``parallel/aot.py``): seconds spent lowering and compiling
AOT entries before the window: ``aot.stats()["build_s"]`` at the first
snapshot this reader is asked for, which ``run.py`` takes right after
warm-up, at the instant ``setup_s`` ends."""

_first: list = []


def snapshot(srv) -> dict:
    from fluidframework_tpu.parallel import aot

    if not _first:
        _first.append(aot.stats().get("build_s"))
    return {}


def read(ctx):
    return _first[0] if _first else None
