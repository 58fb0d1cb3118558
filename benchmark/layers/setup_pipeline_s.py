"""Start-up (``service/pipeline.py``): seconds the front door and the
pipeline stages took before the window (the joins and the load of the
resident fleet go through them): the seven pipeline lanes' own seconds
at the first snapshot this reader is asked for, which ``run.py`` takes
right after warm-up, at the instant ``setup_s`` ends."""

from benchmark.layers import lanes

_first: list = []


def snapshot(srv) -> dict:
    if not _first:
        _first.append(
            lanes.seconds(lanes.snapshot(srv), lanes.PIPELINE, own=True)
        )
    return {}


def read(ctx):
    return _first[0] if _first else None
