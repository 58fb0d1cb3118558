"""Front door + pipeline stages, stage deli (``service/lambdas.py``,
``service/sequencer.py``): host milliseconds per device dispatch in the
deli runner's pump (read, ticket, emit, checkpoint), the lane's OWN
seconds, window deltas. Also says one ``deli`` line: how many op frames
the window's run pass ticketed (``deli_frames_batched``), how many it
handed to the per-record path (``deli_frames_single``), and the batched
share. A program without the two counts (the parent of the run pass)
reads the lane all the same and says the counts as absent."""

from benchmark.layers import lanes

COUNTS = ("deli_frames_batched", "deli_frames_single")


def snapshot(srv) -> dict:
    out = lanes.snapshot(srv)
    stats = getattr(srv.service, "stats", None)
    if out and stats is not None:
        counts = stats()
        out.update({k: counts[k] for k in COUNTS if k in counts})
    return out


def read(ctx):
    w = ctx.window
    if "lane_own_s.deli" not in w or w["pump_dispatches"] <= 0:
        return None
    batched, single = (w.get(k) for k in COUNTS)
    frames = (batched or 0) + (single or 0)
    ctx.out.say(
        "deli", dispatches=w["pump_dispatches"], own_s=w["lane_own_s.deli"],
        sweeps=w["lane_n.deli"], frames_batched=batched,
        frames_single=single,
        batched_share=batched / frames if frames else None,
    )
    return 1e3 * w["lane_own_s.deli"] / w["pump_dispatches"]
