"""Front door + pipeline stages (``service/pipeline.py``,
``network_server.py``): host milliseconds per device dispatch in the
front door, deli, scribe, scriptorium, broadcaster, device stage and the
socket delivery sweep, each lane's OWN seconds (a stage that triggers a
device feed is not charged the feed: that is ``flush_host_ms``), window
deltas. Also says one ``lanes`` line: every lane's count, own and whole
milliseconds per dispatch."""

from benchmark.layers import lanes

snapshot = lanes.snapshot


def read(ctx):
    w = ctx.window
    host_s = lanes.seconds(w, lanes.PIPELINE, own=True)
    if host_s is None or w["pump_dispatches"] <= 0:
        return None
    per = 1e3 / w["pump_dispatches"]
    ctx.out.say("lanes", dispatches=w["pump_dispatches"], per_dispatch={
        key[len("lane_n."):]: {
            "n": n,
            "own_ms": per * w["lane_own_s." + key[len("lane_n."):]],
            "ms": per * w["lane_s." + key[len("lane_n."):]],
        }
        for key, n in w.items() if key.startswith("lane_n.") and n
    })
    return per * host_s
