"""Front door + pipeline stages: milliseconds per device dispatch that the
cyclic collector held the process (the ``gc_pause`` lane: every pass
bracketed by ``gc.callbacks``), window deltas. The pauses fall inside
whatever stage lane was open, so this is a part of ``pipeline_host_ms``
and ``flush_host_ms``, not a term beside them. Pauses are buffered until
something drains them: ``snapshot`` drains first. Also says one
``gc_pause`` line: the window's passes by generation
(``gc_pauses_total{gen}``), their seconds, and the process's peak
resident set so far (the host's memory is part of the result)."""

import resource

from benchmark.layers import lanes

GENERATIONS = ("0", "1", "2")


def snapshot(srv) -> dict:
    from fluidframework_tpu.telemetry import profiler

    if not hasattr(profiler, "totals"):  # the parent of the lane totals
        return {}
    profiler.drain_gc_events()
    out = lanes.snapshot(srv)
    passes = profiler.gc_pause_counter()
    for gen in GENERATIONS:
        out[f"gc_passes.{gen}"] = passes.value(gen=gen)
    return out


def read(ctx):
    w = ctx.window
    if "lane_s.gc_pause" not in w or w["pump_dispatches"] <= 0:
        return None
    ctx.out.say(
        "gc_pause", dispatches=w["pump_dispatches"],
        pause_s=w["lane_s.gc_pause"], passes=w["lane_n.gc_pause"],
        passes_by_generation={
            gen: w.get(f"gc_passes.{gen}") for gen in GENERATIONS
        },
        host_peak_rss_kb=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss,
    )
    return 1e3 * w["lane_s.gc_pause"] / w["pump_dispatches"]
