#!/usr/bin/env python3
"""The device's idle gaps of a run's profiler trace, split among the
INNERMOST host spans that cover them on the server's loop thread (the
thread that carries the program's ``fluid.*`` spans), and the program's
spans by name. ``trace_reduce`` charges a whole gap to one span; this
splits it, which is what PERF.md's "where the time goes" is written from.
The profiler names every Python thread ``python3`` and ``trace_reduce``
keeps a line's name only, so the loop, the benchmark's main thread and the
executor threads are one line here: ``(no span)`` means none of them was
in a span, and a span of another thread that overlaps counts as nested.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace 1
    python3 benchmark/tools/gap_lanes.py <cell> [<out.json>]
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as T  # noqa: E402

OURS = ("fluid.", "bench.")


def loop_line(events) -> tuple:
    """(plane, line) of the host thread with the most ``fluid.*`` spans."""
    seen: dict = {}
    for e in events:
        if e.name.startswith("fluid.") and not T.DEVICE_PLANE.match(e.plane):
            seen[(e.plane, e.line)] = seen.get((e.plane, e.line), 0) + 1
    return max(seen, key=seen.get) if seen else None


def idle_gaps(events) -> list:
    """[start, end) of every gap between operations on the first device
    plane, from the trace's first to its last event."""
    planes = sorted({e.plane for e in events if T.DEVICE_PLANE.match(e.plane)})
    spans = [e for e in events if e.dur_ns > 0]
    if not planes or not spans:
        return []
    busy = T.union(
        (e.start_ns, e.start_ns + e.dur_ns) for e in spans
        if e.plane == planes[0] and e.line in T.OP_LINES
    )
    edges = [min(e.start_ns for e in spans)]
    edges += [t for s, e in busy for t in (s, e)]
    edges.append(max(e.start_ns + e.dur_ns for e in spans))
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def split(events, gaps) -> dict:
    """Seconds of the gaps by the innermost span of the loop thread that
    covers each instant; ``(no span)`` where none does."""
    where = loop_line(events)
    thread = sorted(
        (e for e in events if (e.plane, e.line) == where and e.dur_ns > 0),
        key=lambda e: e.start_ns,
    )
    out: dict = {}
    k = 0
    for a, b in gaps:
        while k < len(thread) and thread[k].start_ns + thread[k].dur_ns <= a:
            k += 1  # sorted by start: what ended before this gap is done
        clipped = []
        for e in thread[k:]:
            if e.start_ns >= b:
                break
            lo, hi = max(a, e.start_ns), min(b, e.start_ns + e.dur_ns)
            if hi > lo:
                clipped.append(T.Event(e.plane, e.line, e.name, lo, hi - lo))
        own = T.self_times(clipped)
        for name, s in own.items():
            out[name] = out.get(name, 0.0) + s
        rest = (b - a) / 1e9 - sum(own.values())
        if rest > 0:
            out["(no span)"] = out.get("(no span)", 0.0) + rest
    return out


def report(events) -> dict:
    gaps = idle_gaps(events)
    ours: dict = {}
    for e in events:
        if e.name.startswith(OURS):
            n, s = ours.get(e.name, (0, 0.0))
            ours[e.name] = (n + 1, s + e.dur_ns / 1e9)
    bulk = [
        (e.start_ns, e.start_ns + e.dur_ns) for e in events
        if e.name == "bench.submit_frames_bulk"
    ]
    stages = [e for e in events if e.name.startswith("fluid.")]
    inside = sum(
        any(lo <= e.start_ns and e.start_ns + e.dur_ns <= hi for lo, hi in bulk)
        for e in stages
    )
    return {
        "idle_s": sum(b - a for a, b in gaps) / 1e9,
        "gaps": len(gaps),
        "loop_thread": loop_line(events),
        "idle_by_innermost_span": sorted(
            split(events, gaps).items(), key=lambda kv: -kv[1]
        ),
        "spans": {k: list(v) for k, v in sorted(ours.items())},
        "fluid_spans_inside_bench_submit_frames_bulk": [inside, len(stages)],
    }


def main() -> int:
    cell = sys.argv[1]
    events = T.load_xplane(os.path.join(ROOT, "benchmark_out", cell, "trace"))
    text = json.dumps(report(events), indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
