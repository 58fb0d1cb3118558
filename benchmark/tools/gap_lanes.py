#!/usr/bin/env python3
"""The device's idle gaps of a run's profiler trace by host span, every
name and not the result line's ten, and the program's spans by name. The
split is ``trace_reduce.reduce``'s own (``split_gaps``: the innermost span
of the server's loop thread first, then any other thread's, then
``host:nothing-traced``), so this prints what the run's ``breakdown``
printed, unabridged: what PERF.md's "where the time goes" is written from.

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


def report(events) -> dict:
    r = T.reduce(events)
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
        "idle_s": r["window_s"] - r["busy_s"],
        "loop_thread": T.loop_thread(events),
        "idle_by_span": r["idle_by_span"],
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
