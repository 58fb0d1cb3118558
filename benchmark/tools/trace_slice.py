#!/usr/bin/env python3
"""A short piece of a run's profiler trace as plain events: how the
recorded traces of ``benchmark/tests/data`` were made. An event is written
with its thread (six fields; the recordings of PR 26 and PR 27 hold five).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace 1
    python3 benchmark/tools/trace_slice.py <cell> <seconds> <out.json.gz>
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import trace_reduce

    cell, seconds, out = sys.argv[1], float(sys.argv[2]), sys.argv[3]
    events = trace_reduce.load_xplane(
        os.path.join(ROOT, "benchmark_out", cell, "trace")
    )
    lo = min(e.start_ns for e in events if e.dur_ns > 0)
    lo += int(0.5e9)  # past the profiler's own start-up
    trace_reduce.dump_json(
        [e for e in events if lo <= e.start_ns < lo + seconds * 1e9], out
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
