#!/usr/bin/env python3
"""Several runs of the benchmark's command in one call, one after another,
each a process of its own (this one never touches JAX, so the chip is
free for each child). Every run's output goes to
``chiprun_out/<label>/<seed>.log`` and its last line, with the seed, the exit
code and the wall seconds, to ``chiprun_out/<label>/results.jsonl``.

    python3 benchmark/tools/runs.py --label ingest-a --workload h100k-ingest-zipf \\
        --seeds 101,102,103 --seconds 20 --trace 0 [-- extra arguments of run.py]

``--burners N`` keeps N busy-looping processes beside every run: a stand-in
for a neighbour on a shared host, to see how far a cell's numbers move.

How the spreads, the knee sweep and the seeds of PERF.md were measured.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--label", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--burners", type=int, default=0)
    ap.add_argument("extra", nargs="*")
    args = ap.parse_args()
    # Results go where the caller stands; the runs are made in the
    # checkout this file belongs to (a ``git archive`` copy, say).
    out_dir = os.path.join(os.getcwd(), "chiprun_out", args.label)
    os.makedirs(out_dir, exist_ok=True)
    rc_all = 0
    for n, seed in enumerate(args.seeds.split(",")):
        cmd = [
            sys.executable, "benchmark/run.py", "--workload", args.workload,
            "--seed", seed, "--seconds", args.seconds, "--trace", args.trace,
            *args.extra,
        ]
        t0 = time.time()
        burners = [
            subprocess.Popen([sys.executable, "-c", "while True: pass"])
            for _ in range(args.burners)
        ]
        try:
            with open(os.path.join(out_dir, f"{seed}.log"), "w") as log:
                proc = subprocess.run(
                    cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=log, text=True
                )
                log.write("\n--- stdout ---\n" + proc.stdout)
        finally:
            for b in burners:
                b.kill()
            for b in burners:
                b.wait()
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
        try:
            last = json.loads(lines[-1]) if lines else None
        except ValueError:
            last = None
        rec = {
            "seed": int(seed), "rc": proc.returncode,
            "wall_s": round(time.time() - t0, 2), "result": last,
            "extra": args.extra, "burners": args.burners,
        }
        with open(os.path.join(out_dir, "results.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
        rc_all = rc_all or proc.returncode
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
