#!/usr/bin/env python3
"""What one ``profiler.span`` costs on this machine's host, microseconds:
disarmed with no trace running (the serving default), with the timeline
armed, and under a running JAX profiler trace taken the way the
benchmark takes it (python tracer off). The numbers of PERF.md's
"what tracing costs" come from this, run on the chip's machine.

    python3 benchmark/tools/span_cost.py
"""

import json
import os
import sys
import tempfile
import timeit

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax

    from fluidframework_tpu.telemetry import profiler

    def stage():  # a sweep's stage span
        with profiler.span("deli"):
            pass

    def boxcar():  # a boxcar's span, with its id and rows as event stats
        with profiler.span("host_stage", boxcar=3, rows=512):
            pass

    def us(fn, n=200_000):
        fn()
        return 1e6 * min(timeit.repeat(fn, number=n, repeat=5)) / n

    out = {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "disarmed_us": {"stage": us(stage), "boxcar": us(boxcar)},
    }
    profiler.arm(3_600_000)
    out["armed_us"] = {"stage": us(stage), "boxcar": us(boxcar)}
    profiler.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["traced_us"] = {
                "stage": us(stage, 20_000), "boxcar": us(boxcar, 20_000),
            }
        finally:
            jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
