"""Device step (``parallel/fleet._fused_sparse_step``): device
milliseconds per step inside the fused step's program that are not the
merge kernel: the scatter into the dense batch and the lane
slice/bitcast fusions around ``apply_ops_packed``. From the trace's
device plane: the ``XLA Modules`` events of the program named
``jit_fluid_step`` less the kernel's events inside them, per step. A
trace without that module (a program that does not name its step, or a
platform without a module line) reads nothing."""

import os
import re

from benchmark import trace_reduce as T

MODULES = "XLA Modules"
STEP = re.compile(r"^jit_fluid_step(\(\d+\))?$")
KERNEL = re.compile(r"^apply_ops_packed(\.\d+)?$")


def glue_ms(events) -> float:
    """Mean over the step programs on the first device plane of (the
    program's time less its kernel events' time), milliseconds; None
    where no step program was traced."""
    planes = sorted({e.plane for e in events if T.DEVICE_PLANE.match(e.plane)})
    if not planes:
        return None
    on = [e for e in events if e.plane == planes[0] and e.dur_ns > 0]
    steps = [e for e in on if e.line == MODULES and STEP.match(e.name)]
    if not steps:
        return None
    kernels = sorted(
        (e.start_ns, e.dur_ns) for e in on
        if e.line in T.OP_LINES and KERNEL.match(e.name)
    )
    glue_ns = k = 0
    for step in sorted(steps, key=lambda e: e.start_ns):
        end = step.start_ns + step.dur_ns
        while k < len(kernels) and kernels[k][0] < step.start_ns:
            k += 1
        inside = 0
        while k < len(kernels) and kernels[k][0] < end:
            inside += kernels[k][1]
            k += 1
        glue_ns += step.dur_ns - inside
    return glue_ns / len(steps) / 1e6


def read(ctx):
    return glue_ms(T.load_xplane(os.path.join(ctx.run_dir, "trace")))
