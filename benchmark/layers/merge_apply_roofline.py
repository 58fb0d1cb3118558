"""Kernel (``ops/pallas_kernel.py``): the merge-apply kernel's share of the
MEMORY roofline.

The least the chip must move for one whole-pool step is every segment lane
of the base pool once in and once out: slots x capacity x 15 lanes x 4
bytes, twice (``step_bytes``). That over the chip's HBM bandwidth
(``peaks.json``, keyed by ``device_kind``; an unknown kind is an error) is
the least time a step can take; divided by the kernel's mean traced time
it is the share. ROADMAP S5 expects this kernel to be bound inside VMEM
(VPU/XLU work per row), so a low share is the expected reading and names
how far the memory roofline is, not a fault.

The kernel carries no stable name yet; ``KERNEL`` matches what the trace
shows today and the harness prints the names it saw.
"""

import re

from benchmark import harness as H

SEGMENT_LANES = 15  # ops/segment_state.py SEGMENT_LANES at PR 24
KERNEL = re.compile(r"^apply_ops_packed(\.\d+)?$")


def step_bytes(n_slots: int, capacity: int) -> int:
    return n_slots * capacity * SEGMENT_LANES * 4 * 2


def read(ctx):
    peaks = H.load_json("peaks.json")["peaks"]
    kind = ctx.out.device["kind"]
    if ctx.rehearsal and ctx.out.device["platform"] != "tpu":
        return None  # a CPU rehearsal has no kernel time to read
    if kind not in peaks:
        raise KeyError(f"peaks.json has no entry for device kind {kind!r}")
    seconds = calls = 0
    for name, s in ctx.trace["ops"].items():
        if KERNEL.search(name):
            seconds += s
            calls += ctx.trace["op_counts"][name]
    if calls == 0:
        return None
    n_slots, capacity = ctx.base_pool
    least = step_bytes(n_slots, capacity) / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
