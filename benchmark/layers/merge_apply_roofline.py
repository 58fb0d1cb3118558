"""Kernel (``ops/pallas_kernel.py``): the merge-apply kernel's share of the
MEMORY roofline, reckoned by what the boxcar's work needs and not by what
today's step sweeps.

A boxcar touches its busy documents and no others. The least the chip
must move for one step is each busy document's segment lanes once in and
once out: busy documents x capacity x 15 lanes x 4 bytes, twice
(``step_bytes``). The op rows the step reads (B x K x 16 x 4 bytes, 3% of
that at K=8) are LEFT OUT, so the count errs low. That over the chip's HBM
bandwidth (``peaks.json``, keyed by ``device_kind``; an unknown kind is an
error) is the least time a step can take; divided by the kernel's mean
traced time per call it is the share. A step that sweeps the whole pool
for 128 documents reads some thousandths of a per cent: ROADMAP S2, stated
as a number. ROADMAP S5 expects the kernel bound inside VMEM, so a low
share is expected of a busy-set step too.

Busy documents per dispatch come from the counters taken at the trace's
start and stop, the same stretch as the kernel's time: a ``bulk_ingest``
batch draws its documents without replacement and gives each one frame,
so documents = real op rows / ``ops_per_frame``. That holds only while
one batch is one boxcar, which the reader checks (real rows per dispatch
== ``frames_per_batch`` x ``ops_per_frame``); where it does not hold it
reads nothing and says why. The count can understate the share, never
overstate it.

The kernel is found by name, ``KERNEL``; the harness prints the names the
trace holds, and the reader says one line with the numbers it used.
"""

import re

from benchmark import harness as H
from benchmark.layers import real_rows_per_dispatch

SEGMENT_LANES = 15  # ops/segment_state.py SEGMENT_LANES at PR 24
KERNEL = re.compile(r"^apply_ops_packed(\.\d+)?$")

snapshot = real_rows_per_dispatch.snapshot  # real_rows, pump_dispatches


def step_bytes(documents: float, capacity: int) -> float:
    return documents * capacity * SEGMENT_LANES * 4 * 2


def busy_documents(ctx):
    """Mean busy documents per dispatch over the traced stretch, or None
    and a line saying why."""
    c, p = ctx.trace["counters"], ctx.params
    if "real_rows" not in c or not {"frames_per_batch", "ops_per_frame"} <= p.keys():
        why = "no real_rows counter, or a mix that is not batches of equal frames"
    elif c["pump_dispatches"] <= 0:
        why = "no dispatch in the traced stretch"
    elif c["real_rows"] != (
        c["pump_dispatches"] * p["frames_per_batch"] * p["ops_per_frame"]
    ):
        why = "a boxcar is not one batch: its rows do not count its documents"
    else:
        return c["real_rows"] / p["ops_per_frame"] / c["pump_dispatches"]
    ctx.out.say(
        "merge_apply_roofline", read=None, why=why,
        real_rows=c.get("real_rows"), dispatches=c.get("pump_dispatches"),
        frames_per_batch=p.get("frames_per_batch"),
        ops_per_frame=p.get("ops_per_frame"),
    )
    return None


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
    documents = busy_documents(ctx)
    if documents is None:
        return None
    n_slots, capacity = ctx.base_pool
    least = step_bytes(documents, capacity)
    share = 100.0 * least / peaks[kind]["hbm_bytes_per_s"] / (seconds / calls)
    ctx.out.say(
        "merge_apply_roofline", read=share,
        busy_documents_per_dispatch=documents, capacity=capacity,
        least_bytes=least, hbm_bytes_per_s=peaks[kind]["hbm_bytes_per_s"],
        kernel_calls=calls, mean_kernel_ms=1e3 * seconds / calls,
        dispatches=ctx.trace["counters"]["pump_dispatches"],
        base_pool_slots=n_slots,
        whole_pool_bytes=step_bytes(n_slots, capacity),
    )
    return share
