"""The ``gc_pause_ms`` reader (PR 32): the collector's lane as a window
delta per device dispatch. It reads a number from a program that has the
pause hooks and the lane totals (the parent of PR 32 has both, PR 27's),
nothing (and does not raise) from a program without the totals, and both
rehearsal cells report it with the ``gc_pause`` line beside it.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_gc_pause_ms.py -q

Nothing here is a time: every line says it ran on the CPU.
"""

import gc
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.layers import gc_pause_ms

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
SRV = None  # the reader's snapshot takes the server and reads the profiler alone


def _say_into(said):
    return types.SimpleNamespace(out=types.SimpleNamespace(
        say=lambda event, **kv: said.append((event, kv))
    ))


def test_gc_pause_is_the_lanes_window_delta_per_dispatch():
    """The collector's pauses reach the lane only when something drains
    them: the reader's snapshot does, so a pass still buffered when the
    window closes is counted, and one before it opens is not."""
    from fluidframework_tpu.telemetry import profiler

    profiler.reset()
    profiler._GC_PENDING.append((0.0, 0.5, 2))  # before the window
    before = {**gc_pause_ms.snapshot(SRV), "pump_dispatches": 3}
    assert not profiler._GC_PENDING and before["lane_s.gc_pause"] == 0.5
    profiler._GC_PENDING.extend(
        [(1.0, 1.004, 0), (2.0, 2.002, 0), (3.0, 3.010, 1)]
    )
    after = {**gc_pause_ms.snapshot(SRV), "pump_dispatches": 11}
    profiler.reset()
    said = []
    ctx = _say_into(said)
    ctx.window = {k: after[k] - before[k] for k in after}
    assert gc_pause_ms.read(ctx) == pytest.approx(16.0 / 8)
    (event, line), = said
    assert event == "gc_pause" and line["passes"] == 3
    assert line["passes_by_generation"] == {"0": 2.0, "1": 1.0, "2": 0.0}
    assert line["pause_s"] == pytest.approx(0.016)
    assert line["host_peak_rss_kb"] > 0


def test_gc_pause_reads_the_programs_own_hooks():
    """What the parent's program has: ``gc.callbacks`` hooks that buffer,
    a drain, the lane. A real collection between two snapshots reads a
    number; a window without one reads 0, not nothing."""
    from fluidframework_tpu.telemetry import profiler

    profiler.reset()
    installed = profiler.install_gc_hooks()
    try:
        first = {**gc_pause_ms.snapshot(SRV), "pump_dispatches": 0}
        gc.collect(0)
        second = {**gc_pause_ms.snapshot(SRV), "pump_dispatches": 4}
    finally:
        if installed:
            profiler.uninstall_gc_hooks()
    said = []
    ctx = _say_into(said)
    ctx.window = {k: second[k] - first[k] for k in second}
    value = gc_pause_ms.read(ctx)
    assert value > 0 and said[0][1]["passes_by_generation"]["0"] >= 1
    assert value == pytest.approx(1e3 * ctx.window["lane_s.gc_pause"] / 4)
    ctx.window = {k: 0 for k in second} | {"pump_dispatches": 4}
    assert gc_pause_ms.read(ctx) == 0.0


def test_gc_pause_reads_nothing_without_lane_totals(monkeypatch):
    from fluidframework_tpu.telemetry import profiler

    monkeypatch.delattr(profiler, "totals")
    assert gc_pause_ms.snapshot(SRV) == {}
    ctx = types.SimpleNamespace(out=None)
    ctx.window = {"pump_dispatches": 3, "t": 2.0}
    assert gc_pause_ms.read(ctx) is None
    # The lane is there and nothing was dispatched: nothing to divide by.
    ctx.window = {"pump_dispatches": 0, "lane_s.gc_pause": 0.1}
    assert gc_pause_ms.read(ctx) is None


@pytest.mark.parametrize("workload", ["rehearsal-ingest", "rehearsal-ws"])
def test_gc_pause_is_reported_on_a_rehearsal(workload):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "2147483832", "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines[-1]["correct"] is True
    name = f"gc_pause_ms.{workload.split('-')[1]}"
    assert lines[-1]["metrics"][name]["value"] >= 0
    said, = [ln for ln in lines if ln.get("event") == "gc_pause"]
    assert said["platform"] == "cpu" and said["dispatches"] > 0
    assert set(said["passes_by_generation"]) == {"0", "1", "2"}
    assert said["passes"] == sum(said["passes_by_generation"].values())
