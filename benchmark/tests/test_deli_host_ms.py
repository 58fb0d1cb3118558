"""The ``deli_host_ms`` reader (PR 35): the deli lane's own seconds as a
window delta per device dispatch, with one ``deli`` line that says how
many op frames the run pass ticketed and how many took the per-record
path. It reads the lane from a program that has the lane totals and not
the two counts (the parent of PR 35) and says the counts as absent,
nothing (and does not raise) from a program without the totals, and both
rehearsal cells report it.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_deli_host_ms.py -q

Nothing here is a time: every line says it ran on the CPU.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.layers import deli_host_ms

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _server(counts=None):
    service = types.SimpleNamespace()
    if counts is not None:
        service.stats = lambda: dict(counts)
    return types.SimpleNamespace(service=service)


def _read(before, after):
    said = []
    ctx = types.SimpleNamespace(out=types.SimpleNamespace(
        say=lambda event, **kv: said.append((event, kv))
    ))
    ctx.window = {k: after[k] - before[k] for k in after}
    return deli_host_ms.read(ctx), said


def test_deli_host_ms_is_the_lanes_own_seconds_per_dispatch():
    """Own seconds: what a span opened inside the deli sweep covers (a
    collector pause has its own lane and no span, so it stays in) is not
    deli's. The counts are window deltas too."""
    from fluidframework_tpu.telemetry import profiler

    profiler.reset()
    counts = {"deli_frames_batched": 100, "deli_frames_single": 7}
    srv = _server(counts)
    profiler.record("deli", 0.0, 9.0)  # before the window
    before = {**deli_host_ms.snapshot(srv), "pump_dispatches": 2}
    for _ in range(3):
        with profiler.span("deli"):
            with profiler.span("front_door"):
                pass
    profiler.record("deli", 0.0, 0.012)
    counts.update(deli_frames_batched=480, deli_frames_single=27)
    after = {**deli_host_ms.snapshot(srv), "pump_dispatches": 10}
    profiler.reset()
    value, said = _read(before, after)
    own = after["lane_own_s.deli"] - before["lane_own_s.deli"]
    assert 0.012 <= own < after["lane_s.deli"] - before["lane_s.deli"] + 1e-12
    assert value == pytest.approx(1e3 * own / 8)
    (event, line), = said
    assert event == "deli" and line["dispatches"] == 8 and line["sweeps"] == 4
    assert (line["frames_batched"], line["frames_single"]) == (380, 20)
    assert line["batched_share"] == pytest.approx(0.95)


def test_deli_host_ms_reads_the_lane_of_a_program_without_the_counts():
    """The parent of PR 35: lane totals and no ``stats()``, or a
    ``stats()`` that does not count frames. The counts are said as
    absent, the lane is read."""
    from fluidframework_tpu.telemetry import profiler

    for srv in (_server(), _server({"something_else": 1})):
        profiler.reset()
        before = {**deli_host_ms.snapshot(srv), "pump_dispatches": 0}
        profiler.record("deli", 0.0, 0.006)
        after = {**deli_host_ms.snapshot(srv), "pump_dispatches": 4}
        profiler.reset()
        assert not any(k.startswith("deli_frames") for k in after)
        value, said = _read(before, after)
        assert value == pytest.approx(1.5)
        line = said[0][1]
        assert line["frames_batched"] is None and line["frames_single"] is None
        assert line["batched_share"] is None


def test_deli_host_ms_reads_nothing_without_lane_totals(monkeypatch):
    from fluidframework_tpu.telemetry import profiler

    monkeypatch.delattr(profiler, "totals")
    srv = _server({"deli_frames_batched": 1, "deli_frames_single": 0})
    assert deli_host_ms.snapshot(srv) == {}
    ctx = types.SimpleNamespace(out=None)
    ctx.window = {"pump_dispatches": 3, "t": 2.0}
    assert deli_host_ms.read(ctx) is None
    # The lane is there and nothing was dispatched: nothing to divide by.
    ctx.window = {"pump_dispatches": 0, "lane_own_s.deli": 0.1}
    assert deli_host_ms.read(ctx) is None


def test_deli_host_ms_counts_a_real_service():
    """``PipelineFluidService.stats()`` is what the snapshot reads."""
    from fluidframework_tpu.protocol.opframe import OpFrame
    from fluidframework_tpu.service.pipeline import PipelineFluidService

    svc = PipelineFluidService(n_partitions=2, device_backend=False)
    srv = types.SimpleNamespace(service=svc)
    conns = {d: svc.connect(d) for d in ("a", "b", "c")}
    before = {**deli_host_ms.snapshot(srv), "pump_dispatches": 0}
    svc.submit_frames_bulk([
        (d, c.client_id, OpFrame.build(
            "s", ["ins", "ins"], [0, 1], [1, 2], ["x", "y"], csn0=1,
            ref=svc.doc_head(d)))
        for d, c in conns.items()
    ])
    after = {**deli_host_ms.snapshot(srv), "pump_dispatches": 1}
    value, said = _read(before, after)
    assert value > 0 and said[0][1]["frames_batched"] == 3
    assert said[0][1]["frames_single"] == 0
    assert said[0][1]["batched_share"] == 1.0


@pytest.mark.parametrize("workload", ["rehearsal-ingest", "rehearsal-ws"])
def test_deli_host_ms_is_reported_on_a_rehearsal(workload):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "2147483835", "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines[-1]["correct"] is True
    kind = workload.split("-")[1]
    got = {k: v["value"] for k, v in lines[-1]["metrics"].items()}
    assert 0 < got[f"deli_host_ms.{kind}"] <= got[f"pipeline_host_ms.{kind}"]
    said, = [ln for ln in lines if ln.get("event") == "deli"]
    assert said["platform"] == "cpu" and said["dispatches"] > 0
    # Bulk ingest arrives in runs; a websocket's frame mostly stands
    # alone in its chunk and takes the per-frame body.
    assert said["frames_batched"] >= 0 and said["frames_single"] >= 0
    assert said["frames_batched" if kind == "ingest" else "frames_single"] > 0
    assert said["batched_share"] == pytest.approx(
        said["frames_batched"]
        / (said["frames_batched"] + said["frames_single"])
    )
    lanes_line, = [ln for ln in lines if ln.get("event") == "lanes"]
    assert got[f"deli_host_ms.{kind}"] == pytest.approx(
        lanes_line["per_dispatch"]["deli"]["own_ms"]
    )
