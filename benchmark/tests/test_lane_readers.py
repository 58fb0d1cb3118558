"""The layer readers that read the program's lane totals and counters
(PR 27): each gives a number on the rehearsal cells with ``--trace 1``,
reads nothing (and does not raise) on a program without the counters,
and the trace reduction is pinned on traces that hold the program's
``fluid.*`` spans: hand-made events, and two 0.3 s slices recorded on a
TPU v5 lite (one traced run of each cell, PR 27, ``tools/trace_slice.py``).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lane_readers.py -q

Nothing here is a time: every line says it ran on the CPU.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import trace_reduce as T
from benchmark.layers import (
    lanes,
    loop_blocked_share,
    loop_wait_ms,
    pipeline_host_ms,
    read_host_ms,
    read_queue_ms,
    read_transfer_ms,
    real_rows_per_dispatch,
    setup_aot_build_s,
    setup_pipeline_s,
    step_glue_ms,
)
from benchmark.tools import gap_lanes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
DEV, OPS, HOST, LOOP = "/device:TPU:0", "XLA Ops", "/host:CPU", "python3"

NEW = {
    "rehearsal-ws": {
        "pipeline_host_ms.ws", "loop_blocked_share.ws", "loop_wait_ms.ws",
        "real_rows_per_dispatch.ws", "read_queue_ms.ws", "read_host_ms.ws",
        "read_transfer_ms.ws", "setup_aot_build_s", "setup_pipeline_s",
    },
    "rehearsal-ingest": {
        "pipeline_host_ms.ingest", "real_rows_per_dispatch.ingest",
        "setup_aot_build_s", "setup_pipeline_s",
    },
}
WINDOW_READERS = (
    pipeline_host_ms, loop_blocked_share, loop_wait_ms, read_queue_ms,
    read_host_ms, read_transfer_ms, real_rows_per_dispatch,
)


def ev(name, start, dur, plane=DEV, line=OPS):
    return T.Event(plane, line, name, start, dur)


def host(name, start, dur, line=LOOP):
    return T.Event(HOST, line, name, start, dur)


# -- on the rehearsal cells -------------------------------------------------


@pytest.mark.parametrize("workload", sorted(NEW))
def test_new_metrics_and_lanes_line_on_a_rehearsal(workload):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "2147483801", "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    last = lines[-1]
    assert last["correct"] is True
    got = {k: v["value"] for k, v in last["metrics"].items()}
    assert NEW[workload] <= set(got), sorted(NEW[workload] - set(got))
    for name in NEW[workload]:
        assert isinstance(got[name], (int, float)) and got[name] >= 0, name
    # A CPU trace has no device plane, so no step program to read.
    assert "step_glue_ms.ingest" not in got
    kind = workload.split("-")[1]
    assert got[f"real_rows_per_dispatch.{kind}"] <= got[f"rows_per_dispatch.{kind}"]
    assert got["setup_aot_build_s"] + got["setup_pipeline_s"] <= next(
        ln["setup_s"] for ln in lines if ln.get("event") == "window"
    )
    said, = [ln for ln in lines if ln.get("event") == "lanes"]
    assert said["platform"] == "cpu" and said["dispatches"] > 0
    per = said["per_dispatch"]
    assert {"deli", "scriptorium", "broadcast", "device_stage",
            "host_stage", "ring_put", "dispatch", "scan_consume"} <= set(per)
    assert all(v["n"] > 0 and 0 <= v["own_ms"] <= v["ms"] + 1e-9
               for v in per.values())
    pipeline = sum(per[k]["own_ms"] for k in lanes.PIPELINE if k in per)
    assert got[f"pipeline_host_ms.{kind}"] == pytest.approx(pipeline)


# -- on a program without the counters (the parent of PR 27) ----------------


def _bare_server():
    dev = types.SimpleNamespace(
        flush_totals={"staging_s": 0.0, "staged_rows": 0}, pump_dispatches=3,
    )
    return types.SimpleNamespace(
        service=types.SimpleNamespace(device=dev), lag_ticks=5,
    )


def test_readers_read_nothing_where_the_program_has_no_counter(monkeypatch):
    from fluidframework_tpu.parallel import aot
    from fluidframework_tpu.telemetry import profiler

    monkeypatch.delattr(profiler, "totals")
    monkeypatch.setattr(aot, "stats", lambda: {"builds": 2, "calls": 9})
    monkeypatch.setattr(setup_aot_build_s, "_first", [])
    monkeypatch.setattr(setup_pipeline_s, "_first", [])
    srv = _bare_server()
    ctx = types.SimpleNamespace(out=None)
    # What the harness itself counts is there; nothing of the readers is.
    ctx.window = {"pump_dispatches": 3, "t": 2.0}
    for reader in (*WINDOW_READERS, setup_aot_build_s, setup_pipeline_s):
        assert reader.snapshot(srv) == {}, reader.__name__
        assert reader.read(ctx) is None, reader.__name__


def test_readers_give_numbers_from_the_lane_totals(monkeypatch):
    from fluidframework_tpu.telemetry import profiler

    srv = _bare_server()
    srv.lag_sum_ms = 10.0
    srv.service.device.flush_totals["real_rows"] = 0
    profiler.reset()
    before = {**lanes.snapshot(srv), **loop_wait_ms.snapshot(srv),
              **real_rows_per_dispatch.snapshot(srv), "t": 0.0}
    profiler.record("deli", 0.0, 0.004)
    profiler.record("socket_out", 0.0, 0.002)
    profiler.record("scan_consume", 0.0, 0.5)
    for _ in range(2):
        profiler.record("read_wait", 1.0, 1.006)
    profiler.record("read_settle", 0.0, 0.010)
    profiler.record("read_gather", 0.0, 0.001)
    profiler.record("read_transfer", 0.0, 0.007)
    profiler.record("read_finish", 0.0, 0.001)
    srv.lag_sum_ms, srv.lag_ticks = 40.0, 15
    srv.service.device.flush_totals["real_rows"] = 24
    srv.service.device.pump_dispatches = 11
    after = {**lanes.snapshot(srv), **loop_wait_ms.snapshot(srv),
             **real_rows_per_dispatch.snapshot(srv), "t": 2.0}
    profiler.reset()
    said = []
    ctx = types.SimpleNamespace(out=types.SimpleNamespace(
        say=lambda event, **kv: said.append((event, kv))
    ))
    ctx.window = {k: after[k] - before[k] for k in after}
    assert pipeline_host_ms.read(ctx) == pytest.approx(6.0 / 8)
    assert said[0][0] == "lanes" and said[0][1]["dispatches"] == 8
    assert said[0][1]["per_dispatch"]["deli"]["n"] == 1
    assert "front_door" not in said[0][1]["per_dispatch"]  # count 0: left out
    assert loop_blocked_share.read(ctx) == pytest.approx(25.0)
    assert loop_wait_ms.read(ctx) == pytest.approx(3.0)
    assert real_rows_per_dispatch.read(ctx) == pytest.approx(3.0)
    assert read_queue_ms.read(ctx) == pytest.approx(6.0)
    assert read_host_ms.read(ctx) == pytest.approx(12.0)
    assert read_transfer_ms.read(ctx) == pytest.approx(7.0)


def test_setup_readers_keep_the_first_snapshot(monkeypatch):
    from fluidframework_tpu.parallel import aot
    from fluidframework_tpu.telemetry import profiler

    monkeypatch.setattr(setup_aot_build_s, "_first", [])
    monkeypatch.setattr(setup_pipeline_s, "_first", [])
    profiler.reset()
    profiler.record("front_door", 0.0, 1.5)
    profiler.record("deli", 0.0, 2.0)
    profiler.record("host_stage", 0.0, 9.0)  # not a pipeline lane
    built = [31.5]
    monkeypatch.setattr(aot, "stats", lambda: {"build_s": built[0]})
    srv = _bare_server()
    assert setup_aot_build_s.snapshot(srv) == {}
    assert setup_pipeline_s.snapshot(srv) == {}
    built[0] = 99.0  # a build inside the window
    profiler.record("deli", 0.0, 5.0)
    setup_aot_build_s.snapshot(srv), setup_pipeline_s.snapshot(srv)
    profiler.reset()
    assert setup_aot_build_s.read(None) == 31.5
    assert setup_pipeline_s.read(None) == pytest.approx(3.5)


# -- the trace reduction on traces that hold fluid.* spans -------------------


def test_gap_outside_any_bench_span_is_charged_to_the_fluid_span():
    """The ws cell's case: nothing of the benchmark encloses the server's
    loop, so a gap goes to the program's span that covers it best."""
    events = [
        ev("apply_ops_packed.1", 1000, 500), ev("apply_ops_packed.1", 3000, 500),
        host("fluid.socket_out", 1600, 1300),
        host("fluid.deli", 2950, 40),
        host("np.asarray(jax.Array)", 900, 550),
    ]
    gaps = dict(map(tuple, T.reduce(events)["idle_gaps"]))
    assert gaps["fluid.socket_out"] == 1500 / 1e9  # the whole 1500..3000


def test_enclosing_bench_span_still_beats_the_stage_spans_inside_it():
    """What ``_host_activity`` does TODAY under an enclosing ``bench.*``
    span: it charges a whole gap to ONE span by cover squared over
    duration, so an enclosing span of about the gap's length wins over
    every stage span inside it, whatever its docstring says. The
    `benchmark` issue that splits a gap among the innermost spans changes
    this test; ``tools/gap_lanes.py`` already does the split."""
    events = [
        ev("apply_ops_packed.1", 0, 1000), ev("apply_ops_packed.1", 2400, 1000),
        host("bench.submit_frames_bulk", 1000, 1500),
        host("fluid.front_door", 1010, 300), host("fluid.deli", 1320, 500),
        host("fluid.scriptorium", 1830, 200), host("fluid.device_stage", 2040, 350),
    ]
    gaps = dict(map(tuple, T.reduce(events)["idle_gaps"]))
    assert gaps == {"bench.submit_frames_bulk": 1400 / 1e9}
    split = gap_lanes.split(events, gap_lanes.idle_gaps(events))
    assert split["fluid.deli"] == pytest.approx(500 / 1e9)
    assert split["fluid.front_door"] == pytest.approx(300 / 1e9)
    assert split["bench.submit_frames_bulk"] == pytest.approx(50 / 1e9)
    assert sum(split.values()) == pytest.approx(1400 / 1e9)


def test_gap_split_takes_the_innermost_span_and_names_what_none_covers():
    events = [
        ev("k", 0, 100), ev("k", 1100, 100),
        host("fluid.read_settle", 150, 600), host("fluid.deli", 200, 100),
        host("fluid.scan_consume", 400, 300),
        host("np.asarray(jax.Array)", 420, 250),
        host("fluid.read_transfer", 0, 5000, line="executor"),  # another thread
    ]
    assert gap_lanes.loop_line(events) == (HOST, LOOP)
    gaps = gap_lanes.idle_gaps(events)
    assert gaps == [(100, 1100), (1200, 5000)]
    split = gap_lanes.split(events, gaps[:1])
    assert split == pytest.approx({
        "(no span)": 400 / 1e9, "fluid.read_settle": 200 / 1e9,
        "fluid.deli": 100 / 1e9, "fluid.scan_consume": 50 / 1e9,
        "np.asarray(jax.Array)": 250 / 1e9,
    })
    rep = gap_lanes.report(events)
    assert rep["spans"]["fluid.deli"] == [1, 100 / 1e9]
    assert rep["fluid_spans_inside_bench_submit_frames_bulk"] == [0, 4]


def test_step_glue_is_the_step_program_less_its_kernel():
    mods = "XLA Modules"
    events = [
        ev("jit_fluid_step(77)", 0, 1000, line=mods),
        ev("broadcast_in_dim.24", 0, 60), ev("apply_ops_packed.1", 100, 700),
        ev("slice_bitcast_fusion", 800, 150),
        ev("jit_fluid_scan(5)", 1000, 5, line=mods),
        ev("jit_fluid_step(77)", 2000, 900, line=mods),
        ev("apply_ops_packed.1", 2050, 700),
        ev("jit_fluid_compact(9)", 3000, 400, line=mods),
        ev("compact_packed.1", 3000, 400),
    ]
    assert step_glue_ms.glue_ms(events) == pytest.approx((300 + 200) / 2 / 1e6)
    # The parent's program names its step after the Python function.
    old = [e._replace(name=e.name.replace("fluid_step", "fused")) for e in events]
    assert step_glue_ms.glue_ms(old) is None
    assert step_glue_ms.glue_ms([host("fluid.deli", 0, 10)]) is None
    recorded = T.load_json(os.path.join(DATA, "trace_ingest_slice.json.gz"))
    assert step_glue_ms.glue_ms(recorded) is None  # PR 26's trace: jit_fused


# -- recorded on the chip (0.3 s of one traced run of each cell, PR 27) -------


def test_recorded_ws_trace_charges_gaps_to_the_programs_spans():
    events = T.load_json(os.path.join(DATA, "trace_ws_fluid_slice.json.gz"))
    r = T.reduce(events)
    names = [name for name, _ in r["idle_gaps"]]
    assert names[0] == "fluid.socket_out"
    assert {"fluid.read_transfer", "fluid.read_gather", "fluid.ring_put",
            "fluid.scan_consume"} <= set(names)
    assert not any(name.startswith("bench.") for name in names)
    rep = gap_lanes.report(events)
    assert rep["fluid_spans_inside_bench_submit_frames_bulk"][0] == 0
    assert rep["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    # Every stage and read lane is on the trace, the sweeps many times.
    for lane in (*lanes.PIPELINE, *read_host_ms.LANES, "read_transfer",
                 "host_stage", "ring_put", "dispatch", "scan_consume"):
        assert rep["spans"][f"fluid.{lane}"][0] >= 1, lane


def test_recorded_ingest_trace_one_span_takes_a_whole_gap():
    """The ingest cell as it is: ``bench.submit_frames_bulk`` encloses a
    whole batch (93 ms, most of it the blocked wait for the step), far
    longer than the 14 ms gap, so the reducer's score gives the gap to
    ``fluid.deli`` — all of it, though deli covers a third. The split is
    ``gap_lanes``'s; the stage spans lie inside the benchmark's span of
    the same trace (one clock, no offset)."""
    events = T.load_json(os.path.join(DATA, "trace_ingest_fluid_slice.json.gz"))
    r = T.reduce(events)
    gaps = dict(map(tuple, r["idle_gaps"]))
    idle = r["window_s"] - r["busy_s"]
    assert r["idle_gaps"][0][0] == "fluid.deli" and gaps["fluid.deli"] > 0.8 * idle
    assert "bench.submit_frames_bulk" not in gaps
    rep = gap_lanes.report(events)
    split = dict(rep["idle_by_innermost_span"])
    assert 0.2 * idle < split["fluid.deli"] < 0.4 * idle
    for lane in ("front_door", "scriptorium", "broadcast", "device_stage"):
        assert split[f"fluid.{lane}"] > 0, lane
    assert sum(split.values()) == pytest.approx(idle, rel=1e-6)
    inside, of = rep["fluid_spans_inside_bench_submit_frames_bulk"]
    assert inside == of == 45
    # The step program less its kernel, per step: three steps in the slice.
    assert step_glue_ms.glue_ms(events) == pytest.approx(9.106, abs=0.01)
