"""The layer readers that read the program's lane totals and counters
(PR 27): each gives a number on the rehearsal cells with ``--trace 1``,
reads nothing (and does not raise) on a program without the counters,
and the trace reduction's split of the device's idle gaps among the host
spans that cover them (PR 28) is pinned on traces that hold the program's
``fluid.*`` spans: hand-made events, two 0.3 s slices recorded on a TPU v5
lite before an event kept its thread (one traced run of each cell, PR 27,
``tools/trace_slice.py``), and one of the ws cell that keeps it (PR 28).

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


def gaps_of(events) -> dict:
    return dict(map(tuple, T.reduce(events)["idle_by_span"]))


def test_gap_outside_any_bench_span_is_charged_to_the_fluid_span():
    """The ws cell's case: nothing of the benchmark encloses the server's
    loop, so a gap goes to the program's spans that cover it, each the
    instants it is innermost for."""
    events = [
        ev("apply_ops_packed.1", 1000, 500), ev("apply_ops_packed.1", 3000, 500),
        host("fluid.socket_out", 1600, 1300),
        host("fluid.deli", 2950, 40),
        host("np.asarray(jax.Array)", 900, 550),
    ]
    # The trace starts at 900: idle 900..1000 lies under np.asarray;
    # 1500..1600, 2900..2950 and 2990..3000 under no span.
    assert gaps_of(events) == pytest.approx({
        "fluid.socket_out": 1300 / 1e9, "fluid.deli": 40 / 1e9,
        "np.asarray(jax.Array)": 100 / 1e9, T.NOTHING: (100 + 50 + 10) / 1e9,
    })


def test_enclosing_bench_span_yields_to_the_stage_spans_inside_it():
    """Under an enclosing ``bench.*`` span of about the gap's length the
    gap is divided among the stage spans inside it; the enclosing span is
    left the instants between them."""
    events = [
        ev("apply_ops_packed.1", 0, 1000), ev("apply_ops_packed.1", 2400, 1000),
        host("bench.submit_frames_bulk", 1000, 1500),
        host("fluid.front_door", 1010, 300), host("fluid.deli", 1320, 500),
        host("fluid.scriptorium", 1830, 200), host("fluid.device_stage", 2040, 350),
    ]
    r = T.reduce(events)
    split = dict(map(tuple, r["idle_by_span"]))
    assert split == pytest.approx({
        "fluid.deli": 500 / 1e9, "fluid.device_stage": 350 / 1e9,
        "fluid.front_door": 300 / 1e9, "fluid.scriptorium": 200 / 1e9,
        "bench.submit_frames_bulk": 50 / 1e9,
    })
    assert sum(split.values()) == pytest.approx(1400 / 1e9)
    assert r["idle_gaps"] == r["idle_by_span"]  # five names: none cut off
    assert r["idle_gaps"][0][0] == "fluid.deli"
    # The tool prints the reduction's own split.
    assert gap_lanes.report(events)["idle_by_span"] == r["idle_by_span"]


def test_gap_split_takes_the_innermost_span_and_names_what_none_covers():
    events = [
        ev("k", 0, 100), ev("k", 1100, 100),
        host("fluid.read_settle", 150, 600), host("fluid.deli", 200, 100),
        host("fluid.scan_consume", 400, 300),
        host("np.asarray(jax.Array)", 420, 250),
    ]
    hosts = [e for e in events if e.plane == HOST]
    assert T.loop_thread(hosts) == (HOST, LOOP, None)
    assert T.innermost(hosts) == [
        (150, 200, "fluid.read_settle"), (200, 300, "fluid.deli"),
        (300, 400, "fluid.read_settle"), (400, 420, "fluid.scan_consume"),
        (420, 670, "np.asarray(jax.Array)"), (670, 700, "fluid.scan_consume"),
        (700, 750, "fluid.read_settle"),
    ]
    assert T.split_gaps(hosts, [(100, 1100)]) == {
        T.NOTHING: 400, "fluid.read_settle": 200, "fluid.deli": 100,
        "fluid.scan_consume": 50, "np.asarray(jax.Array)": 250,
    }
    rep = gap_lanes.report(events)
    assert rep["loop_thread"] == (HOST, LOOP, None)
    assert rep["spans"]["fluid.deli"] == [1, 100 / 1e9]
    assert rep["fluid_spans_inside_bench_submit_frames_bulk"] == [0, 3]


def test_two_threads_over_one_gap_the_loop_is_asked_first():
    """An executor thread's span and the feeder's wait both overlap the
    whole gap; each takes only the instants the loop thread has no span
    for, the one that started last first. The loop thread is the line
    with the most ``fluid.*`` spans, told from the others by ``thread``
    alone: every Python thread's line is named ``python3``."""
    def on(thread, name, start, dur):
        return T.Event(HOST, LOOP, name, start, dur, thread)

    events = [
        ev("k", 0, 100), ev("k", 1100, 100),
        on(7, "fluid.socket_out", 150, 300), on(7, "fluid.deli", 200, 100),
        on(7, "fluid.read_gather", 600, 100),
        on(3, "fluid.read_transfer", 250, 700),    # executor: 250..950
        on(1, "bench.wait_front_door", 0, 1200),   # the feeder's thread
    ]
    hosts = [e for e in events if e.plane == HOST]
    assert T.loop_thread(hosts) == (HOST, LOOP, 7)
    assert gaps_of(events) == pytest.approx({
        "fluid.socket_out": 200 / 1e9, "fluid.deli": 100 / 1e9,
        "fluid.read_gather": 100 / 1e9,
        # 450..600 and 700..950: the loop is in no span
        "fluid.read_transfer": (150 + 250) / 1e9,
        # 100..150 and 950..1100: nobody else is
        "bench.wait_front_door": (50 + 150) / 1e9,
    })
    # With the thread forgotten the three lines are one and whatever
    # started last wins: the executor's span, begun inside ``deli``, is
    # taken for nested in the loop's spans and robs them (250..600).
    merged = gaps_of([e._replace(thread=None) for e in events])
    assert merged["fluid.read_transfer"] == pytest.approx((350 + 250) / 1e9)
    assert merged["fluid.deli"] == pytest.approx(50 / 1e9)
    # No thread in any span: the rest of the gap has its own name.
    alone = gaps_of(events[:5])
    assert alone[T.NOTHING] == pytest.approx((50 + 150 + 400) / 1e9)


def test_an_event_carries_its_thread_through_a_recording(tmp_path):
    path = str(tmp_path / "slice.json.gz")
    events = [T.Event(HOST, LOOP, "fluid.deli", 5, 7, 3), ev("k", 0, 100)]
    T.dump_json(events, path)
    assert T.load_json(path) == events and T.load_json(path)[0].thread == 3
    assert T.load_json(path)[1].thread is None
    # A recording of five fields an event (PR 26, PR 27) loads as it is.
    old = T.load_json(os.path.join(DATA, "trace_ingest_fluid_slice.json.gz"))
    assert {e.thread for e in old} == {None}


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
    """The ws cell: nothing encloses the loop, which waits for traffic
    nearly half of the idle time, in no span. ``fluid.read_transfer``, an
    executor thread's wait that merely overlaps gaps, is left what the
    loop's own spans do not cover, a thousandth of the idle time."""
    events = T.load_json(os.path.join(DATA, "trace_ws_fluid_slice.json.gz"))
    r = T.reduce(events)
    idle = r["window_s"] - r["busy_s"]
    split = dict(map(tuple, r["idle_by_span"]))
    assert sum(split.values()) == pytest.approx(idle, rel=1e-6)
    names = [name for name, _ in r["idle_gaps"]]
    assert names[:3] == [T.NOTHING, "np.asarray(jax.Array)", "fluid.socket_out"]
    assert 0.4 * idle < split[T.NOTHING] < 0.5 * idle
    assert 0.1 * idle < split["fluid.socket_out"] < 0.15 * idle
    assert "fluid.read_transfer" not in names
    assert 0 < split["fluid.read_transfer"] < 0.005 * idle
    assert {"fluid.dispatch", "fluid.host_stage", "fluid.deli",
            "fluid.ring_put", "fluid.scan_consume"} <= set(names)
    assert not any(name.startswith("bench.") for name in split)
    rep = gap_lanes.report(events)
    assert rep["idle_by_span"] == r["idle_by_span"]
    assert rep["fluid_spans_inside_bench_submit_frames_bulk"][0] == 0
    assert rep["idle_s"] == pytest.approx(idle)
    # Every stage and read lane is on the trace, the sweeps many times.
    for lane in (*lanes.PIPELINE, *read_host_ms.LANES, "read_transfer",
                 "host_stage", "ring_put", "dispatch", "scan_consume"):
        assert rep["spans"][f"fluid.{lane}"][0] >= 1, lane


def test_recorded_ingest_trace_a_gap_is_split_among_the_stages():
    """The ingest cell as it is: ``bench.submit_frames_bulk`` encloses a
    whole batch (93 ms, most of it the blocked wait for the step) and the
    stage spans lie inside it (one clock, no offset). Of the 14 ms the
    device idles a batch deli has the largest part, between a quarter and
    a third, and the device
    stage, the readback's tail, the feeder's turn-round, the front door,
    scriptorium and the broadcaster are each named."""
    events = T.load_json(os.path.join(DATA, "trace_ingest_fluid_slice.json.gz"))
    r = T.reduce(events)
    idle = r["window_s"] - r["busy_s"]
    split = dict(map(tuple, r["idle_by_span"]))
    assert r["idle_gaps"][0][0] == "fluid.deli"
    assert 0.2 * idle < split["fluid.deli"] < 0.4 * idle
    assert max(split.values()) < 0.5 * idle
    named = {name for name, _ in r["idle_gaps"]}
    assert {"fluid.deli", "fluid.device_stage", "np.asarray(jax.Array)",
            "bench.wait_front_door", "fluid.front_door", "fluid.scriptorium",
            "fluid.broadcast", T.NOTHING} <= named
    # The enclosing span keeps only the instants between the stages.
    assert split["bench.submit_frames_bulk"] < 0.01 * idle
    assert sum(split.values()) == pytest.approx(idle, rel=1e-6)
    rep = gap_lanes.report(events)
    assert rep["idle_by_span"] == r["idle_by_span"]
    inside, of = rep["fluid_spans_inside_bench_submit_frames_bulk"]
    assert inside == of == 45
    # The step program less its kernel, per step: three steps in the slice.
    assert step_glue_ms.glue_ms(events) == pytest.approx(9.106, abs=0.01)


# -- recorded with the thread kept (0.3 s of one traced run a cell, PR 28) ---


def device_gaps(events) -> list:
    """The idle intervals of the device plane, the slow way."""
    spans = [e for e in events if e.dur_ns > 0]
    busy = T.union((e.start_ns, e.start_ns + e.dur_ns) for e in spans
                   if e.plane == DEV and e.line == OPS)
    edges = [min(e.start_ns for e in spans)]
    edges += [t for lo, hi in busy for t in (lo, hi)]
    edges.append(max(e.start_ns + e.dur_ns for e in spans))
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def test_recorded_ws_trace_an_executors_span_takes_only_what_the_loop_leaves():
    events = T.load_json(os.path.join(DATA, "trace_ws_threads_slice.json.gz"))
    hosts = [e for e in events if e.plane == HOST and e.dur_ns > 0]
    loop = T.loop_thread(hosts)
    executors = {(e.plane, e.line, e.thread) for e in hosts
                 if e.name == "fluid.read_transfer"}
    # Three Python threads, one line name: only ``thread`` tells them apart.
    assert loop[:2] == (HOST, LOOP) and len(executors) == 2
    assert loop not in executors and {k[:2] for k in executors} == {loop[:2]}
    r = T.reduce(events)
    split = dict(map(tuple, r["idle_by_span"]))
    idle = r["window_s"] - r["busy_s"]
    assert sum(split.values()) == pytest.approx(idle, rel=1e-6)
    assert r["idle_gaps"][0][0] == T.NOTHING and split[T.NOTHING] > 0.5 * idle
    # The instants of the gaps the loop thread has no span for, and of
    # those the executors' waits cover: the most they can be charged.
    gaps = sorted(device_gaps(events), key=lambda g: g[0] - g[1])
    gaps = sorted(gaps[:T.ATTRIBUTED_GAPS])
    on_loop = [e for e in hosts if (e.plane, e.line, e.thread) == loop]
    left = T.charge(gaps, T.innermost(on_loop), {})
    most: dict = {}
    T.charge(left, T.innermost(
        e for e in hosts if e.name == "fluid.read_transfer"), most)
    assert 0 < split["fluid.read_transfer"] * 1e9 <= most["fluid.read_transfer"]
    whole = sum(e.dur_ns for e in hosts if e.name == "fluid.read_transfer")
    assert split["fluid.read_transfer"] * 1e9 < 0.02 * whole  # 0.15 of 15 ms


def test_recorded_ingest_trace_the_feeders_thread_is_told_from_the_loops():
    events = T.load_json(os.path.join(DATA, "trace_ingest_threads_slice.json.gz"))
    hosts = [e for e in events if e.plane == HOST and e.dur_ns > 0]
    loop = T.loop_thread(hosts)
    feeder, = {(e.plane, e.line, e.thread) for e in hosts
               if e.name == "bench.wait_front_door"}
    assert feeder != loop and feeder[:2] == loop[:2] == (HOST, LOOP)
    r = T.reduce(events)
    split = dict(map(tuple, r["idle_by_span"]))
    idle = r["window_s"] - r["busy_s"]
    assert r["idle_gaps"][0][0] == "fluid.deli"
    assert 0.2 * idle < split["fluid.deli"] < 0.4 * idle
    assert max(split.values()) < 0.5 * idle
    assert {"fluid.device_stage", "np.asarray(jax.Array)", "fluid.front_door",
            "fluid.scriptorium", "fluid.broadcast", "bench.wait_front_door",
            "bench.submit_frames_bulk"} <= {n for n, _ in r["idle_gaps"]}
    # The feeder waits for the front door all through a batch; it is
    # charged the turn-round between batches alone. With the thread
    # forgotten its span, begun 0.7 ms into the loop's, robs the stages.
    assert split["bench.wait_front_door"] < 0.05 * idle
    merged = T.reduce([e._replace(thread=None) for e in events])
    assert dict(map(tuple, merged["idle_by_span"]))[
        "bench.wait_front_door"] > 3 * split["bench.wait_front_door"]
    assert step_glue_ms.glue_ms(events) == pytest.approx(9.106, abs=0.02)
