"""The ``ws_table`` kind and the readers of a table deployment (PR 38):
the CPU rehearsal of the kind end to end (four tables of four websocket
writers each on 256 resident table documents, found by name as the other
rehearsals are), the shape of its result line, every ``.table`` metric read
in a traced run, ``correct`` shown to fail under all three controls and on
a cut or a stale grid, a run that stays correct when the server's loop
stalls across the window's end, the early one-line refusal of a program
that serves no matrix channel, and the three new readers on hand-made
window deltas and on a program without the counts.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_table.py -q

Nothing here is a time: every line says it ran on the CPU. The traced
rehearsal is started from this file only (two traced runs of one workload
share ``benchmark_out/<workload>/trace``, PERF.md section 7).
"""

import json
import os
import types

import pytest

from benchmark.layers import (
    axis_op_share,
    grid_read_ms,
    matrix_counts,
    matrix_stage_ms,
)
from benchmark.tests.test_meeting import STALL_AT_END
from benchmark.tests.test_rehearsal import DRIVER, ROOT, _argv, _compared, _run

WORKLOAD, CELL = "rehearsal-table", "mx10k-ws-table"
TABLES, WRITERS = 4, 4

# The program as the parent of PR 38 had it: the device lambda drops every
# op that is not a string-kernel op.
NO_MATRIX = """
from fluidframework_tpu.service import device_lambda
device_lambda.MATRIX_KINDS = ()
"""

# The REST read entry alone, underneath the timed reads: ``cut`` drops a
# grid's last row; ``cached`` replies what it replied first, which is right
# for a table nobody edits and stale behind live writes.
BREAK_REST = """
import json
from fluidframework_tpu.service.network_server import FluidNetworkServer
_orig, _first = FluidNetworkServer._channel_read, {{}}
async def _broken(self, doc_id, channel_id, view):
    if view is not None or doc_id == "table-probe":
        return await _orig(self, doc_id, channel_id, view)
    if {cached} and doc_id in _first:
        return _first[doc_id]
    status, payload = await _orig(self, doc_id, channel_id, view)
    if not {cached}:
        reply = json.loads(payload)
        reply["grid"] = reply["grid"][:-1]
        payload = json.dumps(reply).encode()
    return _first.setdefault(doc_id, (status, payload))
FluidNetworkServer._channel_read = _broken
"""


def _bench(group):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return {
        m["name"] for m in bench[group] if CELL in m.get("workloads", [CELL])
    }


@pytest.mark.parametrize("trace", [0, 1])
def test_table_runs_end_to_end(trace):
    proc, lines = _run(_argv(WORKLOAD, 2147483801 + trace, trace))
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    events = {}
    for ln in lines[:-1]:
        rec = json.loads(ln)
        assert rec["platform"] == "cpu" and "device_kind" in rec
        events[rec.get("event")] = rec
    assert last["correct"] is True and last["failed"] == 0, "\n".join(
        ln[:400] for ln in lines if '"compared"' in ln or "mismatch" in ln
    )
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    assert events["probe"]["grid"] == [[]]
    compared = _compared(lines)
    assert compared["documents_compared"]["value"] == 2 * TABLES
    assert compared["clients_compared"]["value"] == TABLES * WRITERS
    assert compared["read_replies_of_written_documents"]["value"] > 0
    for what in (
        "read_replies_differ_from_replay", "served_grid_differs_from_replay",
        "durable_log_faults", "acked_ops_missing_from_log",
        "client_grid_differs_from_served", "client_ops_still_pending",
        "cells_served_under_a_removed_handle", "docs_with_errors",
    ):
        assert compared[what]["limit"] == 0 and compared[what]["value"] == 0
    window = events["window"]
    assert window["migrations_in_window"] == 0
    assert window["aot_keys_built_in_window"] == []
    assert sum(window["actions"].values()) > 0
    assert window["ops_sent"] >= sum(window["actions"].values())
    totals = events["matrix_totals"]
    # The fleet at load: two axis inserts a table and its cells.
    assert totals["matrix_axis_ops"] >= 2 * 256
    assert totals["matrix_cell_ops"] >= 252 * 16 + TABLES * 256
    assert totals["matrix_reads"] > 0
    if not trace:
        assert set(last["metrics"]) == _bench("end_to_end")
        return
    # Every per-layer metric of the cell but the device's own (a CPU trace
    # has no device plane), each a number.
    got = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(got) == _bench("per_layer") - {"device_step_ms.table"}
    assert all(isinstance(v, (int, float)) and v >= 0 for v in got.values())
    assert 0 < got["axis_op_share.table"] < 100
    assert got["matrix_stage_ms.table"] > 0 and got["grid_read_ms.table"] > 0
    assert got["socket_out_ms.table"] <= got["pipeline_host_ms.table"]
    said = events["matrix"]
    assert said["axis_ops"] + said["cell_ops"] > 0 and said["grids_joined"] > 0
    assert 100.0 * said["axis_ops"] / (
        said["axis_ops"] + said["cell_ops"]
    ) == pytest.approx(got["axis_op_share.table"])


def test_table_controls_are_told_apart():
    proc, lines = _run(_argv(WORKLOAD, 3000000038, 0, "--control", "1"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(lines[-1])["correct"] is True
    control = [json.loads(ln) for ln in lines if '"control"' in ln]
    assert [c["what"] for c in control] == [
        "replay with the last op withheld",
        "a reply one acknowledged cell write stale",
        "a grid whose cells are ahead of its axes",
    ]
    assert control[0]["told_apart"] >= control[0]["documents"] - 1
    assert control[1]["told_apart"] >= control[1]["needed"]
    assert control[2]["documents"] >= 1
    assert control[2]["told_apart"] >= control[2]["needed"]


@pytest.mark.parametrize("cached", [False, True])
def test_a_cut_or_stale_grid_is_not_correct(cached):
    code = DRIVER.format(
        root=ROOT, patch=BREAK_REST.format(cached=cached),
        argv=_argv(WORKLOAD, 38 + cached),
    )
    proc, lines = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(lines[-1])["correct"] is False
    compared = _compared(lines)
    assert compared["read_replies_differ_from_replay"]["value"] > 0
    assert compared["served_grid_differs_from_replay"]["value"] >= TABLES
    # The log, the clients' acknowledgements and the device are sound.
    assert compared["durable_log_faults"]["value"] == 0
    assert compared["acked_ops_missing_from_log"]["value"] == 0
    assert compared["docs_with_errors"]["value"] == 0


def test_a_stall_across_the_windows_end_leaves_the_table_run_correct():
    """No child reports its grids before the window's last ops are
    sequenced and taken in, however long the server's loop held them: the
    children say ``sent``, the parent names the heads (PR 36's lesson)."""
    code = DRIVER.format(root=ROOT, patch=STALL_AT_END, argv=_argv(WORKLOAD, 40))
    proc, lines = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0, "\n".join(
        ln[:400] for ln in lines if '"compared"' in ln or "mismatch" in ln
    )
    assert any('"children_sent"' in ln for ln in lines)
    assert _compared(lines)["client_grid_differs_from_served"]["value"] == 0


def test_a_program_without_matrix_channels_is_refused_at_once():
    """What the parent of PR 38 meets in the new cell: one line, before
    the fleet is loaded, exit code 1, and no result."""
    code = DRIVER.format(root=ROOT, patch=NO_MATRIX, argv=_argv(WORKLOAD, 41))
    proc, lines = _run(code, timeout=180)
    assert proc.returncode == 1
    assert not any('"correct"' in ln for ln in lines)
    assert not any('"load"' in ln for ln in lines)
    said = [ln for ln in proc.stderr.splitlines() if ln.startswith("benchmark:")]
    assert len(said) == 1 and "serves no matrix channel" in said[0]
    assert "unknown channel" in said[0] and "404" in said[0]


# -- the new readers on window deltas ---------------------------------------

# The window of one CPU rehearsal of the kind (4 tables x 4 writers, 3 s;
# PR 38), as ``harness.delta`` gave it.
WINDOW = {
    "pump_dispatches": 8, "t": 3.25,
    "lane_n.matrix_stage": 40, "lane_s.matrix_stage": 0.00081,
    "lane_own_s.matrix_stage": 0.00065,
    "lane_n.matrix_read": 65, "lane_s.matrix_read": 0.0078,
    "lane_own_s.matrix_read": 0.0078,
    "matrix.matrix_axis_ops": 8, "matrix.matrix_cell_ops": 32,
    "matrix.matrix_cells_live": 1, "matrix.matrix_cells_dropped": 8,
    "matrix.matrix_reads": 65,
}


def _ctx(window):
    said = []
    ctx = types.SimpleNamespace(
        window=window, result={},
        out=types.SimpleNamespace(say=lambda event, **kv: said.append((event, kv))),
    )
    return ctx, said


@pytest.mark.parametrize("reader,want", [
    (matrix_stage_ms, 1e3 * 0.00065 / 8),
    (grid_read_ms, 1e3 * 0.0078 / 65),
    (axis_op_share, 20.0),
])
def test_table_readers_give_numbers_from_the_counts(reader, want):
    ctx, said = _ctx(dict(WINDOW))
    assert reader.read(ctx) == pytest.approx(want)
    if reader is axis_op_share:
        assert said == [("matrix", {
            "axis_ops": 8, "cell_ops": 32, "cells_live_delta": 1,
            "cells_dropped": 8, "grids_joined": 65})]
    else:
        assert said == []


@pytest.mark.parametrize("reader", [matrix_stage_ms, grid_read_ms, axis_op_share])
def test_table_readers_read_nothing_without_the_counts(reader, monkeypatch):
    """The parent of PR 38: lane totals without the two lanes, ``stats()``
    without the matrix counts; a program with neither; and a window in
    which no table was written or read."""
    from fluidframework_tpu.telemetry import profiler

    totals = profiler.totals()
    monkeypatch.setattr(profiler, "totals", lambda: {
        k: v for k, v in totals.items() if not k.startswith("matrix_")})
    service = types.SimpleNamespace(stats=lambda: {
        "deli_frames_batched": 3, "deli_frames_single": 1})
    snap = reader.snapshot(types.SimpleNamespace(service=service))
    assert snap and not any("matrix" in k for k in snap)
    ctx, said = _ctx({**{k: 0.0 for k in snap}, "pump_dispatches": 3, "t": 2.0})
    assert reader.read(ctx) is None and said == []
    monkeypatch.delattr(profiler, "totals")
    bare = types.SimpleNamespace(service=types.SimpleNamespace())
    assert reader.snapshot(bare) == {}
    ctx, said = _ctx({"pump_dispatches": 3, "t": 2.0})
    assert reader.read(ctx) is None and said == []
    quiet = {k: 0 for k in WINDOW}
    quiet["pump_dispatches"] = 5
    ctx, said = _ctx(quiet)
    assert reader.read(ctx) is None and said == []


def test_matrix_counts_snapshot_names_what_the_program_counts():
    stats = {k: i + 1 for i, k in enumerate(matrix_counts.STATS)}
    srv = types.SimpleNamespace(
        service=types.SimpleNamespace(stats=lambda: stats))
    snap = matrix_counts.snapshot(srv)
    assert snap["matrix.matrix_reads"] == stats["matrix_reads"]
    assert {k for k in snap if k.startswith("matrix.")} == {
        f"matrix.{k}" for k in matrix_counts.STATS}
    assert "lane_s.matrix_read" in snap and "lane_own_s.matrix_stage" in snap
