"""The ``ws_meeting`` kind and the readers of a many-writer document
(PR 36): the CPU rehearsal of the kind end to end (two documents of 120
websocket writers each on 256 resident documents, found by name as the
other rehearsals are), the shape of its result line, every ``.meeting``
metric read in a traced run, ``correct`` shown to fail under both controls
and on a document held under its writers in write slots, a run that stays
correct when the server's loop stalls across the window's end, the early one-line
refusal of a program whose writer cap is under the meeting's, and the new
readers on hand-made window deltas and on a program without the counts.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_meeting.py -q

Nothing here is a time: every line says it ran on the CPU.
"""

import json
import os
import types

import pytest

from benchmark.layers import (
    deliveries_per_message,
    meeting_counts,
    msn_lag_ops,
    noop_share,
    socket_out_ms,
    writer_slots_peak,
)
from benchmark.tests.test_rehearsal import DRIVER, ROOT, _argv, _compared, _run

WORKLOAD, CELL = "rehearsal-meeting", "tsl120-ws-meeting"
WRITERS = 120

# The program as the parent of PR 36 had it: a removers set of three lanes.
NARROW = """
from fluidframework_tpu.protocol import constants
constants.MAX_WRITERS = 93
"""

# One document's sequencer never counts its last writer: the document is
# held one write slot under its writers.
HOLD_UNDER = """
from fluidframework_tpu.service import sequencer
_join = sequencer.DocumentSequencer.join
def _held(self, *a, **kw):
    out = _join(self, *a, **kw)
    self.writer_slots_peak = min(self.writer_slots_peak, {writers} - 1)
    return out
sequencer.DocumentSequencer.join = _held
"""


# The server's loop stands still for four seconds across the window's end,
# as a shared machine's does now and then: the window's last frames are
# sequenced long after every child's own schedule has ended.
STALL_AT_END = """
import threading, time
from benchmark import harness
from benchmark.traffic import ws_edit
_tell = ws_edit._tell
def _tell_and_stall(st, **cmd):
    if cmd.get("cmd") == "window":
        def stall():
            end = cmd["at"] + cmd["seconds"]
            time.sleep(max(0.0, end - 0.3 - time.monotonic()))
            harness.on_loop(st.srv, lambda: time.sleep(4.0))
        threading.Thread(target=stall, daemon=True).start()
    return _tell(st, **cmd)
ws_edit._tell = _tell_and_stall
"""


def _bench(group):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return {
        m["name"] for m in bench[group] if CELL in m.get("workloads", [CELL])
    }


@pytest.mark.parametrize("trace", [0, 1])
def test_meeting_runs_end_to_end(trace):
    proc, lines = _run(_argv(WORKLOAD, 2147483777 + trace, trace))
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
    compared = _compared(lines)
    assert compared["clients_compared"]["value"] == 2 * WRITERS
    for what in (
        "joins_nacked_for_want_of_a_slot", "ops_refused_with_err_client",
        "meeting_documents_under_their_writers_in_slots", "rejoins_missing",
        "client_text_differs_from_served",
    ):
        assert compared[what]["limit"] == 0 and compared[what]["value"] == 0
    peaks = events["meeting"]["writer_slots_peak"]
    assert len(peaks) == 2 and all(WRITERS <= v <= 124 for v in peaks.values())
    assert events["meeting"]["rejoins"] == 2 * 12  # a tenth, the rehearsal's
    window = events["window"]
    assert window["migrations_in_window"] == 0
    assert window["aot_keys_built_in_window"] == []
    assert window["heartbeat_noops"] > 0 and window["signals_sent"] > 0
    if not trace:
        assert set(last["metrics"]) == _bench("end_to_end")
        return
    # Every per-layer metric of the cell but the device's own (a CPU trace
    # has no device plane), each a number.
    got = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(got) == _bench("per_layer") - {"device_step_ms.meeting"}
    assert all(isinstance(v, (int, float)) and v >= 0 for v in got.values())
    assert got["deliveries_per_message.meeting"] == pytest.approx(WRITERS)
    assert WRITERS <= got["writer_slots_peak.meeting"] <= 124
    assert got["socket_out_ms.meeting"] <= got["pipeline_host_ms.meeting"]
    assert 0 <= got["noop_share.meeting"] < 50 and got["msn_lag_ops.meeting"] > 0
    noops, sent = events["noops"], events["deliveries"]
    # The heartbeat's noops reach deli and take no sequence number.
    assert noops["received"] > noops["sequenced"]
    assert sent["ops"] + sent["frames"] == WRITERS * sent["sequenced"]
    assert sent["signals_written"] == WRITERS * sent["signals_received"]


def test_meeting_controls_are_told_apart():
    proc, lines = _run(_argv(WORKLOAD, 3000000019, 0, "--control", "1"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(lines[-1])["correct"] is True
    control = [json.loads(ln) for ln in lines if '"control"' in ln]
    assert len(control) == 2
    assert control[0]["told_apart"] >= control[0]["documents"] - 1
    assert control[1]["told_apart"] >= control[1]["needed"]


def test_a_stall_across_the_windows_end_leaves_the_run_correct():
    """A document's writers live in every child: no child reports its
    texts before the ops that other children sent last are sequenced and
    taken in, however long the server's loop held them (PR 36's first
    check was refused for a run that read ``client_text_differs_from_
    served`` with no op failed)."""
    code = DRIVER.format(root=ROOT, patch=STALL_AT_END, argv=_argv(WORKLOAD, 17))
    proc, lines = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0, "\n".join(
        ln[:400] for ln in lines if '"compared"' in ln or "mismatch" in ln
    )
    compared = _compared(lines)
    assert compared["clients_compared"]["value"] == 2 * WRITERS
    assert compared["client_text_differs_from_served"]["value"] == 0


def test_a_document_held_under_its_writers_in_slots_is_not_correct():
    code = DRIVER.format(
        root=ROOT, patch=HOLD_UNDER.format(writers=WRITERS),
        argv=_argv(WORKLOAD, 15),
    )
    proc, lines = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(lines[-1])["correct"] is False
    compared = _compared(lines)
    assert compared["meeting_documents_under_their_writers_in_slots"]["value"] == 2
    assert compared["client_text_differs_from_served"]["value"] == 0


def test_a_program_with_a_narrower_cap_is_refused_at_once():
    """What the parent of PR 36 meets in the new cell: one line, before
    the fleet is loaded, and no result."""
    code = DRIVER.format(root=ROOT, patch=NARROW, argv=_argv(WORKLOAD, 16))
    proc, lines = _run(code, timeout=120)
    assert proc.returncode != 0
    assert not any('"correct"' in ln for ln in lines)
    assert not any('"load"' in ln for ln in lines)
    said = [ln for ln in proc.stderr.splitlines() if ln.strip()]
    assert len(said) == 1 and "admits 93 concurrent writers" in said[0]
    assert "needs 120" in said[0]


# -- the new readers on window deltas ---------------------------------------

# The window of one CPU rehearsal of the kind (2 documents x 120 writers,
# 4 s; PR 36), as ``harness.delta`` gave it.
WINDOW = {
    "pump_dispatches": 46, "t": 4.25, "lane_own_s.socket_out": 0.675,
    "meeting.noops_received": 443, "meeting.noops_sequenced": 6,
    "meeting.msn_lag_sum": 827, "meeting.msn_lag_count": 55,
    "meeting.join_nacks_slots": 0, "meeting.signals_received": 49,
    "meeting.signals_delivered": 5880, "meeting.socket.frames_delivered": 0,
    "meeting.socket.ops_delivered": 6600,
    "meeting.socket.signals_delivered": 5880,
}


def _ctx(window, result=None):
    said = []
    ctx = types.SimpleNamespace(
        window=window, result=result or {},
        out=types.SimpleNamespace(say=lambda event, **kv: said.append((event, kv))),
    )
    return ctx, said


@pytest.mark.parametrize("reader,want", [
    (socket_out_ms, 1e3 * 0.675 / 46),
    (deliveries_per_message, 120.0),
    (noop_share, 100.0 * 6 / 55),
    (msn_lag_ops, 827 / 55),
])
def test_meeting_readers_give_numbers_from_the_counts(reader, want):
    ctx, said = _ctx(dict(WINDOW))
    assert reader.read(ctx) == pytest.approx(want)
    if reader is noop_share:
        assert said == [("noops", {
            "sequenced_messages": 55, "received": 443, "sequenced": 6})]
    if reader is deliveries_per_message:
        assert said[0][0] == "deliveries" and said[0][1]["ops"] == 6600
        assert said[0][1]["signals_written"] == 5880


def test_frames_count_as_deliveries_beside_json_ops():
    w = dict(WINDOW)
    w["meeting.socket.frames_delivered"] = 240
    w["meeting.msn_lag_count"] = 57
    ctx, _ = _ctx(w)
    assert deliveries_per_message.read(ctx) == pytest.approx(120.0)


def test_writer_slots_peak_is_what_the_kind_read_after_the_window():
    ctx, _ = _ctx({}, {"layer": {"writer_slots_peak": 122}})
    assert writer_slots_peak.read(ctx) == 122
    ctx, _ = _ctx({}, {"layer": {}})
    assert writer_slots_peak.read(ctx) is None


@pytest.mark.parametrize("reader", [
    socket_out_ms, deliveries_per_message, noop_share, msn_lag_ops,
])
def test_meeting_readers_read_nothing_without_the_counts(reader, monkeypatch):
    """The parent of PR 36: ``stats()`` without the ticket loop's counts, a
    server without ``ops_delivered``; and a window that sequenced nothing."""
    from fluidframework_tpu.telemetry import profiler

    monkeypatch.delattr(profiler, "totals")
    service = types.SimpleNamespace(stats=lambda: {
        "deli_frames_batched": 3, "deli_frames_single": 1})
    srv = types.SimpleNamespace(service=service, frames_delivered=7)
    assert reader.snapshot(srv) == {}
    assert meeting_counts.snapshot(
        types.SimpleNamespace(service=types.SimpleNamespace())) == {}
    ctx, said = _ctx({"pump_dispatches": 3, "t": 2.0})
    assert reader.read(ctx) is None and said == []
    quiet = {k: 0 for k in WINDOW}
    ctx, said = _ctx(quiet)
    assert reader.read(ctx) is None and said == []


def test_meeting_counts_snapshot_names_what_the_program_counts():
    stats = {k: i for i, k in enumerate(meeting_counts.STATS)}
    srv = types.SimpleNamespace(
        service=types.SimpleNamespace(stats=lambda: stats),
        frames_delivered=5, ops_delivered=7, signals_delivered=11,
    )
    snap = meeting_counts.snapshot(srv)
    assert snap["meeting.msn_lag_count"] == stats["msn_lag_count"]
    assert snap["meeting.socket.ops_delivered"] == 7
    assert set(snap) == {f"meeting.{k}" for k in meeting_counts.STATS} | {
        f"meeting.socket.{k}" for k in meeting_counts.SERVER}
