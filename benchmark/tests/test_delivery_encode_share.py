"""The ``delivery_encode_share`` reader (PR 37): encode passes of the
delivery sweep as a share of what it wrote to op sockets, a window delta
of four always-on integers of the server, with one ``delivery_encodes``
line. It reads nothing (and does not raise) from a program without the
count (the parent of PR 37); the meeting rehearsal's traced case in
``test_meeting.py`` holds the result line to every ``.meeting`` metric of
``BENCHMARK.json``, this one among them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_delivery_encode_share.py -q

Nothing here is a time: every line says it ran on the CPU.
"""

import json
import os
import types

import pytest

from benchmark.layers import delivery_encode_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ctx(window):
    said = []
    ctx = types.SimpleNamespace(window=window, out=types.SimpleNamespace(
        say=lambda event, **kv: said.append((event, kv))
    ))
    return ctx, said


def _window(encodes, ops=0, frames=0, signals=0):
    return {
        "pump_dispatches": 7,
        "delivery.delivery_encodes": encodes,
        "delivery.ops_delivered": ops,
        "delivery.frames_delivered": frames,
        "delivery.signals_delivered": signals,
    }


@pytest.mark.parametrize("window,want", [
    (_window(50, ops=30, signals=20), 100.0),          # a fan-out of one
    (_window(1000, frames=4000), 25.0),                # four sockets, frame wire
    (_window(2044, ops=122760, signals=122520), 100.0 * 2044 / 245280),
])
def test_share_is_encodes_over_deliveries_in_percent(window, want):
    ctx, said = _ctx(window)
    assert delivery_encode_share.read(ctx) == pytest.approx(want)
    (event, line), = said
    assert event == "delivery_encodes"
    assert line["encodes"] == window["delivery.delivery_encodes"]
    assert line["deliveries"] == (
        line["ops_delivered"] + line["frames_delivered"]
        + line["signals_delivered"]
    )


def test_reads_nothing_from_a_program_without_the_count():
    """The parent of PR 37 counts what it wrote and not what it encoded."""
    parent = types.SimpleNamespace(
        ops_delivered=9, frames_delivered=2, signals_delivered=4
    )
    assert delivery_encode_share.snapshot(parent) == {}
    ctx, said = _ctx({"pump_dispatches": 3, "t": 2.0})
    assert delivery_encode_share.read(ctx) is None and said == []
    # The count is there and nothing was written: nothing to divide by.
    ctx, said = _ctx(_window(0))
    assert delivery_encode_share.read(ctx) is None and said == []


def test_snapshot_names_what_the_server_counts():
    srv = types.SimpleNamespace(
        delivery_encodes=3, ops_delivered=5, frames_delivered=7,
        signals_delivered=11,
    )
    assert delivery_encode_share.snapshot(srv) == {
        "delivery.delivery_encodes": 3, "delivery.ops_delivered": 5,
        "delivery.frames_delivered": 7, "delivery.signals_delivered": 11,
    }


@pytest.mark.parametrize("sockets", [1, 4])
def test_share_of_a_real_servers_sweep(sockets):
    """``FluidNetworkServer``'s own integers are what the snapshot reads:
    one op and one signal to ``sockets`` connections of one document."""
    from fluidframework_tpu.protocol.types import DocumentMessage, MessageType
    from fluidframework_tpu.service.network_server import (
        FluidNetworkServer,
        _Session,
    )
    from fluidframework_tpu.service.pipeline import PipelineFluidService

    svc = PipelineFluidService(n_partitions=1, device_backend=False)
    srv = FluidNetworkServer(svc)
    for _ in range(sockets):
        s = _Session(types.SimpleNamespace(write=lambda data: None))
        s.conn, s.doc_id = svc.connect("doc"), "doc"
        srv._sessions.append(s)
    srv._drain_all()
    before = delivery_encode_share.snapshot(srv)
    conn = srv._sessions[0].conn
    conn.submit(DocumentMessage(
        client_sequence_number=1, reference_sequence_number=svc.doc_head("doc"),
        type=MessageType.OPERATION, contents=None,
    ))
    conn.submit_signal({"at": 3})
    srv._drain_all()
    after = delivery_encode_share.snapshot(srv)
    ctx, said = _ctx({k: after[k] - before[k] for k in after})
    assert delivery_encode_share.read(ctx) == pytest.approx(100.0 / sockets)
    assert said[0][1]["encodes"] == 2
    assert said[0][1]["deliveries"] == 2 * sockets


def test_both_cells_that_serve_websockets_list_the_metric():
    """``run.py`` finds a metric's reader by the name before the first dot
    and reports it in the cells its ``workloads`` names. (A traced
    rehearsal of its own is left out on purpose: two traced runs of one
    workload at once share ``benchmark_out/<workload>/trace``, and tier-1
    already runs both rehearsals traced from two other files; the meeting
    rehearsal's traced case holds the line to every listed metric.)"""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = {
        m["name"]: m for m in bench["per_layer"]
        if m["name"].split(".", 1)[0] == "delivery_encode_share"
    }
    assert {k: v["workloads"] for k, v in mine.items()} == {
        "delivery_encode_share.meeting": ["tsl120-ws-meeting"],
        "delivery_encode_share.ws": ["p12k5-ws-edit"],
    }
    for m in mine.values():
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "%", "lower", "program_counter", "ack_p95_ms"
        )
        assert m["layer"] == "front door + pipeline stages"
    assert [m["name"] for m in bench["per_layer"][-2:]] == sorted(mine)
