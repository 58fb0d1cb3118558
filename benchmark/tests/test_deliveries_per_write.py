"""The ``deliveries_per_write`` reader (PR 45): messages the delivery sweep
wrote to op sockets per write that carried them, a window delta of five
always-on integers of the server, with one ``socket_writes`` line (the
writes, the messages, the sessions passed over). It reads nothing (and
does not raise) from a program without the count (the parent of PR 45);
the rehearsals' traced cases hold the result line to every listed metric
of their cell, this one among them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_deliveries_per_write.py -q

Nothing here is a time: every line says it ran on the CPU.
"""

import json
import os
import types

import pytest

from benchmark.layers import deliveries_per_write

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ctx(window):
    said = []
    ctx = types.SimpleNamespace(window=window, out=types.SimpleNamespace(
        say=lambda event, **kv: said.append((event, kv))
    ))
    return ctx, said


def _window(writes, passed=0, ops=0, frames=0, signals=0):
    return {
        "pump_dispatches": 7,
        "writes.socket_writes": writes,
        "writes.sessions_passed": passed,
        "writes.ops_delivered": ops,
        "writes.frames_delivered": frames,
        "writes.signals_delivered": signals,
    }


@pytest.mark.parametrize("window,want", [
    # every message a write of its own
    (_window(50, ops=30, signals=20), 1.0),
    # an op and its signal always together, 120 sockets
    (_window(120000, ops=120000, signals=120000), 2.0),
    # a table's filled rows among one-op actions, four sockets of 128
    (_window(80276, passed=2566380, ops=122096), 122096 / 80276),
    # the frame wire: one frame a sweep a socket
    (_window(15880, passed=492280, frames=15880), 1.0),
])
def test_messages_over_writes(window, want):
    ctx, said = _ctx(window)
    assert deliveries_per_write.read(ctx) == pytest.approx(want)
    (event, line), = said
    assert event == "socket_writes"
    assert line["writes"] == window["writes.socket_writes"]
    assert line["sessions_passed"] == window["writes.sessions_passed"]
    assert line["messages"] == sum(
        window[f"writes.{k}"]
        for k in ("ops_delivered", "frames_delivered", "signals_delivered")
    )


def test_reads_nothing_from_a_program_without_the_count():
    """The parent of PR 45 counts what it wrote and not its writes."""
    parent = types.SimpleNamespace(
        delivery_encodes=3, ops_delivered=9, frames_delivered=2,
        signals_delivered=4,
    )
    assert deliveries_per_write.snapshot(parent) == {}
    ctx, said = _ctx({
        "pump_dispatches": 3, "t": 2.0, "delivery.ops_delivered": 9,
        "delivery.frames_delivered": 2, "delivery.signals_delivered": 4,
    })
    assert deliveries_per_write.read(ctx) is None and said == []
    # The count is there and nothing was written: nothing to divide by.
    ctx, said = _ctx(_window(0, passed=1536))
    assert deliveries_per_write.read(ctx) is None and said == []


def test_snapshot_names_what_the_server_counts_under_its_own_prefix():
    srv = types.SimpleNamespace(
        socket_writes=13, sessions_passed=17, ops_delivered=5,
        frames_delivered=7, signals_delivered=11, delivery_encodes=3,
    )
    assert deliveries_per_write.snapshot(srv) == {
        "writes.socket_writes": 13, "writes.sessions_passed": 17,
        "writes.ops_delivered": 5, "writes.frames_delivered": 7,
        "writes.signals_delivered": 11,
    }


@pytest.mark.parametrize("sockets,idle", [(1, 0), (4, 0), (4, 60)])
def test_a_real_servers_sweeps(sockets, idle):
    """``FluidNetworkServer``'s own integers are what the snapshot reads:
    two sweeps, one carrying an op AND a signal to ``sockets`` connections
    of one document, one carrying an op alone, beside ``idle`` sessions of
    another document that hold nothing and are passed over."""
    from fluidframework_tpu.protocol.types import DocumentMessage, MessageType
    from fluidframework_tpu.service.network_server import (
        FluidNetworkServer,
        _Session,
    )
    from fluidframework_tpu.service.pipeline import PipelineFluidService

    svc = PipelineFluidService(n_partitions=1, device_backend=False)
    srv = FluidNetworkServer(svc)
    for doc, n in (("doc", sockets), ("elsewhere", idle)):
        for _ in range(n):
            s = _Session(types.SimpleNamespace(write=lambda data: None))
            s.conn, s.doc_id = svc.connect(doc), doc
            srv._sessions.append(s)
    srv._drain_all()
    before = deliveries_per_write.snapshot(srv)
    conn = srv._sessions[0].conn

    def op(csn):
        return DocumentMessage(
            client_sequence_number=csn,
            reference_sequence_number=svc.doc_head("doc"),
            type=MessageType.OPERATION, contents=None,
        )

    conn.submit(op(1))
    conn.submit_signal({"at": 3})
    srv._drain_all()
    conn.submit(op(2))
    srv._drain_all()
    after = deliveries_per_write.snapshot(srv)
    ctx, said = _ctx({k: after[k] - before[k] for k in after})
    assert deliveries_per_write.read(ctx) == pytest.approx(1.5)
    (_, line), = said
    assert line["writes"] == 2 * sockets
    assert line["messages"] == 3 * sockets
    assert line["sessions_passed"] == 2 * idle


def test_the_three_cells_that_serve_websockets_list_the_metric():
    """``run.py`` finds a metric's reader by the name before the first dot
    and reports it in the cells its ``workloads`` names; new entries go at
    the end of the list."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = {
        m["name"]: m for m in bench["per_layer"]
        if m["name"].split(".", 1)[0] == "deliveries_per_write"
    }
    assert {k: v["workloads"] for k, v in mine.items()} == {
        "deliveries_per_write.meeting": ["tsl120-ws-meeting"],
        "deliveries_per_write.table": ["mx10k-ws-table"],
        "deliveries_per_write.ws": ["p12k5-ws-edit"],
    }
    for m in mine.values():
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "messages", "higher", "program_counter", "ack_p95_ms"
        )
        assert m["layer"] == "front door + pipeline stages"
    names = [m["name"] for m in bench["per_layer"]]
    first = min(names.index(n) for n in mine)
    assert names[first:first + 3] == list(mine)  # appended together
