"""The delivery sweep of ``_drain_all`` follows what is queued (PR 45).

A session with nothing queued costs one test and is passed over; a session
with something queued gets ONE write of everything the sweep found on its
connection, in the order the per-message sweep wrote it (sequenced ops and
frames in inbox order, then signals, then nacks). Contracts under test:

- the one write's bytes are the per-socket reference encodings laid end to
  end, on the JSON wire, on the frame wire and in a room that mixes them;
- ``socket_writes`` counts the sessions that held something, flat in the
  messages a session holds (1 / 3 / 9) and in the idle sessions beside
  them; ``sessions_passed`` counts the idle sessions;
- a sweep over 128 sessions with nothing queued touches no connection
  method, makes no write and encodes nothing;
- an item that cannot be encoded mid-list fails its session's write: the
  whole batch goes back in order and leaves at the next sweep, once;
- ``LocalConnection`` and ``MultiNodeConnection`` go through the same
  three lists, and an op the cluster sequenced behind the facade's back is
  in its inboxes by the sweep (the service's pump runs first);
- over a real loopback socket a ``network_driver`` client decodes a 33-op
  flush that left in one write to the messages of the durable log.
"""

import asyncio
import time

import pytest

from fluidframework_tpu.drivers.network_driver import NetworkFluidService
from fluidframework_tpu.protocol.types import (
    DocumentMessage,
    MessageType,
    NackErrorType,
    NackMessage,
)
from fluidframework_tpu.service import network_server as ns_mod
from fluidframework_tpu.service.local_server import LocalFluidService
from fluidframework_tpu.service.multinode import MultiNodeFluidService
from fluidframework_tpu.service.network_server import (
    FluidNetworkServer,
    _Session,
)
from fluidframework_tpu.testing import faults

from test_delivery_encode_once import (
    _decoded,
    _deliveries,
    _offer,
    _one_write,
    _op,
    _reference_chunks,
    _room,
    _server,
    _Writer,
)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _nack(seq: int) -> NackMessage:
    return NackMessage(
        sequence_number=seq, content_code=429,
        error_type=NackErrorType.THROTTLING, message="slow down é",
        retry_after_s=0.25, client_sequence_number=7,
    )


# -- (a) everything a connection holds, one write, the parent's order --------


@pytest.mark.parametrize("wire", ["json", "frame", "mixed"])
def test_ops_a_frame_a_signal_and_a_nack_leave_in_one_write(wire):
    flags = {
        "json": [False] * 4, "frame": [True] * 4,
        "mixed": [True, False, True, False],
    }[wire]
    server = _server()
    sessions = _room(server, "doc", 4, frames_ok=flags)
    svc, sender = server.service, sessions[0].conn
    csn = _offer("json_op", sender, svc)
    csn = _offer("frame", sender, svc, csn)  # 3 ops in one SeqFrame
    csn = _offer("json_op", sender, svc, csn)
    _offer("signal", sessions[1].conn, svc)
    for s in sessions[:2]:  # a nack goes to one connection, here to two
        s.conn.nacks.append(_nack(svc.doc_head("doc")))
    owed = [_reference_chunks(s) for s in sessions]
    sent = _deliveries(server)
    writes, passed = server.socket_writes, server.sessions_passed
    server._drain_all()
    for s, chunks, frames_ok in zip(sessions, owed, flags):
        # op, frame (one binary or its three texts), op, signal, (nack).
        has_nack = s in sessions[:2]
        assert len(chunks) == (4 if frames_ok else 6) + has_nack
        assert _one_write(s, chunks)
        assert not (s.conn.inbox or s.conn.signals or s.conn.nacks)
    assert server.socket_writes == writes + 4
    assert server.sessions_passed == passed
    # Messages a socket: nacks are not among the three counts.
    assert _deliveries(server) - sent == sum(len(c) for c in owed) - 2
    assert _decoded(sessions[0].writer) == _decoded(sessions[1].writer)
    server._drain_all()  # nothing twice
    assert all(len(s.writer.chunks) == 1 for s in sessions)


# -- (b) the counters that say it engages ------------------------------------


@pytest.mark.parametrize("held", [1, 3, 9])
@pytest.mark.parametrize("n", [1, 4, 120])
def test_socket_writes_are_the_sessions_that_held_something(n, held):
    """``held`` messages a socket (a filled table row is nine ops): one
    write a session whatever it holds, none for the seven idle sessions of
    another document, which the sweep passes over."""
    server = _server()
    room = _room(server, "doc", n, frames_ok=False)
    other = _room(server, "elsewhere", 7, frames_ok=False)
    svc, sender = server.service, room[0].conn
    writes, sent = server.socket_writes, _deliveries(server)
    passed = server.sessions_passed
    for csn in range(1, held):
        _offer("json_op", sender, svc, csn)
    _offer("signal", sender, svc)
    owed = [_reference_chunks(s) for s in room]
    server._drain_all()  # ONE sweep
    assert server.socket_writes == writes + n
    assert server.sessions_passed == passed + 7
    assert _deliveries(server) == sent + held * n
    assert all(_one_write(s, chunks) for s, chunks in zip(room, owed))
    assert not any(s.writer.chunks for s in other)


@pytest.mark.parametrize("idle", [0, 100, 1000])
def test_sessions_passed_are_the_idle_sessions(idle):
    server = _server()
    room = _room(server, "doc", 4, frames_ok=True)
    for i in range(idle // 4):
        _room(server, f"idle-{i}", 4, frames_ok=(i % 2 == 0))
    unbound = _Session(_Writer())  # a socket that has not connected yet
    server._sessions.append(unbound)
    assert len(server._sessions) == 4 + idle + 1
    passed, writes = server.sessions_passed, server.socket_writes
    _offer("frame", room[0].conn, server.service)
    server._drain_all()
    assert server.socket_writes == writes + 4
    assert server.sessions_passed == passed + idle
    server._drain_all()  # now the four are idle too
    assert server.socket_writes == writes + 4
    assert server.sessions_passed == passed + idle + idle + 4
    assert not unbound.writer.chunks


class _CountingConn:
    """A connection that holds nothing and counts whatever is called on
    it: the three lists are all a sweep may read."""

    supports_nopump = True

    def __init__(self):
        self.inbox, self.signals, self.nacks = [], [], []
        self.calls = 0

    def take_inbox(self, *a, **kw):
        self.calls += 1
        return []

    take_inbox_raw = take_inbox


def test_an_idle_sweep_over_128_sessions_touches_no_connection_method():
    server = _server()
    for _ in range(128):
        s = _Session(_Writer())
        s.conn = _CountingConn()
        server._sessions.append(s)
    before = server.socket_writes, server.delivery_encodes, _deliveries(server)
    passed = server.sessions_passed
    for _ in range(3):
        server._drain_all()
    assert sum(s.conn.calls for s in server._sessions) == 0
    assert not any(s.writer.chunks for s in server._sessions)
    assert before == (
        server.socket_writes, server.delivery_encodes, _deliveries(server)
    )
    assert server.sessions_passed == passed + 3 * 128


# -- (c) an item that cannot be encoded, mid-list ----------------------------


def test_an_item_that_cannot_be_encoded_mid_list_fails_its_sessions_write(
    monkeypatch,
):
    """Three ops, a signal and a nack queued; the SECOND op's encoding
    raises. The encode is inside the write's ``try``: nothing of the
    batch reached the socket, so all of it goes back to its own queues in
    order, and the next sweep (the encoding mended) writes it once."""
    server = _server()
    sessions = _room(server, "doc", 3, frames_ok=False)
    svc, sender = server.service, sessions[0].conn
    for csn in (1, 2, 3):
        _offer("json_op", sender, svc, csn)
    _offer("signal", sender, svc)
    sessions[0].conn.nacks.append(_nack(svc.doc_head("doc")))
    owed = [_reference_chunks(s) for s in sessions]
    queued = [
        (list(s.conn.inbox), list(s.conn.signals), list(s.conn.nacks))
        for s in sessions
    ]
    bad = sessions[0].conn.inbox[1]
    real = ns_mod.to_jsonable

    def broken(m):
        if m is bad:
            raise ValueError("no encoding")
        return real(m)

    monkeypatch.setattr(ns_mod, "to_jsonable", broken)
    sent, writes = _deliveries(server), server.socket_writes
    server._drain_all()
    for s, held in zip(sessions, queued):
        assert s.writer.chunks == []
        assert (s.conn.inbox, s.conn.signals, s.conn.nacks) == held
    assert (_deliveries(server), server.socket_writes) == (sent, writes)
    monkeypatch.setattr(ns_mod, "to_jsonable", real)
    server._drain_all()
    for s, chunks in zip(sessions, owed):
        assert _one_write(s, chunks)
        assert not (s.conn.inbox or s.conn.signals or s.conn.nacks)
    assert _deliveries(server) == sent + 4 * 3
    assert server.socket_writes == writes + 3
    ops = [x for x in _decoded(sessions[1].writer) if x[0] == "op"]
    assert ops == sorted(ops) and len(set(ops)) == 3


# -- (d) the other services' connections: the same three lists --------------


def _bare_room(svc, n: int):
    server = FluidNetworkServer(svc)
    sessions = []
    for _ in range(n):
        s = _Session(_Writer())
        s.conn, s.doc_id = svc.connect("doc"), "doc"
        server._sessions.append(s)
        sessions.append(s)
    server._drain_all()
    for s in sessions:
        s.writer.chunks.clear()
    return server, sessions


@pytest.mark.parametrize("make", [LocalFluidService, MultiNodeFluidService])
def test_local_and_multinode_connections_are_passed_idle_and_written_once(make):
    svc = make()
    server, sessions = _bare_room(svc, 2)
    passed, writes = server.sessions_passed, server.socket_writes
    server._drain_all()  # nothing queued
    assert server.sessions_passed == passed + 2
    assert not any(s.writer.chunks for s in sessions)
    head = max(m.sequence_number for m in svc.get_deltas("doc"))
    sender = sessions[0].conn
    for csn in (1, 2):
        sender.submit(_op_at(csn, head))
    sender.submit_signal({"at": 1})
    server._drain_all()
    assert server.socket_writes == writes + 2
    for s in sessions:
        assert len(s.writer.chunks) == 1
        got = _decoded(s.writer)
        assert [x for x in got if x[0] == "op"] == [
            ("op", head + 1), ("op", head + 2)
        ]
        assert got[-1][0] == "signal"


def test_an_op_sequenced_behind_the_facades_back_is_delivered_by_the_sweep():
    """``MultiNodeConnection.take_inbox`` itself reads the shared log, so
    an inbox can look empty while the log holds an op for it (another
    facade's writer, a rebuilt owner). ``_drain_all`` pumps the service
    before the sweep: the one test reads inboxes that are whole."""
    svc = MultiNodeFluidService()
    server, sessions = _bare_room(svc, 2)
    head = max(m.sequence_number for m in svc.get_deltas("doc"))
    svc.cluster.owner("doc").ticket(
        "doc", sessions[0].conn.client_id, _op_at(1, head)
    )
    assert not any(s.conn.inbox for s in sessions)
    server._drain_all()
    for s in sessions:
        assert _decoded(s.writer) == [("op", head + 1)]


def _op_at(csn: int, ref: int) -> DocumentMessage:
    return DocumentMessage(
        client_sequence_number=csn, reference_sequence_number=ref,
        type=MessageType.OPERATION, contents={"n": csn},
    )


# -- (e) a real socket and the driver's decoder ------------------------------


def test_a_33_op_flush_in_one_write_decodes_to_the_logs_messages():
    """What a filled column is in a shared table: 33 sequenced ops found
    by one sweep. Each socket gets them in ONE write, and the
    ``network_driver`` client at the other end of a loopback socket reads
    the same 33 messages the durable log holds, in order."""
    srv = _server()
    srv.start()
    try:
        net = NetworkFluidService("127.0.0.1", srv.port)
        a, b = net.connect("doc"), net.connect("doc")
        for c in (a, b):  # the joins
            assert c.wait_for(lambda c=c: len(c.inbox) >= 1)
        time.sleep(0.1)
        seen = {id(c): len(c.take_inbox()) for c in (a, b)}

        def flush_33():
            sender = next(
                s.conn for s in srv._sessions
                if s.conn is not None and s.conn.client_id == a.client_id
            )
            before = srv.socket_writes, srv.ops_delivered
            for csn in range(1, 34):
                sender.submit(_op(sender, csn, srv.service))
            srv._drain_all()
            return (
                srv.socket_writes - before[0], srv.ops_delivered - before[1]
            )

        async def on_loop():
            return flush_33()

        writes, ops = asyncio.run_coroutine_threadsafe(
            on_loop(), srv._loop
        ).result(10)
        assert (writes, ops) == (2, 66)  # one write a socket, 33 ops in it
        log = [
            m for m in srv.service.get_deltas("doc")
            if m.client_sequence_number >= 1 and m.client_id == a.client_id
        ][-33:]
        assert len(log) == 33
        for c in (a, b):
            assert c.wait_for(lambda c=c: len(c.inbox) >= 33), seen
            got = c.take_inbox()
            assert got == log
        a.disconnect()
        b.disconnect()
    finally:
        srv.stop()
