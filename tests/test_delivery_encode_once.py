"""The delivery sweep of ``_drain_all`` encodes a queued item once a sweep,
not once a socket (PR 37).

The broadcasters queue ONE object (a sequenced message, a ``SeqFrame``, a
signal) on every connection of a room; within one sweep its wire bytes are
built at most once per wire format and every socket that has it queued is
written the same bytes. Contracts under test:

- what each socket receives is byte for byte the per-socket encoding (the
  parent's expressions, written out here as the reference), laid end to
  end in ONE write a socket a sweep (PR 45: a sweep's unit of delivery is
  everything it found queued on the connection);
- ``delivery_encodes`` is flat over 1 / 10 / 120 connections of a room
  while the deliveries grow with them;
- a ``ws.deliver`` fault on the k-th socket puts every item of that
  connection's write back in its own queue and nobody else's (a crash
  after the write: none of them), and every message still arrives
  exactly once;
- two documents whose signals carry the same ``(client_id, num)`` get
  their own bytes; a room mixing wires builds each format once; a
  rejoined connection gets the expanded suffix of a frame only.
"""

import json

import pytest

from fluidframework_tpu.protocol.opframe import OpFrame, SeqFrame
from fluidframework_tpu.protocol.types import DocumentMessage, MessageType
from fluidframework_tpu.service import network_server as ns_mod
from fluidframework_tpu.service import wsproto
from fluidframework_tpu.service.codec import to_jsonable
from fluidframework_tpu.service.network_server import (
    FluidNetworkServer,
    _Session,
)
from fluidframework_tpu.service.pipeline import PipelineFluidService
from fluidframework_tpu.telemetry import metrics
from fluidframework_tpu.testing import faults

MINT = 1 << 14  # shared_string._MINT_STRIDE (content-id scoping)
KINDS = ("json_op", "frame", "signal")


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


class _Writer:
    """Duck-typed asyncio writer: one chunk a ``write``."""

    def __init__(self):
        self.chunks = []

    def write(self, data) -> None:
        self.chunks.append(data)

    def close(self) -> None:
        pass


def _room(server, doc: str, n: int, frames_ok) -> list:
    """``n`` connected op sessions of ``doc`` (``frames_ok`` one flag for
    all or one a session), joins drained and the writers emptied."""
    svc = server.service
    flags = [frames_ok] * n if isinstance(frames_ok, bool) else list(frames_ok)
    sessions = []
    for flag in flags:
        s = _Session(_Writer())
        s.conn, s.doc_id, s.frames_ok = svc.connect(doc), doc, flag
        server._sessions.append(s)
        sessions.append(s)
    server._drain_all()
    for s in sessions:
        assert not s.conn.inbox and not s.conn.signals
        s.writer.chunks.clear()
    return sessions


def _server():
    return FluidNetworkServer(
        PipelineFluidService(n_partitions=1, device_backend=False)
    )


def _op(conn, csn: int, svc) -> DocumentMessage:
    return DocumentMessage(
        client_sequence_number=csn,
        reference_sequence_number=svc.doc_head(conn.doc_id),
        type=MessageType.OPERATION,
        contents={"address": "s", "contents": {"n": csn, "text": "é\n"}},
    )


def _frame(conn, k: int, c0: int, svc) -> OpFrame:
    origs = [conn.conn_no * MINT + c0 + j for j in range(k)]
    return OpFrame.build(
        "s", ["ins"] * k, [0] * k, origs, ["x"] * k, csn0=c0,
        ref=svc.doc_head(conn.doc_id),
    )


def _offer(kind: str, conn, svc, csn: int = 1) -> int:
    """Queue one item of ``kind`` on the room; returns the next csn."""
    if kind == "json_op":
        conn.submit(_op(conn, csn, svc))
        return csn + 1
    if kind == "frame":
        conn.submit_frame(_frame(conn, 3, csn, svc))
        return csn + 3
    conn.submit_signal({"cursor": csn, "who": "é"})
    return csn


# -- the reference: the per-socket encoding, as the parent wrote it ----------


def _ref_text(obj: dict) -> bytes:
    return wsproto.encode_frame(wsproto.OP_TEXT, json.dumps(obj).encode())


def _reference_chunks(s: _Session) -> list:
    """What ONE socket is owed for what its connection has queued, each
    item encoded for this socket alone: inbox, then signals, then nacks."""
    out = []
    for m in s.conn.inbox:
        if hasattr(m, "sequence_number"):
            out.append(_ref_text({"type": "op", "msg": to_jsonable(m)}))
        elif s.frames_ok:
            out.append(wsproto.encode_frame(wsproto.OP_BINARY, m.encode()))
        else:
            out.extend(
                _ref_text({"type": "op", "msg": to_jsonable(x)})
                for x in m.messages()
            )
    for sig in s.conn.signals:
        out.append(_ref_text({
            "type": "signal",
            "client_id": sig.client_id,
            "num": sig.client_connection_number,
            "content": sig.content,
        }))
    for nk in s.conn.nacks:
        out.append(_ref_text({"type": "nack", "nack": to_jsonable(nk)}))
    return out


def _one_write(s: _Session, chunks: list) -> bool:
    """The socket got ONE write: the reference encodings end to end."""
    return s.writer.chunks == [b"".join(chunks)]


def _decoded(writer: _Writer) -> list:
    """A socket's stream as comparable items: ("op", seq), ("signal",
    client, num) — a binary frame counts as its ops."""
    out = []
    for opcode, payload in wsproto.FrameDecoder().feed(b"".join(writer.chunks)):
        if opcode == wsproto.OP_BINARY:
            sf = SeqFrame.decode(payload)
            out.extend(("op", q) for q in range(sf.first_seq, sf.last_seq + 1))
            continue
        m = json.loads(payload.decode())
        if m["type"] == "op":
            out.append(("op", m["msg"]["sequence_number"]))
        elif m["type"] == "signal":
            out.append(("signal", m["client_id"], m["num"]))
    return out


def _deliveries(server) -> int:
    return (
        server.ops_delivered + server.frames_delivered
        + server.signals_delivered
    )


# -- (a) byte parity ---------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", [1, 4, 120])
def test_every_socket_gets_the_per_socket_encoding(n, kind):
    server = _server()
    sessions = _room(server, "doc", n, frames_ok=(kind == "frame"))
    sender = sessions[0].conn
    csn = _offer(kind, sender, server.service)
    _offer(kind, sender, server.service, csn)  # two items: order shows
    owed = [_reference_chunks(s) for s in sessions]
    assert all(len(chunks) == 2 for chunks in owed)
    before, writes = server.delivery_encodes, server.socket_writes
    server._drain_all()
    for s, chunks in zip(sessions, owed):
        assert _one_write(s, chunks)  # one write a socket, these bytes
        assert not s.conn.inbox and not s.conn.signals
    # Two items, each built once whatever the sockets; one write a socket.
    assert server.delivery_encodes == before + 2
    assert server.socket_writes == writes + n


@pytest.mark.parametrize("kind", KINDS)
def test_json_wire_sessions_get_a_frame_as_its_ops(kind):
    """A room on the JSON wire whose sender uses frames: the frame is
    expanded once and each op's text built once for all sockets."""
    server = _server()
    sessions = _room(server, "doc", 4, frames_ok=False)
    _offer("frame", sessions[0].conn, server.service)
    _offer(kind, sessions[1].conn, server.service)
    owed = [_reference_chunks(s) for s in sessions]
    server._drain_all()
    for s, chunks in zip(sessions, owed):
        assert len(chunks) >= 4 and _one_write(s, chunks)


# -- (b) the counter ---------------------------------------------------------


def _sweep_counts(n: int, frames_ok: bool):
    server = _server()
    sessions = _room(server, "doc", n, frames_ok)
    svc, sender = server.service, sessions[0].conn
    e0, d0 = server.delivery_encodes, _deliveries(server)
    csn = _offer("json_op", sender, svc)
    csn = _offer("frame", sender, svc, csn)
    _offer("signal", sender, svc)
    server._drain_all()  # ONE sweep
    return server.delivery_encodes - e0, _deliveries(server) - d0


@pytest.mark.parametrize("frames_ok", [False, True])
def test_delivery_encodes_flat_while_deliveries_grow(frames_ok):
    e1, d1 = _sweep_counts(1, frames_ok)
    e10, d10 = _sweep_counts(10, frames_ok)
    e120, d120 = _sweep_counts(120, frames_ok)
    # One JSON op, one frame (one binary, or its three ops' texts), one
    # signal: the passes do not depend on the sockets.
    assert e1 == e10 == e120 == (3 if frames_ok else 5)
    assert (d10, d120) == (10 * d1, 120 * d1) and d1 == e1
    # 1.0 an encode a delivery at a fan-out of one, 1/120 in a meeting.
    assert e1 / d1 == 1.0 and e120 / d120 == pytest.approx(1 / 120)


def test_an_idle_sweep_encodes_nothing():
    server = _server()
    _room(server, "doc", 4, frames_ok=True)
    before = server.delivery_encodes
    server._drain_all()
    assert server.delivery_encodes == before


# -- (c) a fault on the k-th socket ------------------------------------------


def _ws_deliver_outcomes() -> dict:
    """``retry_attempts_total{site="ws.deliver"}`` by outcome."""
    c = metrics.REGISTRY.get("retry_attempts_total")
    out: dict = {}
    for key, _suffix, value in c.samples() if c is not None else ():
        d = dict(key)
        if d.get("site") == "ws.deliver":
            out[d["outcome"]] = out.get(d["outcome"], 0) + value
    return out


class _OnNth(faults.FaultPolicy):
    """Pass every invocation of the site but the ``nth`` (from 1)."""

    def __init__(self, nth: int, action: tuple):
        self.nth, self.action, self.seen = nth, action, 0

    def plan(self):
        self.seen += 1
        return self.action if self.seen == self.nth else None


@pytest.mark.parametrize(
    "action,landed", [(("fail",), 0), (("crash", "after"), 4)]
)
@pytest.mark.parametrize("k", [1, 5, 8])
def test_a_fault_on_the_kth_sockets_write_requeues_its_whole_batch_alone(
    k, action, landed
):
    """Three ops and a signal queued on a room of 8; the ONE write of the
    k-th connection faults. ``fail``: nothing landed, the three ops go
    back to that inbox and the signal to its signals, in order;
    crash-after: all four reached the socket, none goes back. Everybody
    else is written once, in this sweep, and the next sweep completes the
    k-th: every item exactly once, the bytes on all eight sockets those
    of the reference."""
    server = _server()
    sessions = _room(server, "doc", 8, frames_ok=False)
    svc, sender = server.service, sessions[2].conn
    for csn in (1, 2, 3):
        _offer("json_op", sender, svc, csn)
    _offer("signal", sender, svc)
    owed = [_reference_chunks(s) for s in sessions]
    assert all(len(chunks) == 4 for chunks in owed)
    queued = [(list(s.conn.inbox), list(s.conn.signals)) for s in sessions]
    # Each earlier connection is one write (3 ops + the signal).
    faults.arm("ws.deliver", _OnNth(k, action))
    writes, counted = server.socket_writes, _ws_deliver_outcomes()
    server._drain_all()
    assert faults.REGISTRY.injected_total("ws.deliver") == 1
    # One count a faulted write, whatever it carried (two queues here).
    outcome = "fatal" if landed else "requeue"
    now = _ws_deliver_outcomes()
    assert now.get(outcome, 0) == counted.get(outcome, 0) + 1
    assert sum(now.values()) == sum(counted.values()) + 1
    assert server.socket_writes == writes + (8 if landed else 7)
    hit = sessions[k - 1]
    for s, chunks, (ops, sigs) in zip(sessions, owed, queued):
        if s is hit and not landed:
            # Nothing of this sweep on this socket; every item back in
            # its own queue, in order.
            assert s.writer.chunks == []
            assert s.conn.inbox == ops and s.conn.signals == sigs
        else:
            assert _one_write(s, chunks)
            assert not s.conn.inbox and not s.conn.signals
    faults.disarm()
    server._drain_all()
    want = _decoded(sessions[3].writer)  # never the k-th
    assert len(want) == 4 and len(set(want)) == 4
    for s, chunks in zip(sessions, owed):
        assert _one_write(s, chunks)  # nothing twice
        assert [x for x in _decoded(s.writer) if x[0] == "op"] == want[:3]
        assert not s.conn.inbox and not s.conn.signals
    server._drain_all()  # nothing left to redeliver
    assert all(len(s.writer.chunks) == 1 for s in sessions)
    assert server.socket_writes == writes + 8


def test_an_item_that_cannot_be_encoded_is_requeued(monkeypatch):
    """The encode stays inside the ``try``: a message whose encoding
    raises goes back to the head of every inbox that held it."""
    server = _server()
    sessions = _room(server, "doc", 3, frames_ok=False)
    _offer("json_op", sessions[0].conn, server.service)
    real = ns_mod.to_jsonable

    def broken(m):
        raise ValueError("no encoding")

    monkeypatch.setattr(ns_mod, "to_jsonable", broken)
    server._drain_all()
    assert all(len(s.conn.inbox) == 1 and not s.writer.chunks for s in sessions)
    monkeypatch.setattr(ns_mod, "to_jsonable", real)
    owed = [_reference_chunks(s) for s in sessions]
    server._drain_all()
    assert all(_one_write(s, chunks) for s, chunks in zip(sessions, owed))


# -- (d) two documents, the same (client_id, num) ----------------------------


def test_two_documents_signals_with_one_number_get_their_own_bytes():
    server = _server()
    svc = server.service
    room_a = _room(server, "doc-a", 3, frames_ok=False)
    room_b = _room(server, "doc-b", 3, frames_ok=False)
    queued = svc.stats()["signals_delivered"]
    room_a[0].conn.submit_signal({"doc": "a"})
    room_b[0].conn.submit_signal({"doc": "b"})
    sig_a, sig_b = room_a[1].conn.signals[0], room_b[1].conn.signals[0]
    assert (sig_a.client_id, sig_a.client_connection_number) == (
        sig_b.client_id, sig_b.client_connection_number
    )
    # One object a signal of a document, on every connection of its room.
    assert all(s.conn.signals[0] is sig_a for s in room_a)
    assert all(s.conn.signals[0] is sig_b for s in room_b)
    before, written = server.delivery_encodes, server.signals_delivered
    server._drain_all()
    assert server.delivery_encodes == before + 2
    for room, doc in ((room_a, "a"), (room_b, "b")):
        for s in room:
            (chunk,) = s.writer.chunks
            ((_, payload),) = wsproto.FrameDecoder().feed(chunk)
            assert json.loads(payload)["content"] == {"doc": doc}
    assert svc.stats()["signals_delivered"] == queued + 6
    assert server.signals_delivered == written + 6


# -- (e) a room that mixes the wires -----------------------------------------


def test_a_mixed_room_builds_each_format_once(monkeypatch):
    server = _server()
    sessions = _room(server, "doc", 4, frames_ok=[True, False, True, False])
    svc = server.service
    _offer("frame", sessions[0].conn, svc)       # 3 ops in one SeqFrame
    _offer("json_op", sessions[1].conn, svc)     # a sequenced message
    owed = [_reference_chunks(s) for s in sessions]
    calls = {"jsonable": 0, "encode": 0, "messages": 0}
    real_jsonable, real_encode = ns_mod.to_jsonable, SeqFrame.encode
    real_messages = SeqFrame.messages

    def counting_jsonable(m):
        calls["jsonable"] += 1
        return real_jsonable(m)

    def counting_encode(self):
        calls["encode"] += 1
        return real_encode(self)

    def counting_messages(self, start=0):
        calls["messages"] += 1
        return real_messages(self, start)

    monkeypatch.setattr(ns_mod, "to_jsonable", counting_jsonable)
    monkeypatch.setattr(SeqFrame, "encode", counting_encode)
    monkeypatch.setattr(SeqFrame, "messages", counting_messages)
    before = server.delivery_encodes
    server._drain_all()
    monkeypatch.undo()
    # The frame: one binary for the two frame-wire sessions, one expansion
    # and three texts for the two JSON ones; the JSON op: one text for all.
    assert calls == {"jsonable": 4, "encode": 1, "messages": 1}
    assert server.delivery_encodes == before + 5
    for s, chunks in zip(sessions, owed):
        assert _one_write(s, chunks)
        opcodes = [
            op for c in s.writer.chunks
            for op, _ in wsproto.FrameDecoder().feed(c)
        ]
        assert opcodes == (
            [wsproto.OP_BINARY, wsproto.OP_TEXT] if s.frames_ok
            else [wsproto.OP_TEXT] * 4
        )
    assert server.frames_delivered == 2 and _decoded(
        sessions[0].writer
    ) == _decoded(sessions[1].writer)


# -- (f) a rejoined connection that straddles a frame ------------------------


@pytest.mark.parametrize("frames_ok", [False, True])
def test_a_straddling_connection_gets_the_expanded_suffix_only(frames_ok):
    """A connection whose watermark lies inside a frame (a rejoin that
    caught up through the deltas) is queued the frame's expanded tail by
    the broadcaster: per-op texts past its watermark, whatever its wire,
    while the rest of the room gets the frame whole."""
    server = _server()
    sessions = _room(server, "doc", 3, frames_ok=frames_ok)
    svc = server.service
    head = svc.doc_head("doc")
    late = sessions[2]
    late.conn.delivered_seq = head + 2  # ops head+1, head+2 already seen
    _offer("frame", sessions[0].conn, svc)  # head+1 .. head+3
    assert [m.sequence_number for m in late.conn.inbox] == [head + 3]
    owed = [_reference_chunks(s) for s in sessions]
    server._drain_all()
    for s, chunks in zip(sessions, owed):
        assert _one_write(s, chunks)
    assert _decoded(late.writer) == [("op", head + 3)]
    assert _decoded(sessions[1].writer) == [
        ("op", head + 1), ("op", head + 2), ("op", head + 3)
    ]
