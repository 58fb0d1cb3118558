"""Sequencer (deli ticket) semantics tests — SURVEY.md Appendix C.2."""

from fluidframework_tpu.protocol.types import (
    DocumentMessage,
    MessageType,
    NackMessage,
)
from fluidframework_tpu.service.sequencer import DocumentSequencer


def op(cseq, ref, contents=None, ty=MessageType.OPERATION):
    return DocumentMessage(
        client_sequence_number=cseq,
        reference_sequence_number=ref,
        type=ty,
        contents=contents,
    )


def test_join_assigns_slots_and_sequences():
    s = DocumentSequencer("d")
    j0 = s.join()
    j1 = s.join()
    assert j0.contents["clientId"] == 0 and j1.contents["clientId"] == 1
    assert (j0.sequence_number, j1.sequence_number) == (1, 2)
    assert j0.type == MessageType.CLIENT_JOIN


def test_sequence_and_msn():
    s = DocumentSequencer("d")
    c0 = s.join().contents["clientId"]
    c1 = s.join().contents["clientId"]
    m = s.ticket(c0, op(1, 2))
    assert m.sequence_number == 3
    # MSN = min refSeq over clients = min(2, join-time 2) = 2
    assert m.minimum_sequence_number == 2
    m2 = s.ticket(c1, op(1, 3))
    assert m2.sequence_number == 4
    assert m2.minimum_sequence_number == 2  # c0 still at refSeq 2


def test_duplicate_dropped_and_gap_nacked():
    s = DocumentSequencer("d")
    c = s.join().contents["clientId"]
    assert s.ticket(c, op(1, 1)).sequence_number == 2
    assert s.ticket(c, op(1, 1)) is None  # duplicate
    nack = s.ticket(c, op(3, 1))  # gap: skipped cseq 2
    assert isinstance(nack, NackMessage) and nack.content_code == 400


def test_stale_refseq_nacked():
    s = DocumentSequencer("d")
    c0 = s.join().contents["clientId"]
    c1 = s.join().contents["clientId"]
    s.ticket(c0, op(1, 2))
    s.ticket(c1, op(1, 3))
    # push MSN up: both clients advance
    s.ticket(c0, op(2, 4))
    s.ticket(c1, op(2, 5))
    assert s.min_seq >= 4
    nack = s.ticket(c0, op(3, 1))
    assert isinstance(nack, NackMessage)
    assert "below MSN" in nack.message


def test_unknown_client_nacked():
    s = DocumentSequencer("d")
    nack = s.ticket(99, op(1, 0))
    assert isinstance(nack, NackMessage)


def test_read_client_cannot_write():
    s = DocumentSequencer("d")
    c = s.join(mode="read").contents["clientId"]
    nack = s.ticket(c, op(1, 0))
    assert isinstance(nack, NackMessage) and nack.content_code == 403


def test_leave_advances_msn():
    s = DocumentSequencer("d")
    c0 = s.join().contents["clientId"]
    c1 = s.join().contents["clientId"]
    s.ticket(c0, op(1, 2))  # c0 refSeq 2, c1 refSeq 2 (join-time)
    s.ticket(c1, op(1, 4))  # c1 refSeq 4
    lv = s.leave(c0)
    assert lv.minimum_sequence_number == 4  # only c1 remains


def test_no_clients_msn_is_seq():
    s = DocumentSequencer("d")
    c = s.join().contents["clientId"]
    s.ticket(c, op(1, 1))
    lv = s.leave(c)
    assert lv.minimum_sequence_number == lv.sequence_number


def test_immediate_noop_consumes_seq_and_updates_msn():
    """The reference's IMMEDIATE noop (non-null contents,
    ``ContainerRuntime.send_noop``) is sequenced like an op."""
    s = DocumentSequencer("d")
    c0 = s.join().contents["clientId"]
    c1 = s.join().contents["clientId"]
    s.ticket(c0, op(1, 2))
    before = s.seq
    noop = s.ticket(c1, op(1, 3, contents="", ty=MessageType.NOOP))
    assert s.seq == before + 1  # gapless stream: it is sequenced
    assert noop.type == MessageType.NOOP
    assert noop.minimum_sequence_number == 2
    assert (s.stats.noops_sequenced, s.stats.noops_received) == (1, 0)


def test_client_noop_moves_msn_without_a_sequence_number(monkeypatch):
    """Noop consolidation (reference deli lambda.ts:896-927): the
    collab-window noop (null contents) updates its client's refSeq,
    consumes neither a sequence number nor a clientSequenceNumber, and
    the MSN it moved rides the next sequenced message; with nothing else
    sequenced for 250 ms, ONE server noop carries it."""
    from fluidframework_tpu.service import sequencer as seq_mod

    clock = [1000.0]
    monkeypatch.setattr(seq_mod.time, "time", lambda: clock[0])
    s = DocumentSequencer("d")
    c0 = s.join().contents["clientId"]
    c1 = s.join().contents["clientId"]
    m = s.ticket(c0, op(1, 2))
    assert (m.sequence_number, m.minimum_sequence_number) == (3, 2)
    # c1 has read 3 and says so with a noop: nothing is sequenced.
    assert s.ticket(c1, op(0, 3, ty=MessageType.NOOP)) is None
    assert s.seq == 3 and s.min_seq == 2
    assert s.clients[c1].ref_seq == 3 and s.clients[c1].client_seq == 0
    assert s.stats.noops_received == 1 and s.stats.noops_sequenced == 0
    # The MSN has not moved (c0 still stands at 2): nothing to carry.
    assert s.noop_pending_since is None and not s.noop_due(clock[0] + 9)
    assert s.ticket(c0, op(0, 3, ty=MessageType.NOOP)) is None
    assert s.noop_pending_since == clock[0]
    # Quiet for less than the consolidation time: not yet.
    clock[0] += 0.2
    assert not s.noop_due(clock[0])
    clock[0] += 0.06
    assert s.noop_due(clock[0])
    noop = s.server_noop()
    assert noop.type == MessageType.NOOP and noop.client_id == -1
    assert (noop.sequence_number, noop.minimum_sequence_number) == (4, 3)
    assert s.stats.noops_sequenced == 1 and s.noop_pending_since is None
    assert s.server_noop() is None  # one, not one a noop
    # c1's next op is csn 1: the noop took no number.
    m = s.ticket(c1, op(1, 4))
    assert (m.sequence_number, m.client_sequence_number) == (5, 1)


def test_client_noop_msn_rides_the_next_op(monkeypatch):
    """An op sequenced inside the consolidation time carries the MSN the
    noops moved, and the server noop is not spent."""
    from fluidframework_tpu.service import sequencer as seq_mod

    clock = [50.0]
    monkeypatch.setattr(seq_mod.time, "time", lambda: clock[0])
    s = DocumentSequencer("d")
    c0 = s.join().contents["clientId"]
    c1 = s.join().contents["clientId"]
    s.ticket(c0, op(1, 2))
    s.ticket(c1, op(0, 3, ty=MessageType.NOOP))
    s.ticket(c0, op(0, 3, ty=MessageType.NOOP))
    assert s.noop_pending_since is not None
    clock[0] += 0.1
    t = s.ticket_uniform(c0, 2, 1, 3, clock[0])
    assert t == (4, 3)  # the op carries MSN 3
    assert s.noop_pending_since is None
    clock[0] += 1.0
    assert not s.noop_due(clock[0]) and s.server_noop() is None
    assert s.stats.noops_sequenced == 0
    assert (s.stats.msn_lag_sum, s.stats.msn_lag_count) == (0 + 1 + 1 + 1, 4)


def test_msn_never_regresses():
    s = DocumentSequencer("d")
    c0 = s.join().contents["clientId"]
    s.ticket(c0, op(1, 1))
    lv_seq = s.min_seq
    s.join()  # new client joins with refSeq = current seq
    assert s.min_seq >= lv_seq


def test_checkpoint_resume():
    s = DocumentSequencer("d")
    c0 = s.join().contents["clientId"]
    s.ticket(c0, op(1, 1))
    cp = s.checkpoint()
    s2 = DocumentSequencer("d", cp)
    m = s2.ticket(c0, op(2, 2))
    assert m.sequence_number == s.seq + 1
    assert s2.ticket(c0, op(2, 2)) is None  # dedup state survived


def test_cap_concurrent_writers_then_clean_429_and_retry():
    """MAX_WRITERS=124 concurrent write slots (four removers-bitmask
    lanes); the next writer gets a clean 429 nack with a retry-after and
    can retry once a departed writer's slot ages past the MSN."""
    from fluidframework_tpu.protocol.constants import MAX_WRITERS

    s = DocumentSequencer("d")
    clients = []
    for _ in range(MAX_WRITERS):
        j = s.join()
        assert j.type == MessageType.CLIENT_JOIN
        clients.append(j.contents["clientId"])
    assert sorted(clients) == list(range(124))
    assert s.writer_slots_peak == MAX_WRITERS
    assert s.stats.writer_slots_peak == MAX_WRITERS and s.stats.join_nacks_slots == 0
    overflow = s.join()
    assert isinstance(overflow, NackMessage)
    assert overflow.content_code == 429
    assert overflow.retry_after_s > 0 and s.stats.join_nacks_slots == 1
    # One writer leaves; its slot recycles only after the MSN passes the
    # leave (everyone has seen it) — then the retry succeeds.
    leave = s.leave(clients[5])
    assert leave is not None
    still = s.join()
    assert isinstance(still, NackMessage)  # leave not yet below MSN
    for c in clients:
        if c != clients[5]:
            s.ticket(c, op(1, leave.sequence_number))
    retry = s.join()
    assert retry.type == MessageType.CLIENT_JOIN
    assert retry.contents["clientId"] == clients[5]
