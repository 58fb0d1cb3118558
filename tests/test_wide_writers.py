"""A document with more than 93 concurrent writers (PR 36: the reference's
own service load test holds 120 clients in one document): the removers set
across its four lanes in both engines, 120 real clients through the served
pipeline with rejoins, and the collab-window heartbeat with deli's noop
consolidation against a fake clock."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fluidframework_tpu.models.shared_string import SharedString
from fluidframework_tpu.ops import encode as E
from fluidframework_tpu.ops.segment_state import (
    RBITS_LANES, RBITS_PER_LANE, SegmentState, rbits_of,
)
from fluidframework_tpu.parallel import fleet as F
from fluidframework_tpu.protocol.constants import (
    ERR_CLIENT, KIND_FREE, MAX_WRITERS, NO_CLIENT, OP_WIDTH, RSEQ_NONE,
)
from fluidframework_tpu.protocol.types import MessageType
from fluidframework_tpu.runtime import container as C
from fluidframework_tpu.runtime.container import ContainerRuntime
from fluidframework_tpu.service.pipeline import PipelineFluidService
from fluidframework_tpu.testing.oracle import OracleDoc

_CAP, _SLOTS, _K = 64, 8, 8
_TOP = MAX_WRITERS - 1


def _host(state):
    return SegmentState(*[np.array(x) for x in state])


def _seed_rows():
    """Four one-character inserts a slot, by writers of the first lane."""
    rows = np.zeros((_SLOTS, 4, OP_WIDTH), np.int32)
    for d in range(_SLOTS):
        for i in range(4):
            rows[d, i] = E.insert(i, 10 * d + i + 1, 1, seq=i + 1, ref=i,
                                  client=(d + i) % 4)
    return rows


def _wide_rows(writers):
    """Writers ``a, b, c, d`` (slots of the upper lanes; ``d`` the last
    slot) on a document of ten characters at seq 5: three of them remove
    overlapping ranges concurrently (all authored against seq 5), then one
    inserts from a view that still holds everything, one from a view that
    has lost its own removes and the first writer's, and one appends."""
    a, b, c, d = writers
    return np.stack([
        E.insert(2, 99, 6, seq=5, ref=4, client=a),
        E.remove(2, 6, seq=6, ref=5, client=b),
        E.remove(4, 8, seq=7, ref=5, client=d),
        E.remove(0, 3, seq=8, ref=5, client=a),
        E.insert(5, 100, 2, seq=9, ref=5, client=c),
        E.insert(4, 101, 1, seq=10, ref=7, client=d),
        E.insert(1, 102, 1, seq=11, ref=10, client=b),
        np.zeros(OP_WIDTH, np.int32),
    ]).astype(np.int32)


def _rows_of(h, slot):
    """One slot's live rows, lane for lane, with the removers as a set of
    writer slots read out of the bitmask lanes."""
    out = []
    for i in range(int(h.count[slot])):
        if int(h.kind[slot, i]) == KIND_FREE:
            continue
        rseq = int(h.rseq[slot, i])
        removers = {
            RBITS_PER_LANE * lane + bit
            for lane, bits in enumerate(rbits_of(h))
            for bit in range(RBITS_PER_LANE)
            if (int(bits[slot, i]) >> bit) & 1
        }
        out.append((
            int(h.orig[slot, i]), int(h.off[slot, i]), int(h.length[slot, i]),
            int(h.seq[slot, i]), int(h.client[slot, i]),
            None if rseq == RSEQ_NONE else rseq, int(h.aval[slot, i]),
            removers,
        ))
    return out


@pytest.mark.parametrize("writers", [
    (93, 100, 110, _TOP),  # all of the fourth lane
    (95, 30, 64, _TOP),  # one of each lane
    (_TOP, 93, 92, 94),  # the lane's edges
])
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_upper_lane_writers_remove_overlapping_ranges(kernel, writers):
    """Equal to the plain oracle lane for lane (structure, every
    remover), no error bit, and the step's other slots bit-identical."""
    assert len(RBITS_LANES) == 4 and _TOP == 123
    pool = F._Pool(_CAP, _SLOTS, kernel)
    seeded = _host(pool._step(pool.state, jnp.asarray(_seed_rows())))
    busy = 5
    rows_b = np.zeros((1, _K, OP_WIDTH), np.int32)
    rows_b[0] = _wide_rows(writers)
    got = _host(F._fused_sparse_step(kernel, None)(
        jax.device_put(seeded), jnp.asarray(rows_b),
        jnp.asarray([busy], np.int32),
    )[0])
    oracle = OracleDoc(NO_CLIENT)
    for row in list(_seed_rows()[busy]) + list(rows_b[0][:-1]):
        oracle.apply(row)
    want = [
        (s.orig, s.off, s.length, s.seq, s.client, s.removed_seq, s.aval,
         set(s.removers))
        for s in oracle.segs
    ]
    assert _rows_of(got, busy) == want
    assert any(len(r[-1]) >= 2 and max(r[-1]) >= 93 for r in want)
    assert int(got.err[busy]) == 0 and int(got.cur_seq[busy]) == 11
    others = np.setdiff1d(np.arange(_SLOTS), [busy])
    for name, x, y in zip(SegmentState._fields, got, seeded):
        assert np.array_equal(x[others], y[others]), name


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_the_slot_past_the_last_lane_is_flagged(kernel):
    pool = F._Pool(_CAP, _SLOTS, kernel)
    rows = np.zeros((_SLOTS, 1, OP_WIDTH), np.int32)
    rows[1, 0] = E.insert(0, 1, 1, seq=1, ref=0, client=_TOP)
    rows[2, 0] = E.insert(0, 1, 1, seq=1, ref=0, client=MAX_WRITERS)
    got = _host(pool._step(pool.state, jnp.asarray(rows)))
    assert int(got.err[1]) == 0 and int(got.err[2]) & ERR_CLIENT


# -- 120 real clients through the served pipeline -------------------------------


def _replayed(svc, doc):
    """(text, head) of the durable log by the plain reference."""
    from benchmark.harness import read_log
    from benchmark.reference.replay import replay

    head, log = read_log(svc, doc)
    text, _acked, _n = replay(log, head)
    return text, head, log


def test_120_writers_with_heartbeat_and_rejoins_converge():
    """One document, 120 real ``ContainerRuntime`` + ``SharedString``
    clients on the served pipeline (device backend on), a few hundred ops
    with the collab-window heartbeat running and a third of the writers
    dropping and rejoining: every client's text == the served text == the
    replay of the durable log; no join was refused; no sequence number
    went to a client's noop."""
    n, doc = 120, "meeting"
    rng = np.random.default_rng(36)
    svc = PipelineFluidService(n_partitions=2)
    rts = [
        ContainerRuntime(svc, doc, channels=(SharedString("s"),))
        for _ in range(n)
    ]
    assert sorted(rt.client_id for rt in rts) == list(range(n))
    clock = [0.0]  # a turn below is one second of the clients' clock
    for rt in rts:
        rt.clock = lambda: clock[0]

    def settle():
        for rt in rts:
            rt.flush()
        while any(rt.process_incoming() for rt in rts):
            pass

    def edit(rt):
        ch = rt.get_channel("s")
        live = len(ch.get_text())
        if live >= 24:
            start = int(rng.integers(0, 9))
            ch.remove_range(start, start + live - 8)
        elif live == 0 or rng.random() < 0.7:
            ch.insert_text(int(rng.integers(0, live + 1)), "abcdef"[live % 6])
        else:
            start = int(rng.integers(0, live))
            ch.remove_range(start, start + 1)

    rejoiners = rng.choice(n, n // 3, replace=False).tolist()
    for turn in range(100):
        # A few writers edit concurrently (same refSeq), then everyone
        # takes in what came: the others' ops drive the heartbeat (its
        # timer mostly: two turns; the count for whoever read fifty).
        clock[0] += 1.0
        for i in rng.choice(n, 3, replace=False).tolist():
            edit(rts[i])
        settle()
        if turn % 2 == 0 and rejoiners:
            # Ungraceful drop with an edit in flight, then the rejoin: the
            # free slots (124 - 120) carry it while the MSN catches up.
            rt = rts[rejoiners.pop()]
            edit(rt)
            rt.flush()
            rt.drop_connection()
            rt.reconnect()
            settle()
    settle()
    stats = svc.stats()
    assert stats["writer_slots_peak"] >= n
    assert stats["join_nacks_slots"] == 0
    assert stats["noops_received"] > 0  # the heartbeat ran
    assert sum(rt.heartbeat_noops for rt in rts) == stats["noops_received"]
    served = svc.device_text(doc, "s")
    text, head, log = _replayed(svc, doc)
    assert served == text
    for rt in rts:
        assert not rt.pending
        assert rt.get_channel("s").get_text() == served
        assert rt.ref_seq == head
    # A client's noop took no sequence number: every NOOP in the log is
    # the server's consolidated one.
    from fluidframework_tpu.service.lambdas import stored_message

    noops = [
        m for _lo, _hi, obj in svc.log_entries(doc, 1, head)
        for m in (obj.messages() if hasattr(obj, "messages")
                  else [stored_message(obj)])
        if m.type == MessageType.NOOP
    ]
    assert all(m.client_id == -1 for m in noops)
    assert len(noops) == stats["noops_sequenced"]
    assert svc.device.stats()["docs_with_errors"] == 0


def test_a_join_past_the_cap_is_refused_for_now_and_says_when():
    from fluidframework_tpu.service.pipeline import JoinRefused

    svc = PipelineFluidService(n_partitions=1, device_backend=False)
    conns = [svc.connect("d") for _ in range(MAX_WRITERS)]
    assert len(conns) == 124
    with pytest.raises(JoinRefused) as ei:
        svc.connect("d")
    assert ei.value.retry_after_s > 0
    assert "writer slots exhausted (124)" in str(ei.value)
    assert svc.stats()["join_nacks_slots"] == 1
    assert svc.stats()["writer_slots_peak"] == 124


# -- the heartbeat's two triggers, and deli's consolidation ----------------------


def _pair(svc, clock):
    rts = [ContainerRuntime(svc, "d", channels=(SharedString("s"),))
           for _ in range(2)]
    for rt in rts:
        rt.clock = lambda: clock[0]
    return rts


def test_heartbeat_after_fifty_ops_of_another_client():
    clock = [0.0]
    svc = PipelineFluidService(n_partitions=1, device_backend=False)
    a, b = _pair(svc, clock)
    for i in range(C.NOOP_COUNT_FREQUENCY - 1):
        a.get_channel("s").insert_text(0, "x")
        a.flush()
    b.process_incoming()
    assert b.heartbeat_noops == 0
    a.get_channel("s").insert_text(0, "x")
    a.flush()
    b.process_incoming()
    assert b.heartbeat_noops == 1  # the fiftieth, whatever the clock says
    assert a.heartbeat_noops == 0  # its own ops are no reason
    assert svc.stats()["noops_received"] == 1
    assert svc.stats()["noops_sequenced"] == 0


def test_heartbeat_two_seconds_after_an_op_of_another_client():
    clock = [0.0]
    svc = PipelineFluidService(n_partitions=1, device_backend=False)
    a, b = _pair(svc, clock)
    a.get_channel("s").insert_text(0, "x")
    a.flush()
    b.process_incoming()
    clock[0] = C.NOOP_TIME_FREQUENCY_S - 0.01
    b.process_incoming()
    assert b.heartbeat_noops == 0
    clock[0] = C.NOOP_TIME_FREQUENCY_S
    b.process_incoming()
    assert b.heartbeat_noops == 1
    clock[0] += 10.0
    b.process_incoming()
    assert b.heartbeat_noops == 1  # nothing new was read: nothing to say


def test_a_send_of_ones_own_resets_the_heartbeat():
    clock = [0.0]
    svc = PipelineFluidService(n_partitions=1, device_backend=False)
    a, b = _pair(svc, clock)
    a.get_channel("s").insert_text(0, "x")
    a.flush()
    b.process_incoming()
    b.get_channel("s").insert_text(0, "y")  # carries b's refSeq itself
    b.flush()
    clock[0] = 5.0
    b.process_incoming()
    assert b.heartbeat_noops == 0


def test_deli_consolidates_client_noops_into_one_server_noop(monkeypatch):
    """Through the pipeline: the clients' noops move the MSN and take no
    sequence number; after 250 ms with nothing else sequenced ONE server
    noop carries it to every client, whose streams stay gapless."""
    from fluidframework_tpu.service import pipeline as P
    from fluidframework_tpu.service import sequencer as S

    wall = [100.0]
    monkeypatch.setattr(S.time, "time", lambda: wall[0])
    monkeypatch.setattr(P.time, "time", lambda: wall[0])
    clock = [0.0]
    svc = PipelineFluidService(n_partitions=1, device_backend=False)
    a, b = _pair(svc, clock)
    a.get_channel("s").insert_text(0, "x")
    a.flush()
    for rt in (a, b):
        rt.process_incoming()
    head = svc.doc_head("d")
    assert a.min_seq < head
    clock[0] = C.NOOP_TIME_FREQUENCY_S
    b.process_incoming()  # b's heartbeat: it has read the head
    a.send_noop()  # a says so at once, the immediate way: sequenced
    for rt in (a, b):
        rt.process_incoming()
    assert svc.doc_head("d") == head + 1
    assert svc.stats()["noops_sequenced"] == 1
    head += 1
    # Now both heartbeat past the new head: nothing is sequenced ...
    clock[0] += C.NOOP_TIME_FREQUENCY_S
    a.get_channel("s").insert_text(0, "z")
    a.flush()
    for rt in (a, b):
        rt.process_incoming()
    head += 1
    clock[0] += C.NOOP_TIME_FREQUENCY_S
    b.process_incoming()
    assert b.heartbeat_noops == 2
    svc.pump()
    assert svc.doc_head("d") == head and not svc.noops_due()
    # a has not said it read z's echo; its immediate noop did before. a's
    # own op carried its refSeq, so the MSN is b's to move: it has.
    wall[0] += S.NOOP_CONSOLIDATION_S - 0.01
    assert not svc.noops_due()
    # ... until the document has been quiet for the consolidation time.
    wall[0] += 0.02
    assert svc.noops_due()
    svc.pump()
    assert svc.doc_head("d") == head + 1
    assert svc.stats()["noops_sequenced"] == 2
    for rt in (a, b):
        rt.process_incoming()
        assert rt.ref_seq == head + 1
    assert a.min_seq == b.min_seq == svc._deli_doc("d").sequencer.min_seq
    assert not svc.noops_due()
    svc.pump()
    assert svc.doc_head("d") == head + 1  # one, not one a noop
