"""Deli's run pass (``lambdas.DeliPartitionLambda``: a read chunk's op
frames ticketed in one gather, one ticket loop on plain integers and one
stamp, each ``SeqFrame`` a view of the run's block) against the
per-record path it replaced on the serving path and keeps as its
fallback (``DocumentLambda.handler_batch`` over ``DeliDocLambda.handler``
→ ``_handle_frame`` → ``DocumentSequencer.ticket_frame``).

Twin deli runners read the same record stream with the clock pinned
(``timestamp`` and ``last_seen`` are wall time): every output record,
every sequencer's ``checkpoint_dict()``, the checkpoint store and the
flight recorder's events must be equal after every chunk, bit for bit.
The state machine itself (``ticket_uniform``) is held to n ``ticket()``
calls, the reference neither path shares.
"""

import dataclasses
import time

import numpy as np
import pytest

from fluidframework_tpu.models.shared_string import SharedString
from fluidframework_tpu.protocol.constants import F_REF, OP_WIDTH
from fluidframework_tpu.protocol.opframe import OpFrame, SeqFrame
from fluidframework_tpu.protocol.types import DocumentMessage, MessageType
from fluidframework_tpu.runtime.container import ContainerRuntime
from fluidframework_tpu.service import lambdas as L
from fluidframework_tpu.service.pipeline import PipelineFluidService
from fluidframework_tpu.service.queue import LogRecord, PartitionedLog
from fluidframework_tpu.service.sequencer import DocumentSequencer
from fluidframework_tpu.telemetry import journal, profiler

NOW = 1_700_000_000.25


@pytest.fixture(autouse=True)
def pinned_clock(monkeypatch):
    monkeypatch.setattr(time, "time", lambda: NOW)
    journal.reset()
    yield
    journal.reset()


# -- the twins ---------------------------------------------------------------


def per_record_lambda():
    return L.DocumentLambda(lambda doc_id, s: L.DeliDocLambda(doc_id, s))


class Deli:
    """One deli runner over its own log and checkpoint store, one
    partition, a checkpoint after every chunk."""

    def __init__(self, make_lambda):
        self.log = PartitionedLog(1)
        self.store = L.CheckpointStore()

        def factory(p, state):
            lam = make_lambda()
            lam.restore_docs(state)
            return lam

        self.runner = L.PartitionRunner(
            self.log, L.RAW_TOPIC, "deli", factory, self.store,
            checkpoint_every=1,
        )
        self.read_to = 0

    @property
    def lam(self):
        return self.runner._lambdas[0]

    def chunk(self, records):
        """Offer one read chunk; what it left behind, made comparable."""
        self.log.send_batch(L.RAW_TOPIC, [(k, dict(v)) for k, v in records])
        error = None
        try:
            self.runner.pump()
        except Exception as e:  # the failing record's own error
            error = (type(e).__name__, str(e))
        deltas = self.log.read(L.DELTAS_TOPIC, 0, self.read_to)
        self.read_to += len(deltas)
        return {
            "error": error,
            "deltas": [(r.key, plain(r.value)) for r in deltas],
            "signals": [
                (r.key, r.value)
                for r in self.log.read(L.SIGNALS_TOPIC, 0, 0)
            ],
            "sequencers": {
                d: lam.sequencer.checkpoint_dict()
                for d, lam in sorted(self.lam._docs.items())
            },
            "store": plain(self.store.load("deli", 0)),
            "offset": self.runner._offsets[0],
            "journal": [
                (e.kind, e.doc, e.seq, e.seq_hi, e.csn, e.csn_hi, e.client,
                 e.detail)
                for e in journal.JOURNAL.events()
            ],
        }


def plain(x):
    """A record value as nested plain data: arrays with their dtype and
    shape, frames and messages field by field."""
    if isinstance(x, np.ndarray):
        return ("ndarray", str(x.dtype), x.shape, x.tolist())
    if isinstance(x, SeqFrame):
        return ("SeqFrame", x.address, x.client_id, x.csn0, plain(x.rows),
                x.texts, x.timestamp)
    if dataclasses.is_dataclass(x):
        return (type(x).__name__, plain(dataclasses.asdict(x)))
    if isinstance(x, dict):
        return {k: plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return type(x)(plain(v) for v in x)
    return x


def both(chunks, between=None):
    """Run the chunks through the per-record twin and the run-pass twin;
    assert every chunk's transcript equal; return the run-pass twin and
    the transcripts."""
    scripts = []
    twins = []
    for make in (per_record_lambda, L.DeliPartitionLambda):
        journal.reset()
        deli = Deli(make)
        script = []
        for i, records in enumerate(chunks):
            if between is not None:
                between(i, deli)
            script.append(deli.chunk(records))
        scripts.append(script)
        twins.append(deli)
    for i, (a, b) in enumerate(zip(*scripts)):
        for what in a:
            assert a[what] == b[what], (i, what)
    return twins[1], scripts[1]


# -- records -----------------------------------------------------------------


def join(doc, mode="write"):
    return (doc, {"t": "join", "mode": mode, "token": doc})


def frame(doc, client, csn0, refs, traces=None):
    """An insert-heavy frame from ``client``: ``refs`` is one refSeq per
    op, or one for all of four ops."""
    if isinstance(refs, int):
        refs = [refs] * 4
    n = len(refs)
    kinds = ["ins" if i % 3 else "rem" for i in range(n)]
    kinds[0] = "ins"
    f = OpFrame.build(
        "s", kinds, list(range(n)), [i + 1 for i in range(n)],
        [f"{doc}.{csn0 + i}" if k == "ins" else None
         for i, k in enumerate(kinds)],
        csn0=csn0, ref=refs[0],
    )
    f.rows[:, F_REF] = np.asarray(refs, np.int32)
    rec = {"t": "opframe", "client": client, "frame": f}
    if traces is not None:
        rec["traces"] = traces
    return (doc, rec)


def op(doc, client, csn, ref):
    msg = DocumentMessage(csn, ref, MessageType.OPERATION, {"x": csn})
    return (doc, {"t": "op", "client": client, "msg": msg})


def n_frames(chunks):
    return sum(v["t"] == "opframe" for c in chunks for _, v in c)


def seqframes(script):
    return [v for chunk in script for _, v in chunk["deltas"]
            if v["t"] == "seqframe"]


def nacks(script):
    return [v for chunk in script for _, v in chunk["deltas"]
            if v["t"] == "nack"]


# -- the steady-state stream ---------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 4, 64])
def test_all_fast_chunks_of_k_frames(k):
    docs = [f"d{i}" for i in range(k)]
    chunks = [[join(d) for d in docs]]
    for turn in range(3):
        chunks.append([frame(d, 0, 1 + 4 * turn, 1) for d in docs])
    deli, script = both(chunks)
    # A frame with no frame beside it takes the per-frame body.
    ways = (deli.lam.frames_batched, deli.lam.frames_single)
    assert ways == ((0, 3) if k == 1 else (3 * k, 0))
    assert len(seqframes(script)) == 3 * k and not nacks(script)


@pytest.mark.parametrize("repeats", [2, 3])
def test_frames_of_one_document_in_one_run_see_each_other(repeats):
    """The ticket loop is sequential: the second frame of a document in
    a run is ticketed against what the first left."""
    run = [frame("a", 0, 1, 1), frame("b", 0, 1, 1)]
    for r in range(1, repeats):
        run.append(frame("a", 0, 1 + 4 * r, 1 + r))  # its ref advances
    run.append(frame("b", 0, 5, 1))
    deli, script = both([[join("a"), join("b")], run])
    assert deli.lam.frames_single == 0
    firsts = [f["frame"][4][3][0][3] for f in seqframes(script)
              if f["frame"][5][0].startswith("a.")]  # F_SEQ of a's frames
    assert firsts == [2, 6, 10][:repeats]


# -- what leaves the fast path, at its place in the run -------------------------


def _slow_cases():
    ok = lambda d, csn0=1: frame(d, 0, csn0, 1)
    lead = [join("a"), join("b"), join("c")]
    first = [ok("a"), ok("b"), ok("c")]  # csn 1..4 each, seq 2..5
    return {
        "duplicate-prefix": (
            [lead, first, [ok("a", 5), frame("b", 0, 3, 1), ok("c", 5)]],
            1, 0),
        "whole-frame-duplicate": (
            [lead, first, [ok("a", 5), frame("b", 0, 1, 1), ok("c", 5),
                           frame("b", 0, 5, 1)]],
            1, 0),
        "csn-gap": (
            [lead, first, [ok("a", 5), frame("b", 0, 7, 1), ok("c", 5),
                           frame("b", 0, 5, 1)]],
            1, 1),
        "refs-differ": (
            [lead, first, [ok("a", 5), frame("b", 0, 5, [1, 2, 2, 3]),
                           ok("c", 5), frame("b", 0, 9, 3)]],
            1, 0),
        "stale-ref-mid-frame": (
            # b's MSN climbs to 5 with its second frame; the third has a
            # ref under it at its third op: two ticketed, a trailing nack.
            [lead, first, [frame("b", 0, 5, 5), ok("a", 5),
                           frame("b", 0, 9, [5, 5, 3, 5]), ok("c", 5),
                           frame("b", 0, 11, 5)]],
            1, 1),
        "stale-ref-whole-frame": (
            [lead, first, [frame("b", 0, 5, 5), frame("b", 0, 9, 2),
                           ok("a", 5), frame("b", 0, 9, 6)]],
            1, 1),
        "unknown-client": (
            [lead, first, [ok("a", 5), frame("b", 7, 1, 1), ok("c", 5)]],
            1, 1),
        "read-only-client": (
            [lead + [join("b", "read")], first,
             [ok("a", 5), frame("b", 1, 1, 1), ok("c", 5)]],
            1, 1),
    }


@pytest.mark.parametrize("case", sorted(_slow_cases()))
def test_a_frame_off_the_fast_path_takes_the_per_record_path(case):
    chunks, single, n_nacks = _slow_cases()[case]
    deli, script = both(chunks)
    assert deli.lam.frames_single == single
    assert deli.lam.frames_batched == n_frames(chunks) - single
    assert len(nacks(script)) == n_nacks
    kinds = {e[0] for e in script[-1]["journal"]}
    assert "frame.ticket" in kinds
    # A trailing nack rides the partial ticket's record, as it always did.
    assert ("frame.nack" in kinds) == (case not in (
        "duplicate-prefix", "refs-differ", "stale-ref-mid-frame"))


def test_a_throttled_document_nacks_every_frame_and_consumes_no_csn():
    """``_nack_all`` (the NackMessages control): the document's frames
    are nacked with ``retry_after_s``, the neighbours' ticketed, and the
    same csn tickets once the control lifts."""
    paused = {"type": "nackMessages", "enable": True, "code": 429,
              "message": "paused"}

    def between(i, deli):
        if i == 2:
            deli.lam.doc("b").sequencer.control(paused)
        if i == 3:
            deli.lam.doc("b").sequencer.control(
                {"type": "nackMessages", "enable": False})

    ok = lambda d, csn0: frame(d, 0, csn0, 1)
    chunks = [[join("a"), join("b")], [ok("a", 1), ok("b", 1)],
              [ok("a", 5), ok("b", 5), ok("a", 9), ok("b", 9)],
              [ok("b", 5), ok("a", 13)]]
    deli, script = both(chunks, between)
    got = nacks(script)
    assert len(got) == 2 and deli.lam.frames_single == 2
    assert all(n["nack"][1]["retry_after_s"] == 1.0 for n in got)
    assert [n["nack"][1]["client_sequence_number"] for n in got] == [5, 9]
    assert deli.lam.doc("b").sequencer.clients[0].client_seq == 8


def test_other_records_between_frames_end_the_run_and_keep_their_place():
    chunks = [
        [join("a"), join("b")],
        [frame("a", 0, 1, 1), frame("b", 0, 1, 1), join("a"),
         frame("a", 1, 1, 3), op("b", 0, 5, 1), frame("b", 0, 6, 1),
         ("a", {"t": "signal", "client": 0, "content": "hi"}),
         frame("a", 0, 5, 3), ("a", {"t": "leave", "client": 1}),
         frame("a", 0, 9, 3), frame("b", 0, 10, 1)],
    ]
    deli, script = both(chunks)
    # Runs of two at either end; the three frames between stand alone.
    assert deli.lam.frames_batched == 4 and deli.lam.frames_single == 3
    order = [(k, v["t"]) for k, v in script[1]["deltas"]]
    assert order == [
        ("a", "seqframe"), ("b", "seqframe"), ("a", "seq"),
        ("a", "seqframe"), ("b", "seq"), ("b", "seqframe"),
        ("a", "seqframe"), ("a", "seq"), ("a", "seqframe"),
        ("b", "seqframe"),
    ]
    assert len(script[1]["signals"]) == 1


def test_a_sampled_frame_keeps_its_trace_stamps():
    """A frame that carries ``traces`` is stamped alfred/end, deli/start,
    deli/end and the SAME list rides its sequenced record."""
    lists = []

    def run(make):
        journal.reset()
        deli = Deli(make)
        traces = [{"service": "alfred", "action": "start", "timestamp": NOW}]
        lists.append(traces)
        deli.chunk([join("a"), join("b")])
        return deli, deli.chunk([
            frame("a", 0, 1, 1), frame("b", 0, 1, 1, traces=traces),
            frame("a", 0, 5, 1),
        ])

    (_, a), (deli, b) = run(per_record_lambda), run(L.DeliPartitionLambda)
    assert a == b
    assert lists[0] == lists[1] and [
        (t["service"], t["action"]) for t in lists[1]
    ] == [("alfred", "start"), ("alfred", "end"), ("deli", "start"),
          ("deli", "end")]
    rec = deli.log.read(L.DELTAS_TOPIC, 0, 0)[-2].value
    assert rec["traces"] is lists[1]
    assert deli.lam.frames_single == 1 and deli.lam.frames_batched == 2


def test_a_second_writers_ref_seq_sets_the_floor():
    """MSN = max(floor, min(r0, the OTHER clients' refSeqs)): the second
    writer parks at 2 and holds the first writer's MSN there; when it
    moves, the floor follows, and never regresses."""
    chunks = [
        [join("a"), join("a"), join("b")],
        [frame("a", 1, 1, 2), frame("b", 0, 1, 1)],  # seq 3..6
        [frame("a", 0, 1, 6), frame("a", 0, 5, 10), frame("b", 0, 5, 5)],
        [frame("a", 1, 5, 12), frame("a", 0, 9, 14)],
        [frame("a", 1, 9, 9), frame("a", 0, 13, 18)],  # 9 < MSN 12: nack
    ]
    deli, script = both(chunks)
    msns = [f["frame"][4][3][0][9] for f in seqframes(script)
            if f["frame"][2] == 0 and len(f["frame"][5]) and
            f["frame"][5][0].startswith("a.")]
    assert msns == [2, 2, 12, 12]
    assert len(nacks(script)) == 1 and deli.lam.frames_single == 1


@pytest.mark.parametrize("seed", range(8))
def test_a_seeded_stream_with_every_kind_of_fault(seed):
    """Frames of 1..6 ops over a few documents with two writers each,
    chunks of 1..40 records; a fifth of the frames carry a fault (a
    replayed or skipped csn, a stale or uneven ref, an unknown client)."""
    rng = np.random.default_rng(4200 + seed)
    docs = [f"d{i}" for i in range(5)]
    chunks = [[join(d) for d in docs] + [join(d) for d in docs]]
    csn = {(d, c): 0 for d in docs for c in (0, 1)}
    head = {d: 2 for d in docs}  # an upper bound of no consequence
    for _ in range(12):
        records = []
        for _ in range(int(rng.integers(1, 41))):
            d = docs[int(rng.integers(len(docs)))]
            c = int(rng.integers(2))
            n = int(rng.integers(1, 7))
            csn0 = csn[d, c] + 1
            ref = int(rng.integers(max(1, head[d] - 6), head[d] + 1))
            refs = [ref] * n
            fault = rng.random()
            if fault < 0.04:
                csn0 = max(1, csn0 - int(rng.integers(1, 4)))
            elif fault < 0.08:
                csn0 += 2
            elif fault < 0.12:
                refs[int(rng.integers(n))] = max(0, ref - 5)
            elif fault < 0.16:
                refs = [int(rng.integers(ref, ref + 3)) for _ in range(n)]
            elif fault < 0.18:
                c = 9
            elif fault < 0.22:
                records.append(op(d, c, csn0, ref))
                csn[d, c] = csn0
                head[d] += 1
                continue
            records.append(frame(d, c, csn0, refs))
            if c != 9 and csn0 <= csn[d, c] + 1:
                csn[d, c] = max(csn[d, c], csn0 + n - 1)
            head[d] += n
        chunks.append(records)
    deli, script = both(chunks)
    assert (deli.lam.frames_batched + deli.lam.frames_single
            == n_frames(chunks))
    assert deli.lam.frames_batched > deli.lam.frames_single > 0
    assert seqframes(script) and nacks(script)


# -- the state machine against n ticket() calls ---------------------------------


@pytest.mark.parametrize("n,r0,other_ref", [
    (1, 2, None), (4, 2, None), (4, 5, 3), (3, 3, 7), (64, 6, 6),
])
def test_ticket_uniform_stamps_what_n_tickets_stamp(n, r0, other_ref):
    per_op, fast = DocumentSequencer("d"), DocumentSequencer("d")
    for s in (per_op, fast):
        s.join()
        s.join()
        for csn in range(1, 6):  # seq 3..7, so refs up to 7 are sound
            s.ticket(1, DocumentMessage(csn, 2, MessageType.OPERATION))
        if other_ref is None:
            s.leave(1)
        else:
            s.clients[1].ref_seq = other_ref
    msgs = [
        per_op.ticket(0, DocumentMessage(c, r0, MessageType.OPERATION))
        for c in range(1, n + 1)
    ]
    seq0, msn = fast.ticket_uniform(0, 1, n, r0, NOW)
    assert [m.sequence_number for m in msgs] == list(range(seq0, seq0 + n))
    assert {m.minimum_sequence_number for m in msgs} == {msn}
    assert fast.checkpoint_dict() == per_op.checkpoint_dict()


@pytest.mark.parametrize("what", [
    "unknown", "read-only", "paused", "duplicate", "gap", "stale",
])
def test_ticket_uniform_changes_nothing_where_it_declines(what):
    s = DocumentSequencer("d")
    s.join()
    s.join(mode="read")
    s.clients[1].ref_seq = 100  # the reader does not hold the MSN down
    s.ticket_uniform(0, 1, 4, 2, NOW)
    s.ticket_uniform(0, 5, 4, 6, NOW)  # MSN 6
    if what == "paused":
        s.control({"type": "nackMessages", "enable": True})
    before = s.checkpoint_dict()
    client, csn0, r0 = {
        "unknown": (5, 1, 6), "read-only": (1, 1, 6), "paused": (0, 9, 6),
        "duplicate": (0, 5, 6), "gap": (0, 10, 6), "stale": (0, 9, 5),
    }[what]
    assert s.ticket_uniform(client, csn0, 4, r0, NOW) is None
    assert s.checkpoint_dict() == before


# -- the crash contract ---------------------------------------------------------


class _Boom:
    """A frame whose rows cannot be gathered."""
    rows = None


@pytest.mark.parametrize("broken", ["frame", "client", "new-document"])
def test_a_record_that_raises_mid_chunk_leaves_the_prefix_stamped(
    broken, monkeypatch
):
    """``BatchHandlerError`` carries the completed prefix's outputs,
    stamped, and ``n_ok``; the runner emits them and moves the offset to
    the failing record; no sequencer past it moved."""
    bad = frame("c", 0, 1, 1)
    if broken == "frame":  # the gather cannot read it
        bad[1]["frame"] = _Boom()
    elif broken == "client":  # the ticket loop meets it
        del bad[1]["client"]
    chunks = [[join(d) for d in "abcd"],
              [frame("a", 0, 1, 1), frame("b", 0, 1, 1), bad,
               frame("d", 0, 1, 1), frame("a", 0, 5, 1)]]
    if broken == "new-document":  # the factory fails for a document
        chunks[0].pop(2)
        real = L.DeliDocLambda.__init__

        def init(self, doc_id, state=None, *rest):
            if doc_id == "c":
                raise RuntimeError("no such document")
            real(self, doc_id, state, *rest)

        monkeypatch.setattr(L.DeliDocLambda, "__init__", init)
    deli, script = both(chunks)
    last = script[-1]
    assert last["error"] is not None and last["offset"] == len(chunks[0]) + 2
    got = seqframes(script)
    assert [f["frame"][4][3][0][3] for f in got] == [2, 2]  # F_SEQ stamped
    assert [f["frame"][4][3][0][9] for f in got] == [1, 1]  # F_MSN stamped
    assert deli.lam.doc("d").sequencer.seq == 1  # past the failure: untouched
    assert deli.lam.doc("a").sequencer.clients[0].client_seq == 4
    # The raw exception, as the handler's own loop gives it.
    lam = L.DeliPartitionLambda()
    for d in "abd":
        lam.handler(d, join(d)[1])
    with pytest.raises(L.BatchHandlerError) as err:
        lam.handler_batch(
            [LogRecord(i, k, v) for i, (k, v) in enumerate(chunks[1])])
    assert err.value.n_ok == 2 and len(err.value.outputs) == 2


def test_an_empty_frame_raises_what_the_per_record_path_raises():
    empty = ("b", {"t": "opframe", "client": 0, "frame": OpFrame(
        "s", np.zeros((0, OP_WIDTH), np.int32), ())})
    _, script = both([[join("a"), join("b")],
                      [frame("a", 0, 1, 1), empty, frame("a", 0, 5, 1)]])
    assert script[-1]["error"][0] == "IndexError"
    assert len(seqframes(script)) == 1


# -- frames as views of the run's block ------------------------------------------


def test_frames_are_views_that_later_chunks_leave_alone():
    deli = Deli(L.DeliPartitionLambda)
    deli.chunk([join("a"), join("b"), join("c")])
    deli.chunk([frame(d, 0, 1, 1) for d in "abc"])
    frames = [r.value["frame"] for r in deli.log.read(L.DELTAS_TOPIC, 0, 3)]
    base = frames[0].rows.base
    assert base is not None and all(f.rows.base is base for f in frames)
    assert base.dtype == np.int32 and base.shape == (12, OP_WIDTH)
    kept = [f.rows.copy() for f in frames]
    deli.chunk([frame(d, 0, 5, 1) for d in "abc"])
    deli.chunk([frame("a", 0, 9, [1, 2, 3, 4]), frame("b", 0, 2, 1)])
    for f, rows in zip(frames, kept):
        np.testing.assert_array_equal(f.rows, rows)
    later = deli.log.read(L.DELTAS_TOPIC, 0, 6)[0].value["frame"]
    assert later.rows.base is not base


def test_no_consumer_writes_into_a_sequenced_frames_rows(monkeypatch):
    """Scriptorium, the broadcaster, the device stage, catch-up reads and
    per-op expansion only read and slice ``SeqFrame.rows``: with every
    view read-only the whole served path runs and converges."""
    real = L.DeliPartitionLambda._ticket_run
    frozen = []

    def ticket_run(self, run):
        out = real(self, run)
        for _, _, value in out:
            if value["t"] == "seqframe":
                value["frame"].rows.flags.writeable = False
                frozen.append(value["frame"])
        return out

    monkeypatch.setattr(L.DeliPartitionLambda, "_ticket_run", ticket_run)
    svc = PipelineFluidService(n_partitions=2)
    reader = ContainerRuntime(svc, "doc", channels=(SharedString("s"),))
    conns = {d: svc.connect(d) for d in ("doc", "other")}
    mint = lambda conn, i: conn.conn_no * (1 << 14) + i
    for turn in range(3):
        svc.submit_frames_bulk([
            (d, c.client_id, OpFrame.build(
                "s", ["ins", "ins"], [0, 1],
                [mint(c, 2 * turn + 1), mint(c, 2 * turn + 2)],
                ["ab", "cd"], csn0=1 + 2 * turn, ref=svc.doc_head(d)))
            for d, c in conns.items()
        ])
    svc.flush_device()
    assert len(frozen) == 6 and not any(
        f.rows.flags.writeable for f in frozen)
    while reader.process_incoming():
        pass
    text = svc.device_text("doc", "s")
    assert len(text) == 12 and reader.get_channel("s").get_text() == text
    late = svc.connect("doc")
    assert sum(getattr(m, "type", None) == MessageType.OPERATION
               for m in late.inbox) == 6
    assert frozen[0].message(1).sequence_number == frozen[0].first_seq + 1


# -- the counters and the armed ticket lane ---------------------------------------


def test_the_service_counts_frames_each_way():
    svc = PipelineFluidService(n_partitions=4, device_backend=False)
    frames_of = lambda st: {k: st[k] for k in (
        "deli_frames_batched", "deli_frames_single")}
    assert set(svc.stats().values()) == {0}
    assert frames_of(svc.stats()) == {
        "deli_frames_batched": 0, "deli_frames_single": 0}
    conns = {f"d{i}": svc.connect(f"d{i}") for i in range(12)}
    build = lambda d, csn0, k=2: OpFrame.build(
        "s", ["ins"] * k, [0] * k, list(range(csn0, csn0 + k)), ["x"] * k,
        csn0=csn0, ref=svc.doc_head(d))
    svc.submit_frames_bulk(
        [(d, c.client_id, build(d, 1)) for d, c in conns.items()])
    svc.submit_frames_bulk(
        [(d, c.client_id, build(d, 3)) for d, c in conns.items()]
        + [("d0", conns["d0"].client_id, build("d0", 1))])  # a replay
    conns["d1"].submit_frame(build("d1", 5))  # the websocket's way in
    assert frames_of(svc.stats()) == {  # the replay and the lone frame
        "deli_frames_batched": 24, "deli_frames_single": 2}
    # The ticket loop's own counts: 12 joins and 25 frames ticketed (the
    # replay was not), one writer a document, no noop, no refusal.
    st = svc.stats()
    assert (st["msn_lag_count"], st["writer_slots_peak"]) == (37, 1)
    assert (st["noops_received"], st["noops_sequenced"],
            st["join_nacks_slots"]) == (0, 0, 0)
    svc.crash_deli()
    assert svc.stats()["deli_frames_batched"] == 0


def test_the_armed_ticket_lane_records_once_per_run():
    deli = Deli(L.DeliPartitionLambda)
    deli.chunk([join(d) for d in "abc"])
    assert profiler.arm(5000)
    try:
        deli.chunk([frame(d, 0, 1, 1) for d in "abc"])
        tickets = [iv for iv in profiler.PROFILER.intervals()
                   if iv.lane == "ticket"]
    finally:
        profiler.disarm()
    assert len(tickets) == 1 and tickets[0].rows == 12
