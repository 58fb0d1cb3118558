"""Partitioned stream-lambda framework + the four service lambdas.

Reference: ``server/routerlicious`` —
- ``lambdas-driver``: ``KafkaRunner`` -> ``PartitionManager`` (one ordered
  queue per partition with a ``CheckpointManager``,
  kafka-service/partitionManager.ts:25, checkpointManager.ts:10) ->
  ``DocumentLambda``/``DocumentPartition`` demultiplexing a partition into
  per-document lambdas (document-router/*.ts).
- ``services-core/src/lambdas.ts``: ``IPartitionLambda`` (:72) /
  ``IPartitionLambdaFactory`` (:88) — the plugin surface.
- ``lambdas``: **deli** (sequencer, deli/lambda.ts:379), **scribe**
  (summary validation + ack, scribe/lambda.ts:106), **scriptorium**
  (op persistence, scriptorium/lambda.ts:32), **broadcaster**
  (fan-out to client rooms, broadcaster/lambda.ts:62).

Execution model: lambdas are stateless replayable consumers; durable
state = checkpoints (offset + lambda state, reference ``IDeliState``
document.ts:56) written on a max-messages heuristic. Delivery is
at-least-once: a crash between produce and commit replays input, and the
replay deterministically re-produces the *same* sequenced messages, which
every downstream consumer absorbs idempotently (scriptorium upserts by
seq, broadcaster drops seqs already delivered to a connection).
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from fluidframework_tpu.protocol.constants import (
    F_CLIENT,
    F_MSN,
    F_REF,
    F_SEQ,
    F_TYPE,
    OP_INSERT,
)
from fluidframework_tpu.protocol.opframe import SeqFrame
from fluidframework_tpu.protocol.types import (
    DocumentMessage,
    MessageType,
    NackMessage,
    SequencedDocumentMessage,
)
from fluidframework_tpu.service import retry
from fluidframework_tpu.service.queue import PartitionedLog
from fluidframework_tpu.telemetry import (
    LumberEventName,
    Lumberjack,
    journal,
    metrics,
    profiler,
    tracing,
)
from fluidframework_tpu.testing.faults import inject_fault
from fluidframework_tpu.service.sequencer import (
    DocumentSequencer,
    FrameTicket,
    SequencerCheckpoint,
    SequencerStats,
)

RAW_TOPIC = "rawdeltas"
DELTAS_TOPIC = "deltas"
SIGNALS_TOPIC = "signals"

# Cached read-only aranges: frame stamping runs per frame on the serving
# path, and np.arange per call is measurable at 10k+ frames/round.
_ARANGES: Dict[int, np.ndarray] = {}


def _arange(n: int) -> np.ndarray:
    a = _ARANGES.get(n)
    if a is None:
        a = np.arange(n, dtype=np.int32)
        a.setflags(write=False)
        _ARANGES[n] = a
    return a


# ---------------------------------------------------------------------------
# Framework


class PartitionLambda:
    """IPartitionLambda: handle one record, emit (topic, key, value) tuples;
    expose/restore durable state for checkpoints.

    ``state()`` MUST return an independent snapshot (no references to
    live mutable structures): the checkpoint store keeps it as-is — a
    defensive deepcopy per checkpoint was the single largest cost on the
    serving pipeline at fleet scale. ``restore`` likewise must not
    mutate the state object it is given.

    ``wants``: optional frozenset of record types (``value["t"]``) the
    lambda acts on. The runner drops non-matching records BEFORE the
    handler call — a consumer that would return ``[]`` anyway must not
    pay Python dispatch (or per-doc demux and dirty-marking) per record
    on the serving path. None = every record (also required for topics
    whose records carry no ``t`` key)."""

    wants: Optional[frozenset] = None

    def handler(self, key: str, value: Any) -> List[Tuple[str, str, Any]]:
        raise NotImplementedError

    def state(self) -> Any:
        return None

    @classmethod
    def restore(cls, state: Any) -> "PartitionLambda":
        raise NotImplementedError


class BatchHandlerError(Exception):
    """Raised by ``handler_batch`` when a record mid-chunk fails: carries
    the outputs already produced and how many records completed, so the
    runner can emit them and commit the offset up to the failure —
    EXACTLY the per-record loop's crash semantics. Without this, outputs
    of records the lambdas already mutated state for (e.g. deli tickets)
    would be discarded while their replay dedup-drops — lost ops."""

    def __init__(self, outputs, n_ok: int, cause: BaseException):
        super().__init__(f"batch handler failed after {n_ok} records")
        self.outputs = outputs
        self.n_ok = n_ok
        self.cause = cause


class CheckpointStore:
    """Durable (in this harness: in-memory, survives lambda restarts)
    checkpoint documents — the Mongo IDeliState/IScribe analog.

    ``merge=True`` saves are INCREMENTAL: the given state is a partial
    per-document dict merged into the stored one (the reference
    checkpoints dirty document state, not the whole partition —
    ``deli/checkpointManager.ts``; serializing every doc every
    checkpoint is quadratic at fleet scale).

    States are stored WITHOUT a defensive copy: ``PartitionLambda.
    state()`` contracts to return an independent snapshot, and
    ``restore`` to treat its input as read-only (deepcopying every
    checkpoint was the dominant host cost of the serving pipeline)."""

    def __init__(self) -> None:
        self._data: Dict[Tuple[str, int], dict] = {}

    def save(self, group: str, partition: int, offset: int, state: Any,
             merge: bool = False) -> None:
        if merge:
            ent = self._data.setdefault(
                (group, partition), {"offset": 0, "state": {}}
            )
            ent["offset"] = offset
            ent["state"].update(state)
            return
        self._data[(group, partition)] = {"offset": offset, "state": state}

    def load(self, group: str, partition: int) -> Optional[dict]:
        ent = self._data.get((group, partition))
        return {"offset": ent["offset"], "state": ent["state"]} if ent else None


class DocumentLambda(PartitionLambda):
    """Demultiplexes one partition into per-document lambdas (the
    document-router): every record's key is its document id; each document
    gets its own lambda instance and strictly-ordered substream."""

    # state() returns only documents touched since the last call; the
    # checkpoint store merges them (dirty-doc checkpointing — without it
    # every checkpoint serializes the whole partition's documents, which
    # is quadratic in fleet size on the serving path).
    incremental_state = True

    def __init__(
        self,
        per_doc_factory: Callable[[str, Any], PartitionLambda],
        wants: Optional[frozenset] = None,
    ):
        self._factory = per_doc_factory
        self._docs: Dict[str, PartitionLambda] = {}
        self._dirty: set = set()
        self.wants = wants

    def doc(self, doc_id: str) -> PartitionLambda:
        if doc_id not in self._docs:
            self._docs[doc_id] = self._factory(doc_id, None)
        return self._docs[doc_id]

    def handler(self, key: str, value: Any) -> List[Tuple[str, str, Any]]:
        self._dirty.add(key)
        return self.doc(key).handler(key, value)

    def handler_batch(self, recs) -> List[Tuple[str, str, Any]]:
        """One read chunk through the router in a single call: the wants
        filter, dirty-marking, and demux run as one tight loop instead of
        per-record dispatch through the runner (documentLambda.ts routes
        per message; at 10k+ frames/round the layers ARE the cost).
        A failing record (or a document the factory cannot make) raises
        :class:`BatchHandlerError` carrying the completed prefix's
        outputs, preserving the per-record loop's output-before-commit
        crash contract. This is the serving path of scribe, foreman and
        the device stage, and of deli's records other than op frames;
        for a chunk's op frames :class:`DeliPartitionLambda` overrides
        it, and this loop over ``DeliDocLambda.handler`` is that pass's
        reference."""
        out: List[Tuple[str, str, Any]] = []
        docs = self._docs
        dirty = self._dirty
        wants = self.wants
        for i, rec in enumerate(recs):
            value = rec.value
            if wants is not None and value.get("t") not in wants:
                continue
            key = rec.key
            try:
                lam = docs.get(key)
                if lam is None:
                    lam = docs[key] = self._factory(key, None)
                dirty.add(key)
                res = lam.handler(key, value)
            except Exception as e:
                raise BatchHandlerError(out, i, e) from e
            if res:
                out.extend(res)
        return out

    def state(self) -> Any:
        dirty, self._dirty = self._dirty, set()
        return {
            doc_id: self._docs[doc_id].state()
            for doc_id in dirty
            if doc_id in self._docs
        }

    def restore_docs(self, state: Dict[str, Any]) -> None:
        for doc_id, doc_state in (state or {}).items():
            self._docs[doc_id] = self._factory(doc_id, doc_state)


class PartitionRunner:
    """One consumer group over one topic: per-partition ordered pump with
    offset commit + state checkpoint every ``checkpoint_every`` messages
    (KafkaRunner + PartitionManager + CheckpointManager collapsed for the
    in-proc synchronous harness)."""

    def __init__(
        self,
        log: PartitionedLog,
        topic: str,
        group: str,
        factory: Callable[[int, Optional[Any]], PartitionLambda],
        checkpoints: Optional[CheckpointStore] = None,
        checkpoint_every: int = 10,
    ):
        self.log = log
        self.topic = topic
        self.group = group
        self.checkpoints = checkpoints or CheckpointStore()
        self.checkpoint_every = checkpoint_every
        self._lambdas: Dict[int, PartitionLambda] = {}
        self._offsets: Dict[int, int] = {}
        self._since_checkpoint: Dict[int, int] = {}
        for p in range(log.n_partitions):
            saved = self.checkpoints.load(group, p)
            self._lambdas[p] = factory(p, saved["state"] if saved else None)
            self._offsets[p] = saved["offset"] if saved else 0
            self._since_checkpoint[p] = 0

    def pump(self) -> int:
        """Drain every partition's backlog; returns records processed.

        Lambdas exposing ``handler_batch`` consume each read chunk in one
        call (outputs flushed with one boxcar append per chunk); others
        run per-record with the ``wants`` type filter applied here.
        Offsets advance per chunk — output-before-commit order is
        preserved, so a crash replays at most one chunk (at-least-once,
        same contract as the per-record loop, coarser granularity)."""
        n = 0
        for p in range(self.log.n_partitions):
            lam = self._lambdas[p]
            batch = getattr(lam, "handler_batch", None)
            wants = getattr(lam, "wants", None)
            while True:
                recs = self.log.read(
                    self.topic, p, self._offsets[p], limit=256
                )
                if not recs:
                    break
                if batch is not None:
                    try:
                        outs = batch(recs)
                    except BatchHandlerError as be:
                        # Commit the completed prefix exactly as the
                        # per-record loop would have, then surface the
                        # failing record's error.
                        if be.outputs:
                            self._emit(be.outputs)
                        if be.n_ok:
                            self._offsets[p] = recs[be.n_ok - 1].offset + 1
                            self._since_checkpoint[p] += be.n_ok
                        raise be.cause
                    if outs:
                        self._emit(outs)
                else:
                    for rec in recs:
                        value = rec.value
                        if wants is not None and value.get("t") not in wants:
                            continue
                        outs = lam.handler(rec.key, value)
                        if outs:
                            self._emit(outs)
                self._offsets[p] = recs[-1].offset + 1
                n += len(recs)
                self._since_checkpoint[p] += len(recs)
                if self._since_checkpoint[p] >= self.checkpoint_every:
                    self.checkpoint(p)
        return n

    def _emit(self, outs: List[Tuple[str, str, Any]]) -> None:
        # Produce failures (the ``queue.send`` boundary) retry with
        # backoff: the in-proc log's boxcar append is atomic w.r.t. the
        # injection boundary, so a retried batch never half-lands; an
        # exhausted retry raises BEFORE the offset advances — the chunk
        # replays and deli's deterministic re-production plus downstream
        # dedup absorb it (the documented at-least-once model).
        by_topic: Dict[str, List[Tuple[str, Any]]] = {}
        for out_topic, out_key, out_value in outs:
            by_topic.setdefault(out_topic, []).append((out_key, out_value))
        for topic, entries in by_topic.items():
            send_batch = getattr(self.log, "send_batch", None)
            if send_batch is not None:
                retry.call_with_retry("queue.send", send_batch, topic, entries)
            else:  # minimal log impls (native binding) only expose send
                for key, value in entries:
                    retry.call_with_retry(
                        "queue.send", self.log.send, topic, key, value
                    )

    def checkpoint(self, partition: Optional[int] = None) -> None:
        parts = range(self.log.n_partitions) if partition is None else [partition]
        for p in parts:
            lam = self._lambdas[p]
            self.checkpoints.save(
                self.group, p, self._offsets[p], lam.state(),
                merge=getattr(lam, "incremental_state", False),
            )
            self.log.commit(self.group, self.topic, p, self._offsets[p])
            self._since_checkpoint[p] = 0


# ---------------------------------------------------------------------------
# Deli — the sequencer lambda


class DeliDocLambda(PartitionLambda):
    """Per-document deli: wraps the pure DocumentSequencer ticket loop and
    lowers raw control/op records to sequenced messages on ``deltas`` (and
    signal numbers on ``signals``)."""

    def __init__(self, doc_id: str, state: Optional[dict] = None,
                 partition: Optional["DeliPartitionLambda"] = None):
        self.doc_id = doc_id
        # The partition's shared counts and its set of documents whose
        # client noops wait for a carrier (None: a deli that stands alone).
        self._partition = partition
        checkpoint = None
        self._signal_counter = 0
        # Monotone dedupe floor per service-signal group: an upstream
        # service lambda (foreman) replaying under at-least-once delivery
        # re-emits signals it already sent; each carries a ``basis`` (the
        # sequenced message that caused it), and deli drops any at or
        # below the group's floor — exactly-once effect without the
        # emitter needing its own durable send state.
        self._signal_basis: Dict[str, int] = {}
        if state is not None:
            checkpoint = SequencerCheckpoint(**state["sequencer"])
            self._signal_counter = state["signals"]
            self._signal_basis = dict(state.get("signal_basis", {}))
        self.sequencer = DocumentSequencer(
            doc_id, checkpoint,
            partition.stats if partition is not None else None,
        )

    def state(self) -> dict:
        return {
            "sequencer": self.sequencer.checkpoint_dict(),
            "signals": self._signal_counter,
            "signal_basis": dict(self._signal_basis),
        }

    def handler(self, key: str, value: dict) -> List[Tuple[str, str, Any]]:
        t = value["t"]
        if t == "opframe":
            # No per-record metric object — a Lumber allocation per
            # frame was measurable serving-path overhead; sampled op
            # tracing (alfred's 1-in-N stamp) remains the observability
            # story for the data plane, metrics cover the control plane.
            return self._handle_frame(key, value)
        if t == "op":
            return self._handle(key, value, t)
        metric = Lumberjack.new_metric(
            LumberEventName.DeliHandler,
            {"tenantId": "local", "documentId": self.doc_id, "recordType": t},
        )
        try:
            out = self._handle(key, value, t)
        except Exception as e:  # pragma: no cover - defensive
            metric.error("deli handler failed", e)
            raise
        metric.success()
        return out

    def _handle(self, key: str, value: dict, t: str) -> List[Tuple[str, str, Any]]:
        out: List[Tuple[str, str, Any]] = []
        if t == "join":
            res = self.sequencer.join(value.get("mode", "write"))
            if isinstance(res, NackMessage):
                out.append(
                    (DELTAS_TOPIC, key, {"t": "nack", "token": value.get("token"),
                                         "nack": res})
                )
            else:
                # The reply token rides the sequenced join so the front
                # door can match slot assignments to connect calls.
                res.contents = {**res.contents, "token": value.get("token")}
                out.append((DELTAS_TOPIC, key, {"t": "seq", "msg": res}))
        elif t == "leave":
            res = self.sequencer.leave(value["client"])
            if res is not None:
                out.append((DELTAS_TOPIC, key, {"t": "seq", "msg": res}))
        elif t == "op":
            res = self.sequencer.ticket(value["client"], value["msg"])
            if isinstance(res, NackMessage):
                out.append(
                    (DELTAS_TOPIC, key,
                     {"t": "nack", "client": value["client"], "nack": res})
                )
            elif res is not None:
                out.append((DELTAS_TOPIC, key, {"t": "seq", "msg": res}))
            # duplicates (None) are dropped silently (checkOrder); so is a
            # client's collab-window noop, which took no sequence number:
            # if it moved the MSN, the document waits for a carrier
            # (``DeliPartitionLambda.noops_due``).
            elif (
                self._partition is not None
                and self.sequencer.noop_pending_since is not None
            ):
                self._partition.noop_waiting.add(key)
        elif t == "servernoop":
            # The consolidation timer's record (reference deli sends its
            # delayed noop through rawdeltas too, so a replay of the raw
            # log tickets the same numbers).
            res = self.sequencer.server_noop()
            if res is not None:
                out.append((DELTAS_TOPIC, key, {"t": "seq", "msg": res}))
        elif t == "opframe":
            out.extend(self._handle_frame(key, value))
        elif t == "summary_decision":
            ack = self.sequencer._sequence_system(
                MessageType.SUMMARY_ACK if value["ok"] else MessageType.SUMMARY_NACK,
                contents={
                    "handle": value["handle"],
                    "summary_seq": value["summary_seq"],
                    "head": value["head"],
                },
            )
            out.append((DELTAS_TOPIC, key, {"t": "seq", "msg": ack}))
        elif t == "signal":
            group = value.get("group")
            if group is not None:
                basis = value["basis"]
                if basis <= self._signal_basis.get(group, 0):
                    return out  # replayed service signal: already sent
                self._signal_basis[group] = basis
            self._signal_counter += 1
            if self._partition is not None:
                self._partition.signals_received += 1
            out.append(
                (SIGNALS_TOPIC, key,
                 {"client": value["client"], "num": self._signal_counter,
                  "content": value["content"]})
            )
        else:  # pragma: no cover
            raise ValueError(f"unknown raw record {value!r}")
        return out

    def _handle_frame(self, key: str, value: dict) -> List[Tuple[str, str, Any]]:
        """Ticket ONE batched binary op frame (protocol/opframe.py) in a
        vectorized call and emit the sequenced frame as one deltas
        record. The REFERENCE for a frame, and the path of every frame
        that is not the steady-state one: a duplicate or a duplicate
        prefix, a csn gap, refs that differ, a stale ref (a partial
        ticket with its trailing nack), any nack, a sampled frame with
        its trace stamps. The serving path of a steady-state frame is
        :meth:`DeliPartitionLambda._ticket_run`, which tickets a read
        chunk's frames together and calls this one, at its place in the
        run, for the rest; ``tests/test_deli_chunk_ticket.py`` holds the
        two bit-equal."""
        client = value["client"]
        frame = value["frame"]
        # Sampled frame (trace list rides the record envelope): the
        # alfred span closes at pump dequeue, the deli span brackets the
        # vectorized ticket. Untraced frames skip every stamp.
        traces = value.get("traces")
        if traces is not None:
            tracing.stamp(traces, tracing.STAGE_ALFRED, "end")
            tracing.stamp(traces, tracing.STAGE_DELI, "start")
        fr = frame.rows
        prof = profiler._ON  # the r16 timeline's ticket lane (one
        # predicate untraced; armed, the SAME two perf_counter reads
        # bracket the native ticketer call)
        if prof:
            t_tk0 = time.perf_counter()
        res = self.sequencer.ticket_frame(
            client, frame.csn0, frame.n, fr[:, F_REF]
        )
        if prof:
            profiler.record(
                "ticket", t_tk0, time.perf_counter(), rows=frame.n
            )
        if traces is not None:
            tracing.stamp(traces, tracing.STAGE_DELI, "end")
        if res is None:
            # Whole-frame duplicate (MSN/csn dedup): silently dropped on
            # the wire by contract — but the flight recorder remembers,
            # so a dup-nacked op's lineage shows WHERE its resubmit died.
            if journal._ON:
                journal.record(
                    "frame.nack", doc=key, client=client, csn=frame.csn0,
                    csn_hi=frame.csn0 + frame.n - 1, reason="dup",
                )
            return []
        if isinstance(res, NackMessage):
            if journal._ON:
                journal.record(
                    "frame.nack", doc=key, client=client, csn=frame.csn0,
                    csn_hi=frame.csn0 + frame.n - 1,
                    reason=getattr(
                        res.error_type, "name", str(res.error_type)
                    ),
                )
            return [(DELTAS_TOPIC, key, {"t": "nack", "client": client,
                                         "nack": res})]
        assert isinstance(res, FrameTicket)
        whole = res.drop == 0 and res.m == frame.n
        rows = np.array(fr if whole else fr[res.drop : res.drop + res.m],
                        np.int32)
        rows[:, F_SEQ] = res.seq0 + _arange(res.m)
        rows[:, F_MSN] = res.msn
        rows[:, F_CLIENT] = client
        if whole:
            texts = frame.texts
        else:
            ins = fr[:, F_TYPE] == OP_INSERT
            t_lo = int(np.count_nonzero(ins[: res.drop]))
            t_hi = int(np.count_nonzero(ins[: res.drop + res.m]))
            texts = frame.texts[t_lo:t_hi]
        sf = SeqFrame(
            frame.address, client, frame.csn0 + res.drop, rows, texts,
            res.timestamp,
        )
        if journal._ON:
            # The ticket event is the lineage JOIN point: it maps the
            # op's pre-sequencing identity (client, csn) to its sequence
            # number, so journal.lineage(doc, seq) can pull in the
            # submit/admit half recorded before a seq existed.
            journal.record(
                "frame.ticket", doc=key, seq=res.seq0,
                seq_hi=res.seq0 + res.m - 1, csn=frame.csn0 + res.drop,
                csn_hi=frame.csn0 + res.drop + res.m - 1, client=client,
            )
        seq_rec: Dict[str, Any] = {"t": "seqframe", "frame": sf}
        if traces is not None:
            # The SAME list object rides the sequenced record: every
            # downstream consumer group (scriptorium, broadcaster, the
            # device stage) stamps into it in-proc.
            seq_rec["traces"] = traces
        out: List[Tuple[str, str, Any]] = [(DELTAS_TOPIC, key, seq_rec)]
        if res.trailing_nack is not None:
            out.append((DELTAS_TOPIC, key, {"t": "nack", "client": client,
                                            "nack": res.trailing_nack}))
        return out


def _gather_frames(run):
    """One block for a run of ``opframe`` records: the frames' rows
    concatenated into a private ``[sum n, OP_WIDTH] int32`` copy (the copy
    the per-record path makes per frame), and what the ticket loop wants
    of every frame read from it at once, as Python lists: the row count,
    the first clientSequenceNumber, the first refSeq, whether every op
    shares that refSeq. None for a run this cannot read (an empty or
    malformed frame): the per-record path then raises what it raises."""
    try:
        rows = [rec.value["frame"].rows for rec in run]
        ns = [r.shape[0] for r in rows]
        if 0 in ns:
            return None
        block = np.concatenate(rows, dtype=np.int32)
    except Exception:
        return None
    starts = np.array(list(itertools.accumulate(ns, initial=0))[:-1])
    refs = block[:, F_REF]
    r0s = np.minimum.reduceat(refs, starts)  # the first, where all agree
    uniform = r0s == np.maximum.reduceat(refs, starts)
    return (block, ns, block[starts, F_SEQ].tolist(), r0s.tolist(),
            uniform.tolist())


class DeliPartitionLambda(DocumentLambda):
    """The deli partition's router, and the SERVING path of an op frame:
    a maximal run of two or more consecutive ``opframe`` records of a
    read chunk is ticketed in one pass (one gather, one ticket loop on
    plain integers, one stamp, each ``SeqFrame`` a view of the run's
    block) instead of a frame at a time. The outputs are, record for
    record and bit for bit, what ``DocumentLambda.handler_batch`` over
    ``DeliDocLambda.handler`` gives (``tests/test_deli_chunk_ticket.py``):
    a frame that is not the steady-state one (a duplicate, a gap, refs
    that differ, a stale ref, any nack, a sampled frame with its trace
    stamps) goes through ``DeliDocLambda._handle_frame`` at its place in
    the run, and every other kind of record through the generic router.
    So does a frame with no frame beside it (a websocket's, one a pump):
    there is nothing to share the gather and the stamp with, and on the
    chip's host the pass cost a third more than ``_handle_frame`` there
    (PERF.md §6, PR 35); from two frames on it costs less.

    ``frames_batched`` / ``frames_single`` count the frames each way
    (``PipelineFluidService.stats()``), ``stats`` is the ticket loop's
    counts over the partition's documents, ``signals_received`` the
    signals deli numbered, and ``noop_waiting`` the documents whose
    client noops moved the MSN and wait for a message to carry it."""

    def __init__(self):
        super().__init__(lambda doc_id, s: DeliDocLambda(doc_id, s, self))
        self.frames_batched = 0
        self.frames_single = 0
        self.stats = SequencerStats()
        self.signals_received = 0
        self.noop_waiting: set = set()

    def noops_due(self, now: float) -> List[str]:
        """The waiting documents that have sequenced nothing for the
        consolidation time: each is due one ``servernoop`` record. A
        document whose MSN a sequenced message has carried meanwhile
        stops waiting."""
        due = []
        for key in list(self.noop_waiting):
            lam = self._docs.get(key)
            if lam is None or lam.sequencer.noop_pending_since is None:
                self.noop_waiting.discard(key)
            elif lam.sequencer.noop_due(now):
                self.noop_waiting.discard(key)
                due.append(key)
        return due

    def handler_batch(self, recs) -> List[Tuple[str, str, Any]]:
        """A read chunk as alternating stretches of op frames (the run
        pass; a lone frame takes the generic router) and of other
        records (the generic router), in record order; a failing record
        raises :class:`BatchHandlerError` with the chunk's completed
        prefix, as the generic router does."""
        out: List[Tuple[str, str, Any]] = []
        n = len(recs)
        i = 0
        while i < n:
            framed = recs[i].value.get("t") == "opframe"
            j = i + 1
            while j < n and (recs[j].value.get("t") == "opframe") == framed:
                j += 1
            run = recs[i:j]
            try:
                if framed and j - i > 1:
                    out.extend(self._ticket_run(run))
                else:
                    self.frames_single += framed
                    out.extend(super().handler_batch(run))
            except BatchHandlerError as be:
                raise BatchHandlerError(
                    out + be.outputs, i + be.n_ok, be.cause
                ) from be.cause
            i = j
        return out

    def _ticket_run(self, run) -> List[Tuple[str, str, Any]]:
        gathered = _gather_frames(run)
        if gathered is None:
            self.frames_single += len(run)
            return super().handler_batch(run)
        block, ns, csn0s, r0s, uniform = gathered
        out: List[Tuple[str, str, Any]] = []
        docs, dirty = self._docs, self._dirty
        journal_on = journal._ON
        # Per frame, for the one stamp: (the first sequence number less
        # the frame's first row: a row's F_SEQ is that plus its own index
        # in the block; the MSN; the client).
        stamps: List[Tuple[int, int, int]] = []
        single = lo = 0
        prof = profiler._ON  # the armed-only ticket lane: once per run
        if prof:
            t_tk0 = time.perf_counter()
        now = time.time()
        try:
            for rec, n, csn0, r0, same in zip(run, ns, csn0s, r0s, uniform):
                key = rec.key
                value = rec.value
                lam = docs.get(key)
                if lam is None:
                    lam = docs[key] = self._factory(key, None)
                dirty.add(key)
                client = value["client"]
                t = None
                if same and value.get("traces") is None:
                    t = lam.sequencer.ticket_uniform(client, csn0, n, r0, now)
                if t is None:
                    out.extend(lam._handle_frame(key, value))
                    single += 1
                    # Its ticket read the clock after this run's: a later
                    # frame of the same document must not be stamped
                    # with an earlier time.
                    now = time.time()
                    stamps.append((0, 0, 0))  # its rows here are nobody's
                else:
                    frame = value["frame"]
                    if journal_on:
                        journal.record(
                            "frame.ticket", doc=key, seq=t[0],
                            seq_hi=t[0] + n - 1, csn=csn0,
                            csn_hi=csn0 + n - 1, client=client,
                        )
                    out.append((DELTAS_TOPIC, key, {
                        "t": "seqframe",
                        "frame": SeqFrame(
                            frame.address, client, csn0, block[lo : lo + n],
                            frame.texts, now,
                        ),
                    }))
                    stamps.append((t[0] - lo, t[1], client))
                lo += n
        except Exception as e:
            raise BatchHandlerError(out, len(stamps), e) from e
        finally:
            # The one stamp, also of the prefix a failing record leaves:
            # its frames are out already, as views of the block.
            m = len(stamps)
            self.frames_single += single
            self.frames_batched += m - single
            if lo:
                rows = np.array(stamps, np.int32).repeat(ns[:m], axis=0)
                block[:lo, F_SEQ] = rows[:, 0] + _arange(lo)
                block[:lo, F_MSN] = rows[:, 1]
                block[:lo, F_CLIENT] = rows[:, 2]
            if prof:
                profiler.record(
                    "ticket", t_tk0, time.perf_counter(), rows=lo
                )
        return out


# ---------------------------------------------------------------------------
# Scribe — summary validation + ack decision


class ScribeDocLambda(PartitionLambda):
    def __init__(self, doc_id: str, state: Optional[dict], store):
        self.doc_id = doc_id
        self.store = store
        self.protocol_head = state["protocol_head"] if state else 0
        self.latest_summary: Optional[tuple] = (
            tuple(state["latest"]) if state and state["latest"] else None
        )
        self._decided: set = set(state["decided"]) if state else set()

    def state(self) -> dict:
        return {
            "protocol_head": self.protocol_head,
            "latest": list(self.latest_summary) if self.latest_summary else None,
            "decided": sorted(self._decided),
        }

    def handler(self, key: str, value: dict) -> List[Tuple[str, str, Any]]:
        if value["t"] != "seq":
            return []
        msg: SequencedDocumentMessage = value["msg"]
        if msg.type != MessageType.SUMMARIZE:
            return []
        if msg.sequence_number in self._decided:
            return []  # replay after crash: decision already produced
        self._decided.add(msg.sequence_number)
        from fluidframework_tpu.service.summary_store import scribe_decide

        m = Lumberjack.new_metric(
            LumberEventName.SummaryWrite,
            {"tenantId": "local", "documentId": self.doc_id,
             "summarySequenceNumber": msg.sequence_number},
        )
        ok, contents = scribe_decide(msg, self.protocol_head, self.store)
        handle, head = contents["handle"], contents["head"]
        if ok:
            self.latest_summary = (handle, head)
            self.protocol_head = msg.sequence_number
            m.success()
        else:
            m.error("summary nacked")
        return [
            (RAW_TOPIC, key,
             {"t": "summary_decision", "ok": ok, "handle": handle,
              "head": head, "summary_seq": msg.sequence_number})
        ]


# ---------------------------------------------------------------------------
# Scriptorium — durable op log (the Mongo deltas collection)


def stored_message(v) -> SequencedDocumentMessage:
    """Materialize one ops-store entry: plain sequenced messages are
    stored as-is; frame ops are stored as ``(SeqFrame, i)`` and expand
    lazily here (read-time cost, only for the range a reader asks for)."""
    return v[0].message(v[1]) if isinstance(v, tuple) else v


class DocOpLog:
    """One document's durable op index (the Mongo deltas collection).

    Point ops (the JSON wire, system messages) store per seq; a sequenced
    FRAME stores ONCE — one list append for its whole contiguous seq run,
    not a dict write per covered op (at 10k+ frames/round the per-op
    writes were the entire scriptorium stage cost). Reads resolve frame
    seqs by bisect and expand lazily through :func:`stored_message`, so
    the read-time shape is unchanged: this class keeps the seq-keyed
    mapping surface (iter/len/contains/getitem/items) the service's
    delta readers and tests already use.

    Idempotence under at-least-once replay: deli re-produces identical
    frames, and per-doc partition order means a replayed frame's run can
    never extend past the stored head — anything at or below it drops.
    """

    __slots__ = ("ops", "frames", "_starts", "head")

    def __init__(self):
        self.ops: Dict[int, SequencedDocumentMessage] = {}
        self.frames: list = []  # ascending, non-overlapping seq runs
        self._starts: List[int] = []  # frames[i].first_seq (bisect key)
        self.head = 0  # highest stored seq (O(1) doc_head probe)

    @inject_fault("store.append")
    def add_msg(self, msg: SequencedDocumentMessage) -> None:
        seq = msg.sequence_number
        self.ops[seq] = msg
        if seq > self.head:
            self.head = seq

    @inject_fault("store.append")
    def add_frame(self, frame) -> None:
        if frame.last_seq <= self.head:
            return  # replay duplicate: identical re-production, drop
        self.frames.append(frame)
        self._starts.append(frame.first_seq)
        self.head = frame.last_seq

    def _frame_entry(self, seq: int):
        import bisect

        i = bisect.bisect_right(self._starts, seq) - 1
        if i >= 0:
            f = self.frames[i]
            if seq <= f.last_seq:
                return (f, seq - f.first_seq)
        return None

    # -- the seq-keyed mapping surface ----------------------------------------

    def __len__(self) -> int:
        return len(self.ops) + sum(f.n for f in self.frames)

    def __iter__(self):
        yield from self.ops
        for f in self.frames:
            yield from range(f.first_seq, f.last_seq + 1)

    def __contains__(self, seq) -> bool:
        return seq in self.ops or self._frame_entry(seq) is not None

    def __getitem__(self, seq):
        m = self.ops.get(seq)
        if m is not None:
            return m
        entry = self._frame_entry(seq)
        if entry is None:
            raise KeyError(seq)
        return entry

    def get(self, seq, default=None):
        m = self.ops.get(seq)
        if m is not None:
            return m
        entry = self._frame_entry(seq)
        return default if entry is None else entry

    def items(self):
        yield from self.ops.items()
        for f in self.frames:
            s0 = f.first_seq
            for i in range(f.n):
                yield s0 + i, (f, i)

    def keys(self):
        return iter(self)


class ScriptoriumLambda(PartitionLambda):
    """Idempotent insert of sequenced ops keyed by (doc, seq): one
    :class:`DocOpLog` per document, frames stored whole.

    Recovery contract for the ``store.append`` boundary: appends retry
    with jittered backoff (``service/retry.py`` — the append is
    idempotent under the head watermark, so a retry of a half-observed
    failure cannot double-store); EXHAUSTED retries raise through the
    runner, whose offset then never advances past the frame — the record
    replays on the next pump (at-least-once), so no sequenced op is ever
    lost to a store outage and none duplicates."""

    wants = frozenset({"seq", "seqframe"})

    def __init__(self, ops_store: Dict[str, DocOpLog]):
        self.ops_store = ops_store

    def _doc(self, key: str) -> DocOpLog:
        log = self.ops_store.get(key)
        if log is None:
            log = self.ops_store[key] = DocOpLog()
        return log

    def handler(self, key: str, value: dict) -> List[Tuple[str, str, Any]]:
        if value["t"] == "seq":
            retry.call_with_retry(
                "store.append", self._doc(key).add_msg, value["msg"]
            )
            if journal._ON:
                journal.record(
                    "log.append", doc=key,
                    seq=value["msg"].sequence_number,
                )
        elif value["t"] == "seqframe":
            traces = value.get("traces")
            if traces is not None:
                tracing.stamp(traces, tracing.STAGE_SCRIPTORIUM, "start")
            retry.call_with_retry(
                "store.append", self._doc(key).add_frame, value["frame"]
            )
            if traces is not None:
                tracing.stamp(traces, tracing.STAGE_SCRIPTORIUM, "end")
            if journal._ON:
                journal.record(
                    "log.append", doc=key, seq=value["frame"].first_seq,
                    seq_hi=value["frame"].last_seq,
                )
        return []

    def handler_batch(self, recs) -> List[Tuple[str, str, Any]]:
        store = self.ops_store
        for rec in recs:
            value = rec.value
            t = value.get("t")
            if t == "seqframe":
                traces = value.get("traces")
                if traces is not None:
                    tracing.stamp(
                        traces, tracing.STAGE_SCRIPTORIUM, "start"
                    )
                log = store.get(rec.key)
                if log is None:
                    log = store[rec.key] = DocOpLog()
                retry.call_with_retry(
                    "store.append", log.add_frame, value["frame"]
                )
                if traces is not None:
                    tracing.stamp(traces, tracing.STAGE_SCRIPTORIUM, "end")
                if journal._ON:
                    journal.record(
                        "log.append", doc=rec.key,
                        seq=value["frame"].first_seq,
                        seq_hi=value["frame"].last_seq,
                    )
            elif t == "seq":
                retry.call_with_retry(
                    "store.append", self._doc(rec.key).add_msg, value["msg"]
                )
                if journal._ON:
                    journal.record(
                        "log.append", doc=rec.key,
                        seq=value["msg"].sequence_number,
                    )
        return []

    def state(self) -> Any:
        return None  # the store itself is the durable artifact


# ---------------------------------------------------------------------------
# Broadcaster — fan-out to client connections (socket rooms)


class BroadcasterLambda(PartitionLambda):
    """Delivers sequenced ops to every connection in the document's room,
    dropping anything a connection already saw (idempotent under replay)."""

    wants = frozenset({"seq", "seqframe", "nack"})

    def __init__(self, rooms: Dict[str, list], observe_traces: bool = False):
        self.rooms = rooms
        # Per-op span reduction is OPT-IN, on only when the SERVICE
        # samples traces (traces is a client-controlled wire field; with
        # sampling off nothing the server didn't ask for may reach the
        # registry — so client-trust must never be the default).
        self.observe_traces = observe_traces

    def handler(self, key: str, value: dict) -> List[Tuple[str, str, Any]]:
        conns = self.rooms.get(key, [])
        if value["t"] == "seq":
            msg = value["msg"]
            if (
                self.observe_traces
                and msg.traces
                and tracing.has_stamp(
                    msg.traces, tracing.STAGE_ALFRED, "start"
                )
                and not tracing.has_stamp(
                    msg.traces, tracing.STAGE_ALFRED, "end"
                )
            ):
                # Sampled per-op path: broadcast is where the op leaves
                # the service, so the front door's span closes HERE — the
                # missing ``alfred end`` that kept spans() from ever
                # producing ``alfred_ms`` — and the completed trace
                # reduces into the registry. The not-already-ended guard
                # keeps a deli crash/replay (same sequenced op re-emitted
                # downstream) from double-observing the span.
                tracing.stamp(msg.traces, tracing.STAGE_ALFRED, "end")
                metrics.observe_stage_spans(tracing.spans(msg.traces))
            if journal._ON:
                journal.record(
                    "broadcast", doc=key, seq=msg.sequence_number,
                    conns=len(conns),
                )
            for conn in conns:
                if msg.sequence_number > conn.delivered_seq:
                    conn.inbox.append(msg)
                    conn.delivered_seq = msg.sequence_number
        elif value["t"] == "seqframe":
            # One inbox append per frame per connection; take_inbox (or
            # the socket drain) expands. A partially-delivered frame
            # (replay straddling the watermark) expands the tail only.
            frame = value["frame"]
            traces = value.get("traces")
            if traces is not None:
                tracing.stamp(traces, tracing.STAGE_BROADCAST, "start")
            if journal._ON:
                journal.record(
                    "broadcast", doc=key, seq=frame.first_seq,
                    seq_hi=frame.last_seq, conns=len(conns),
                )
            for conn in conns:
                if frame.last_seq <= conn.delivered_seq:
                    continue
                if frame.first_seq > conn.delivered_seq:
                    conn.inbox.append(frame)
                else:
                    conn.inbox.extend(
                        frame.messages(conn.delivered_seq - frame.first_seq + 1)
                    )
                conn.delivered_seq = frame.last_seq
            if traces is not None:
                tracing.stamp(traces, tracing.STAGE_BROADCAST, "end")
        elif value["t"] == "nack":
            for conn in conns:
                if value.get("client") == conn.client_id or (
                    value.get("token") is not None
                    and value.get("token") == conn.token
                ):
                    conn.nacks.append(value["nack"])
                    if conn.on_nack:
                        conn.on_nack(value["nack"])
        return []


class SignalBroadcasterLambda(PartitionLambda):
    def __init__(self, rooms: Dict[str, list]):
        self.rooms = rooms
        self.delivered = 0  # signals put on a connection's queue

    def handler(self, key: str, value: dict) -> List[Tuple[str, str, Any]]:
        from fluidframework_tpu.protocol.types import SignalMessage

        # ONE object a signal, queued on every connection of the room as
        # the op broadcaster queues a sequenced message: the socket
        # layer's delivery sweep encodes what it finds queued once per
        # object, not once per socket.
        sig = SignalMessage(
            client_id=value["client"],
            client_connection_number=value["num"],
            content=value["content"],
        )
        for conn in self.rooms.get(key, []):
            if value["num"] > conn.delivered_signal:
                conn.signals.append(sig)
                conn.delivered_signal = value["num"]
                self.delivered += 1
        return []
