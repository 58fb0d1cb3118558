"""The sequencer — deli semantics as a pure per-document state machine.

TPU-native re-design of the reference's deli lambda
(``server/routerlicious/packages/lambdas/src/deli/lambda.ts`` — ``ticket()``
at :742, MSN calc :929-938, dedup/gap ``checkOrder`` :789-798, nack rules
:864-893) and its per-client heap (``clientSeqManager.ts``).

One :class:`DocumentSequencer` owns one document's total order: it validates
inbound raw ops (dedup, gap, stale refSeq), assigns ``sequenceNumber``,
maintains the client table and the minimum sequence number, and emits
sequenced messages. It is deliberately pure/host-side — the ordering path is
not device work; its output batches are what the TPU kernel consumes.

Client slots are small ints (0..MAX_WRITERS-1) so sequenced ops lower
directly to int32 kernel rows.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

from fluidframework_tpu.protocol.constants import MAX_WRITERS
from fluidframework_tpu.telemetry import tracing
from fluidframework_tpu.protocol.types import (
    DocumentMessage,
    MessageType,
    NackErrorType,
    NackMessage,
    SequencedDocumentMessage,
)


FULL_SCOPES = ("doc:read", "doc:write", "summary:write")

# Noop consolidation (reference deli ``noOpConsolidationTimeout``, 250 ms
# in routerlicious ``config.json``): how long a document must have had
# nothing sequenced before deli spends a sequence number on a server noop
# that carries an MSN which client noops have moved.
NOOP_CONSOLIDATION_S = 0.25
# What a join refused for want of a writer slot tells the client to wait:
# a left slot frees when every writer has sent something (an op or its
# collab-window noop, at most two seconds after the leave) past the leave.
JOIN_RETRY_AFTER_S = 1.0


class SequencerStats:
    """Always-on integers of the ticket loop, one object for every
    document of a deli partition (``PipelineFluidService.stats()`` sums
    the partitions; a restarted deli starts at zero, as a restarted
    process would): client noops taken in without a sequence number and
    noops sequenced (a client's immediate one, or the server's
    consolidated one); the most write slots any document held at once
    and the joins refused for want of one; head - MSN summed at every
    ticket, and the tickets."""

    __slots__ = (
        "noops_received", "noops_sequenced", "writer_slots_peak",
        "join_nacks_slots", "msn_lag_sum", "msn_lag_count",
    )

    def __init__(self):
        for k in self.__slots__:
            setattr(self, k, 0)


@dataclass
class _ClientEntry:
    client_id: int
    ref_seq: int
    client_seq: int  # highest clientSequenceNumber seen
    can_evict: bool = True
    mode: str = "write"
    last_seen: float = 0.0  # wall time of last op/join (idle expiry)
    scopes: tuple = FULL_SCOPES  # token claims (reference scopes.ts)


@dataclass
class FrameTicket:
    """Result of a successful (possibly partial) ``ticket_frame``."""

    drop: int  # leading replay duplicates dropped
    m: int  # ops ticketed (rows drop..drop+m-1)
    seq0: int  # first assigned sequence number (contiguous run of m)
    msn: "object"  # np.ndarray [m] per-op minimum sequence numbers
    timestamp: float
    trailing_nack: Optional[NackMessage] = None  # first op past the valid run


@dataclass
class SequencerCheckpoint:
    """Durable sequencer state (reference ``IDeliState``,
    services-core/src/document.ts:56): enough to resume after a crash."""

    sequence_number: int
    minimum_sequence_number: int
    clients: List[dict] = field(default_factory=list)
    next_slot: int = 0
    free_slots: List[List[int]] = field(default_factory=list)  # [slot, leave_seq]
    connection_count: int = 0  # monotonic join ordinal, never recycled


class DocumentSequencer:
    """Assigns the total order for one document (deli ``ticket()``)."""

    def __init__(
        self, doc_id: str, checkpoint: Optional[SequencerCheckpoint] = None,
        stats: Optional[SequencerStats] = None,
    ):
        self.doc_id = doc_id
        self.stats = stats if stats is not None else SequencerStats()
        self.writer_slots_peak = 0  # this document's own
        self.seq = 0
        self.min_seq = 0
        # Control plane (reference deli lambda.ts:989+ ControlMessageType):
        # durable sequence number (UpdateDSN — the log-truncation floor) and
        # maintenance nacking (NackMessages).
        self.durable_seq = 0
        self._nack_all: Optional[dict] = None  # {"code", "message"}
        self._no_client_emitted = True  # fresh doc has no clients
        self.clients: Dict[int, _ClientEntry] = {}
        self._next_slot = 0
        # Slots released by leaves, reusable once their leave seq falls at or
        # below the collab-window floor: every stamp from the old holder is
        # then acked and outside any perspective the kernel can be asked for,
        # so a new holder cannot collide (deli has no cap — string client
        # ids; the int-slot design needs recycling to live that long).
        self._free_slots: List[List[int]] = []
        # Slots are kernel-facing and recycle; the connection ordinal is the
        # never-recycled identity clients scope content ids to (a recycled
        # slot must not collide payload/cell id keyspaces).
        self._conn_count = 0
        # Consolidation: when client noops last left the MSN ahead of
        # what the stream has said (None: nothing to carry), and when
        # the document last sequenced anything.
        self.noop_pending_since: Optional[float] = None
        self.last_sequenced_at = 0.0
        if checkpoint is not None:
            self.seq = checkpoint.sequence_number
            self.min_seq = checkpoint.minimum_sequence_number
            self._next_slot = checkpoint.next_slot
            self._free_slots = [list(x) for x in checkpoint.free_slots]
            self._conn_count = checkpoint.connection_count
            for c in checkpoint.clients:
                self.clients[c["client_id"]] = _ClientEntry(**c)

    # -- session management --------------------------------------------------

    def join(
        self, mode: str = "write", scopes: tuple = FULL_SCOPES
    ) -> Union[SequencedDocumentMessage, NackMessage]:
        """Admit a client; returns the sequenced ClientJoin op.

        The slot cap mirrors the kernel's removers bitmask width: deli's
        1M-clients/doc cap (config.json:57) becomes MAX_WRITERS (124)
        concurrent write slots per document. A slot a writer left is
        handed out again once the leave's seq is at or under the MSN; a
        join that finds none is nacked 429 with a retry-after, and the
        client's connect comes back after it.
        """
        slot = None
        for i, (s, leave_seq) in enumerate(self._free_slots):
            if leave_seq <= self.min_seq:
                slot = s
                del self._free_slots[i]
                break
        if slot is None:
            if self._next_slot >= MAX_WRITERS:
                self.stats.join_nacks_slots += 1
                return NackMessage(
                    self.seq, 429, NackErrorType.LIMIT_EXCEEDED,
                    f"document writer slots exhausted ({MAX_WRITERS})",
                    retry_after_s=JOIN_RETRY_AFTER_S,
                )
            slot = self._next_slot
            self._next_slot += 1
        # Join contents carry the client detail (reference ClientJoin op's
        # IClient payload) — election needs the mode for eligibility, and
        # connNo is the never-recycled ordinal content ids scope to.
        self._conn_count += 1
        self._no_client_emitted = False
        msg = self._sequence_system(
            MessageType.CLIENT_JOIN,
            contents={"clientId": slot, "mode": mode, "connNo": self._conn_count},
        )
        # The new client's collab-window floor is the join op itself.
        self.clients[slot] = _ClientEntry(
            client_id=slot, ref_seq=msg.sequence_number, client_seq=0, mode=mode,
            last_seen=time.time(), scopes=tuple(scopes),
        )
        live = sum(c.mode == "write" for c in self.clients.values())
        if live > self.writer_slots_peak:
            self.writer_slots_peak = live
            if live > self.stats.writer_slots_peak:
                self.stats.writer_slots_peak = live
        return msg

    def leave(self, client_id: int) -> Optional[SequencedDocumentMessage]:
        if client_id not in self.clients:
            return None
        del self.clients[client_id]
        msg = self._sequence_system(MessageType.CLIENT_LEAVE, contents=client_id)
        self._free_slots.append([client_id, msg.sequence_number])
        return msg

    def maybe_no_client(self) -> Optional[SequencedDocumentMessage]:
        """Emit a NoClient system op once when the last client departs
        (reference deli op-events, lambda.ts:136-150) — the service's
        trigger for an end-of-session service summary."""
        if self.clients or self._no_client_emitted:
            return None
        self._no_client_emitted = True
        return self._sequence_system(MessageType.NO_CLIENT, contents=None)

    # -- control plane (reference ControlMessageType, deli lambda.ts:989+) ---

    def control(self, contents: dict) -> SequencedDocumentMessage:
        """Apply a sequenced service control message.

        - ``{"type": "updateDSN", "dsn": N}`` advances the durable sequence
          number (the storage-confirmed floor log truncation may use);
        - ``{"type": "nackMessages", "enable": bool, "code"?, "message"?}``
          toggles maintenance mode: while enabled every client op is nacked
          with the given code (the reference's NackMessages control).
        """
        kind = contents.get("type")
        if kind == "updateDSN":
            self.durable_seq = max(self.durable_seq, int(contents["dsn"]))
        elif kind == "nackMessages":
            if contents.get("enable", True):
                self._nack_all = {
                    "code": int(contents.get("code", 503)),
                    "message": contents.get("message", "service paused"),
                }
            else:
                self._nack_all = None
        else:
            raise ValueError(f"unknown control message {kind!r}")
        return self._sequence_system(MessageType.CONTROL, contents=contents)

    def expire_idle(
        self, timeout_s: float, now: Optional[float] = None
    ) -> List[SequencedDocumentMessage]:
        """Evict clients idle past ``timeout_s`` (reference deli expires
        stale clients via ClientSequenceTimeout so a crashed client that
        never sent leave cannot pin the MSN forever). Returns the sequenced
        leave messages to broadcast."""
        now = time.time() if now is None else now
        stale = [
            c.client_id
            for c in self.clients.values()
            if c.can_evict and now - c.last_seen > timeout_s
        ]
        out = []
        for cid in stale:
            msg = self.leave(cid)
            if msg is not None:
                out.append(msg)
        return out

    # -- the ticket loop ------------------------------------------------------

    def ticket(
        self, client_id: int, msg: DocumentMessage
    ) -> Union[SequencedDocumentMessage, NackMessage, None]:
        """Sequence one raw client op. Returns the sequenced message, a nack,
        or None for a duplicate (silently dropped, reference checkOrder)."""
        entry = self.clients.get(client_id)
        if entry is None:
            return NackMessage(
                self.seq, 400, NackErrorType.BAD_REQUEST, "unknown client"
            )
        if entry.mode != "write":
            return NackMessage(
                self.seq, 403, NackErrorType.INVALID_SCOPE, "read-only client"
            )
        if msg.type == MessageType.NOOP and msg.contents is None:
            return self._take_noop(entry, msg.reference_sequence_number)
        if self._nack_all is not None:
            # Maintenance mode (NackMessages control): reject without
            # consuming the clientSequenceNumber so a later resubmit works.
            return NackMessage(
                self.seq, self._nack_all["code"],
                NackErrorType.LIMIT_EXCEEDED, self._nack_all["message"],
                retry_after_s=1.0,
                client_sequence_number=msg.client_sequence_number,
            )
        # Duplicate: clientSequenceNumber at-or-below the highest seen.
        if msg.client_sequence_number <= entry.client_seq:
            return None
        # Gap: the client skipped a clientSequenceNumber.
        if msg.client_sequence_number != entry.client_seq + 1:
            return NackMessage(
                self.seq, 400, NackErrorType.BAD_REQUEST,
                f"clientSequenceNumber gap (expected {entry.client_seq + 1})",
                client_sequence_number=msg.client_sequence_number,
            )
        # Stale reference: below the collab window floor.
        if msg.reference_sequence_number < self.min_seq:
            return NackMessage(
                self.seq, 400, NackErrorType.BAD_REQUEST,
                f"refSeq {msg.reference_sequence_number} below MSN {self.min_seq}",
                client_sequence_number=msg.client_sequence_number,
            )
        if (
            msg.type == MessageType.SUMMARIZE
            and "summary:write" not in entry.scopes
        ):
            # Unauthorized Summarize -> 403 (reference deli lambda.ts:884-893).
            return NackMessage(
                self.seq, 403, NackErrorType.INVALID_SCOPE,
                "client token lacks summary:write",
                client_sequence_number=msg.client_sequence_number,
            )
        entry.client_seq = msg.client_sequence_number
        entry.ref_seq = msg.reference_sequence_number
        entry.last_seen = time.time()

        # Sampled op tracing: if the front door stamped this message, the
        # sequencer appends its own span (reference deli/lambda.ts:1451).
        # Stamps go on a copy — the inbound message stays caller-owned.
        traces = list(msg.traces)
        if traces:
            tracing.stamp(traces, "deli", "start")

        # A noop that reaches this line is the reference's IMMEDIATE one
        # (non-null contents, ``ContainerRuntime.send_noop()``): it is
        # sequenced like any op (deli lambda.ts:896-927 sends it at
        # once). The collab-window heartbeat's noop (null contents) left
        # through :meth:`_take_noop` above and took no sequence number.
        self.seq += 1
        now = time.time()
        if msg.type == MessageType.NOOP:
            self.stats.noops_sequenced += 1
        msn = self._compute_msn()
        self._sequenced(now, 1, msn)
        if traces:
            tracing.stamp(traces, "deli", "end")
        return SequencedDocumentMessage(
            client_id=client_id,
            sequence_number=self.seq,
            client_sequence_number=msg.client_sequence_number,
            reference_sequence_number=msg.reference_sequence_number,
            minimum_sequence_number=msn,
            type=msg.type,
            contents=msg.contents,
            metadata=msg.metadata,
            timestamp=now,
            traces=traces,
        )

    # -- noop consolidation (reference deli lambda.ts:896-927) ----------------

    def _take_noop(self, entry: _ClientEntry, ref_seq: int) -> None:
        """A client's collab-window noop (null contents): it says how far
        the client has read and nothing else, so it moves the client's
        refSeq and is NOT sequenced. If that leaves the MSN ahead of what
        the stream has said, the next sequenced message of the document
        carries it; :meth:`server_noop` does when nothing else comes for
        ``NOOP_CONSOLIDATION_S``. Unlike the reference's, the noop takes
        no clientSequenceNumber either: this client's nack recovery
        numbers its resubmission from the last op it saw echoed
        (``ContainerRuntime.process_incoming``), and a number that only
        the server had counted would make the next op a duplicate."""
        self.stats.noops_received += 1
        now = time.time()
        entry.last_seen = now
        if ref_seq > entry.ref_seq:
            entry.ref_seq = ref_seq
            if (
                self.noop_pending_since is None
                and self._refseq_floor() > self.min_seq
            ):
                self.noop_pending_since = now
        return None

    def noop_due(self, now: float) -> bool:
        """Whether client noops have moved the MSN and the document has
        sequenced nothing for ``NOOP_CONSOLIDATION_S`` since."""
        since = self.noop_pending_since
        return since is not None and (
            now - max(since, self.last_sequenced_at) >= NOOP_CONSOLIDATION_S
        )

    def server_noop(self) -> Optional[SequencedDocumentMessage]:
        """The consolidated noop: ONE sequenced server message that
        carries the MSN the clients' noops moved, or None when a message
        sequenced meanwhile has carried it already."""
        self.noop_pending_since = None
        if not self.clients or self._refseq_floor() <= self.min_seq:
            return None
        self.stats.noops_sequenced += 1
        return self._sequence_system(MessageType.NOOP, contents=None)

    def _sequenced(self, now: float, n: int, msn: int) -> None:
        """Bookkeeping of every ticket: the lag of the collab window
        behind the head, and that whatever client noops had moved is now
        said."""
        st = self.stats
        st.msn_lag_sum += self.seq - msn
        st.msn_lag_count += 1
        self.last_sequenced_at = now
        self.noop_pending_since = None

    def ticket_uniform(
        self, client_id: int, csn0: int, n: int, r0: int, now: float
    ) -> Optional[Tuple[int, int]]:
        """The steady-state ticket on plain integers: ``n >= 1`` ops of a
        known writer, next in its csn order, all authored against the one
        refSeq ``r0`` at or above the MSN. Returns ``(seq0, msn)`` — the
        run's first sequence number and the one MSN every op of it
        carries, ``max(floor, min(r0, the other clients' refSeqs))`` —
        or None, HAVING CHANGED NOTHING, for anything else: the caller
        then takes :meth:`ticket_frame`, whose checks say which nack,
        duplicate or partial ticket it is."""
        entry = self.clients.get(client_id)
        if (
            entry is None or entry.mode != "write"
            or self._nack_all is not None
            or csn0 != entry.client_seq + 1 or r0 < self.min_seq
        ):
            return None
        floor = r0
        for c in self.clients.values():
            if c.ref_seq < floor and c is not entry:
                floor = c.ref_seq
        if floor < self.min_seq:
            floor = self.min_seq
        entry.client_seq = csn0 + n - 1
        entry.ref_seq = r0
        entry.last_seen = now
        seq0 = self.seq + 1
        self.seq += n
        self.min_seq = floor
        self._sequenced(now, n, floor)
        return seq0, floor

    def ticket_frame(
        self, client_id: int, csn0: int, n: int, refs
    ) -> Union["FrameTicket", NackMessage, None]:
        """Vectorized ticket for an :class:`~fluidframework_tpu.protocol.
        opframe.OpFrame`: n contiguous client ops in one call, with
        per-op semantics identical to n ``ticket()`` calls on OPERATION
        messages — duplicate csns drop from the front, the first invalid
        op nacks and (as per-op ticketing would, via the resulting csn
        gap) implicitly rejects everything after it, MSN advances per op.

        Returns a :class:`FrameTicket` (drop count, valid count, seq0,
        per-op msn array), a NackMessage (``client_sequence_number`` =
        first rejected csn), or None when every op is a replay duplicate.

        This is the REFERENCE for a frame's ticket and the path of every
        frame that is not the steady-state one (a duplicate, a gap, refs
        that differ, a stale ref, any nack). The serving path is deli's
        run pass (``lambdas.DeliPartitionLambda``), which reads a read
        chunk's frames at once and calls :meth:`ticket_uniform` on plain
        integers; ``tests/test_deli_chunk_ticket.py`` holds the two
        bit-equal, ``tests/test_opframe.py`` holds this one to n
        ``ticket()`` calls.
        """
        import numpy as np

        entry = self.clients.get(client_id)
        if entry is None:
            return NackMessage(
                self.seq, 400, NackErrorType.BAD_REQUEST, "unknown client"
            )
        if entry.mode != "write":
            return NackMessage(
                self.seq, 403, NackErrorType.INVALID_SCOPE, "read-only client"
            )
        if self._nack_all is not None:
            return NackMessage(
                self.seq, self._nack_all["code"],
                NackErrorType.LIMIT_EXCEEDED, self._nack_all["message"],
                retry_after_s=1.0, client_sequence_number=csn0,
            )
        drop = max(0, entry.client_seq - csn0 + 1)
        if drop >= n:
            return None  # whole frame is a replay duplicate
        if csn0 + drop != entry.client_seq + 1:
            return NackMessage(
                self.seq, 400, NackErrorType.BAD_REQUEST,
                f"clientSequenceNumber gap (expected {entry.client_seq + 1})",
                client_sequence_number=csn0 + drop,
            )
        # Fast path — the steady-state serving stream: no dup prefix and
        # every op in the frame shares one refSeq (a client-turn batch
        # authored against one head): :meth:`ticket_uniform`, the state
        # machine deli's run pass calls directly.
        now = time.time()
        if drop == 0:
            r0 = int(refs[0])
            if r0 == int(refs[-1]) and (
                n < 3 or (np.asarray(refs) == r0).all()
            ):
                t = self.ticket_uniform(client_id, csn0, n, r0, now)
                if t is not None:
                    return FrameTicket(
                        drop=0, m=n, seq0=t[0],
                        msn=np.full(n, t[1], np.int32), timestamp=now,
                    )
        # General path (per-op semantics in one pass): op i is stale
        # against the MSN established by op i-1 (the freshly advanced
        # floor per-op ticket() checks), and msn_i = max(floor,
        # min(others_min, refs[i])) never regresses. A plain Python loop
        # beats numpy well past typical frame sizes (array overhead
        # ~20µs/frame dominates the deli stage at n<=64).
        others = [
            c.ref_seq for c in self.clients.values() if c.client_id != client_id
        ]
        others_min = min(others) if others else None
        refs_l = [int(x) for x in refs[drop:]]
        n_rem = len(refs_l)
        floor = self.min_seq
        msn_l: List[int] = []
        m = 0
        for r in refs_l:
            if r < floor:
                break
            cand = r if others_min is None else min(r, others_min)
            if cand > floor:
                floor = cand
            msn_l.append(floor)
            m += 1
        if m == 0:
            return NackMessage(
                self.seq, 400, NackErrorType.BAD_REQUEST,
                f"refSeq {refs_l[0]} below MSN {self.min_seq}",
                client_sequence_number=csn0 + drop,
            )
        msn = np.asarray(msn_l, np.int32)
        entry.client_seq = csn0 + drop + m - 1
        entry.ref_seq = refs_l[m - 1]
        entry.last_seen = now
        seq0 = self.seq + 1
        self.seq += m
        self.min_seq = int(msn_l[-1])
        self._sequenced(now, m, self.min_seq)
        nack = None
        if m < n_rem:
            nack = NackMessage(
                self.seq, 400, NackErrorType.BAD_REQUEST,
                f"refSeq below MSN {self.min_seq}",
                client_sequence_number=csn0 + drop + m,
            )
        return FrameTicket(drop=drop, m=m, seq0=seq0, msn=msn,
                           timestamp=now, trailing_nack=nack)

    # -- internals ------------------------------------------------------------

    def _compute_msn(self) -> int:
        """MSN = min over per-client refSeq; no clients -> current seq
        (deli lambda.ts:929-938). The MSN never regresses."""
        msn = self._refseq_floor() if self.clients else self.seq
        self.min_seq = max(self.min_seq, msn)
        return self.min_seq

    def _refseq_floor(self) -> int:
        return min(c.ref_seq for c in self.clients.values())

    def _sequence_system(self, ty: MessageType, contents) -> SequencedDocumentMessage:
        self.seq += 1
        now = time.time()
        msn = self._compute_msn()
        self._sequenced(now, 1, msn)
        return SequencedDocumentMessage(
            client_id=-1,
            sequence_number=self.seq,
            client_sequence_number=-1,
            reference_sequence_number=-1,
            minimum_sequence_number=msn,
            type=ty,
            contents=contents,
            timestamp=now,
        )

    def checkpoint_dict(self) -> dict:
        """Durable state as a plain dict — the ONE serialization of the
        sequencer (``checkpoint()`` wraps it; deli's hot-path checkpoint
        uses it directly to skip the dataclass allocation per dirty doc).
        Keys mirror :class:`SequencerCheckpoint`'s fields exactly."""
        return {
            "sequence_number": self.seq,
            "minimum_sequence_number": self.min_seq,
            "clients": [c.__dict__.copy() for c in self.clients.values()],
            "next_slot": self._next_slot,
            "free_slots": [list(x) for x in self._free_slots],
            "connection_count": self._conn_count,
        }

    def checkpoint(self) -> SequencerCheckpoint:
        return SequencerCheckpoint(**self.checkpoint_dict())
