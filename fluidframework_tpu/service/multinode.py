"""Multi-node ordering — document placement, failover, fenced epochs.

Reference: ``server/routerlicious/packages/memory-orderer`` —
``LocalNode``/``NodeManager`` (localNode.ts) simulate a cluster of ordering
nodes without real machines: each document's sequencer runs on exactly one
node, placement is a lease in a shared ``ReservationManager``
(reservationManager.ts, ZooKeeper-style per §2.9), and a node crash lets
another node acquire the lease and resume from durable state.

The TPU build's version:

- ``OrderingNode`` hosts per-document sequencer state machines; it must
  hold the document's lease (pure-Python ``ReservationManager`` or the C++
  ``NativeCoordination``, interchangeable) to sequence.
- Durable truth is the shared op log + sequencer checkpoints, both fenced
  by the lease epoch: a paused/stale owner's writes are rejected once a
  takeover bumped the epoch (no split-brain sequencing).
- ``NodeCluster`` is the NodeManager/router: it finds or assigns the owner
  node per document and transparently re-routes after failover; clients
  reconnect exactly as they do after an ordinary disconnect.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

from fluidframework_tpu.protocol.types import (
    DocumentMessage,
    MessageType,
    NackMessage,
    SequencedDocumentMessage,
)
from fluidframework_tpu.service import retry
from fluidframework_tpu.service.pipeline import ReservationManager
from fluidframework_tpu.service.residency import HeatTracker
from fluidframework_tpu.service.sequencer import (
    DocumentSequencer,
    SequencerCheckpoint,
)
from fluidframework_tpu.testing import faults


class FencedOpLog:
    """Shared durable op log with epoch fencing per document: appends carry
    the writer's lease epoch and are rejected below the highest seen (the
    write-side half of fenced takeover; scribe/scriptorium durability)."""

    def __init__(self) -> None:
        self._log: Dict[str, List[SequencedDocumentMessage]] = {}
        self._epochs: Dict[str, int] = {}

    def fence(self, doc_id: str, epoch: int) -> None:
        """Raise the document's epoch floor AT TAKEOVER — before the new
        owner's first append — so a stale owner's next write is rejected
        even in the takeover-to-first-append window."""
        self._epochs[doc_id] = max(self._epochs.get(doc_id, 0), epoch)

    def append(self, doc_id: str, epoch: int, msg: SequencedDocumentMessage) -> bool:
        if epoch < self._epochs.get(doc_id, 0):
            return False  # stale owner fenced off
        self._epochs[doc_id] = epoch
        log = self._log.setdefault(doc_id, [])
        if log and msg.sequence_number <= log[-1].sequence_number:
            # Replay after crash-recovery is idempotent — but only for the
            # SAME message; a different message at an existing seq is a
            # fork attempt and must be rejected loudly.
            idx = msg.sequence_number - log[0].sequence_number
            if idx < 0:
                return False
            existing = log[idx]
            return (
                existing.client_id == msg.client_id
                and existing.client_sequence_number
                == msg.client_sequence_number
                and existing.type == msg.type
            )
        log.append(msg)
        return True

    def read(self, doc_id: str, from_seq: int = 0) -> List[SequencedDocumentMessage]:
        log = self._log.get(doc_id)
        if not log:
            return []
        # Gapless, sorted by construction: index instead of scanning.
        start = max(0, from_seq - log[0].sequence_number + 1)
        return log[start:]

    def truncate(self, doc_id: str, below_seq: int) -> int:
        """Drop ops at or below ``below_seq`` (summary-gated log
        truncation; the reference's scribe protocolHead semantics). The
        caller must ensure no consumer can still need them (acked summary
        covers them AND the MSN has passed them)."""
        log = self._log.get(doc_id)
        if not log:
            return 0
        drop = max(0, min(len(log), below_seq - log[0].sequence_number + 1))
        if drop:
            self._log[doc_id] = log[drop:]
        return drop


class CheckpointTable:
    """Shared sequencer-checkpoint store (the Mongo IDeliState analog),
    epoch-fenced like the log."""

    def __init__(self) -> None:
        self._data: Dict[str, Tuple[int, Optional[dict]]] = {}

    def fence(self, doc_id: str, epoch: int) -> None:
        cur = self._data.get(doc_id)
        if cur is None or epoch > cur[0]:
            self._data[doc_id] = (epoch, cur[1] if cur else None)

    def save(self, doc_id: str, epoch: int, cp: SequencerCheckpoint) -> bool:
        cur = self._data.get(doc_id)
        if cur is not None and epoch < cur[0]:
            return False
        self._data[doc_id] = (epoch, cp.__dict__.copy())
        return True

    def load(self, doc_id: str) -> Optional[SequencerCheckpoint]:
        cur = self._data.get(doc_id)
        return SequencerCheckpoint(**cur[1]) if cur and cur[1] else None


class OrderingNode:
    """One ordering host: sequences the documents it holds leases for."""

    def __init__(
        self,
        name: str,
        reservations,
        op_log: FencedOpLog,
        checkpoints: CheckpointTable,
        lease_ttl_s: float = 5.0,
        checkpoint_every: int = 8,
    ):
        self.name = name
        self.reservations = reservations
        self.op_log = op_log
        self.checkpoints = checkpoints
        self.lease_ttl_s = lease_ttl_s
        self.checkpoint_every = checkpoint_every
        self.alive = True
        self._docs: Dict[str, DocumentSequencer] = {}
        self._epochs: Dict[str, int] = {}
        self._since_cp: Dict[str, int] = {}
        # Load accounting (reference partitionManager.ts:25 — the consumer
        # group rebalances by observed lag/throughput): decayed recent op
        # count per owned document; the cluster's rebalance pass reads and
        # ages these. The accumulator is the shared HeatTracker so the
        # rebalancer and single-node residency score heat identically,
        # and rebalance ordering uses the window-normalized rate() (raw
        # accumulators over-weight aged documents vs brand-new ones).
        self.heat = HeatTracker()

    @property
    def op_rate(self) -> Dict[str, float]:
        """Raw decayed op counts per tracked document — the pre-r19 dict
        shape, kept as a read-only view over the HeatTracker."""
        return {d: self.heat.raw(d) for d in self.heat.docs()}

    # -- placement -----------------------------------------------------------

    def try_own(self, doc_id: str) -> bool:
        """Acquire (or refresh) the document's lease; on first acquisition
        restore the sequencer from the last checkpoint + log tail replay."""
        if not self.alive:
            return False
        if doc_id in self._docs:
            if self.reservations.renew(self.name, doc_id, self.lease_ttl_s):
                return True
            # Lease lost (e.g. while paused): drop local state; the new
            # owner's epoch fences our writes either way.
            del self._docs[doc_id]
            del self._epochs[doc_id]
        epoch = self.reservations.acquire(self.name, doc_id, self.lease_ttl_s)
        if epoch is None:
            return False
        # Fence BEFORE reading state: from this point any writer holding an
        # older epoch (a paused previous owner) is rejected, closing the
        # takeover-to-first-append window.
        self.op_log.fence(doc_id, epoch)
        self.checkpoints.fence(doc_id, epoch)
        cp = self.checkpoints.load(doc_id)
        seq = DocumentSequencer(doc_id, cp)
        # Roll forward through ops sequenced after the checkpoint: the log
        # is the truth, and the replay reconstructs the full deli state —
        # counters, the per-client table (joins/leaves/refSeqs after the
        # checkpoint), slot bookkeeping — exactly as the reference's
        # stateless-replayable lambda resumes from offset (§5.3).
        from fluidframework_tpu.service.sequencer import _ClientEntry

        for m in self.op_log.read(doc_id, from_seq=seq.seq):
            seq.seq = m.sequence_number
            seq.min_seq = max(seq.min_seq, m.minimum_sequence_number)
            if m.type == MessageType.CLIENT_JOIN:
                slot = m.contents["clientId"]
                seq.clients[slot] = _ClientEntry(
                    client_id=slot,
                    ref_seq=m.sequence_number,
                    client_seq=0,
                    mode=m.contents.get("mode", "write"),
                    last_seen=time.time(),
                )
                seq._free_slots = [
                    f for f in seq._free_slots if f[0] != slot
                ]
                seq._next_slot = max(seq._next_slot, slot + 1)
                seq._conn_count = max(
                    seq._conn_count, m.contents.get("connNo", 0)
                )
            elif m.type == MessageType.CLIENT_LEAVE:
                if m.contents in seq.clients:
                    del seq.clients[m.contents]
                    seq._free_slots.append([m.contents, m.sequence_number])
            elif m.client_id >= 0 and m.client_id in seq.clients:
                ent = seq.clients[m.client_id]
                ent.client_seq = max(ent.client_seq, m.client_sequence_number)
                ent.ref_seq = m.reference_sequence_number
        self._docs[doc_id] = seq
        self._epochs[doc_id] = epoch
        self._since_cp[doc_id] = 0
        return True

    def kill(self) -> None:
        """Crash the node: in-memory sequencers vanish; leases lapse."""
        self.alive = False
        self._docs.clear()
        self._epochs.clear()
        self.heat = HeatTracker(decay=self.heat.decay)

    def load(self) -> float:
        """Recent-op load over owned documents (+1 per doc so ownership
        itself weighs: many idle docs still cost catch-up state)."""
        return sum(
            self.heat.raw(d) + 1.0 for d in self._docs
        )

    def release_doc(self, doc_id: str) -> bool:
        """Voluntarily hand a document off (load migration): checkpoint
        the sequencer so the next owner's log replay is short, surrender
        the lease, and forget local state. Any in-flight write of ours
        after the new owner fences is rejected by the epoch, exactly as
        after a crash — no op can be lost or duplicated."""
        if doc_id not in self._docs:
            return False
        self.checkpoints.save(
            doc_id, self._epochs[doc_id], self._docs[doc_id].checkpoint()
        )
        self.reservations.release(self.name, doc_id)
        self._docs.pop(doc_id, None)
        self._epochs.pop(doc_id, None)
        self._since_cp.pop(doc_id, None)
        self.heat.forget(doc_id)
        return True

    # -- sequencing ----------------------------------------------------------

    def _emit(self, doc_id: str, msg: SequencedDocumentMessage) -> bool:
        ok = self.op_log.append(doc_id, self._epochs[doc_id], msg)
        if not ok:
            # Fenced: someone took over. Forget the document.
            self._docs.pop(doc_id, None)
            self._epochs.pop(doc_id, None)
            self.heat.forget(doc_id)
            return False
        self.heat.touch(doc_id)
        self._since_cp[doc_id] = self._since_cp.get(doc_id, 0) + 1
        if self._since_cp[doc_id] >= self.checkpoint_every:
            self.checkpoints.save(
                doc_id, self._epochs[doc_id], self._docs[doc_id].checkpoint()
            )
            self._since_cp[doc_id] = 0
        return True

    def join(self, doc_id: str, mode: str = "write"):
        res = self._docs[doc_id].join(mode)
        if not isinstance(res, NackMessage):
            if not self._emit(doc_id, res):
                raise ConnectionError("lost document lease during join")
        return res

    def leave(self, doc_id: str, client_id: int):
        res = self._docs[doc_id].leave(client_id)
        if res is not None:
            self._emit(doc_id, res)
        return res

    def ticket(self, doc_id: str, client_id: int, msg: DocumentMessage):
        res = self._docs[doc_id].ticket(client_id, msg)
        if res is not None and not isinstance(res, NackMessage):
            if not self._emit(doc_id, res):
                return NackMessage(0, 503, 0, "node lost document lease")
        return res


class NodeCluster:
    """NodeManager: routes documents to their owning node, assigning and
    re-assigning ownership through the reservation lease."""

    def __init__(
        self,
        n_nodes: int = 3,
        clock: Callable[[], float] = time.monotonic,
        reservations=None,
        lease_ttl_s: float = 5.0,
    ):
        self.clock = clock
        self.reservations = (
            reservations
            if reservations is not None
            else ReservationManager(clock)
        )
        self.op_log = FencedOpLog()
        self.checkpoints = CheckpointTable()
        self.nodes: List[OrderingNode] = [
            OrderingNode(
                f"node-{i}", self.reservations, self.op_log, self.checkpoints,
                lease_ttl_s,
            )
            for i in range(n_nodes)
        ]

    def _try_own(self, node: OrderingNode, doc_id: str) -> bool:
        """One ownership attempt through the fault boundary: an injected
        coordination failure (``lease.acquire``/``lease.renew``) counts as
        not-owned and the router retries — the next candidate (or the
        same holder on the election pass) re-attempts, so a transient
        coordination blip never strands a document. A real takeover is
        still epoch-fenced either way."""
        try:
            return node.try_own(doc_id)
        except faults.InjectedFault as e:
            retry.retry_counter().inc(site=e.site, outcome="retry")
            return False

    def owner(self, doc_id: str) -> OrderingNode:
        """The lease-holding node, electing one if none (or the holder is
        dead — its lease must lapse first, which the TTL guarantees)."""
        holder = self.reservations.holder(doc_id)
        if holder is not None:
            node = next((n for n in self.nodes if n.name == holder), None)
            if node is not None and node.alive and self._try_own(node, doc_id):
                return node
        # Assign: spread by a STABLE hash (builtin hash is seed-randomized
        # per process, which would make placement nondeterministic),
        # skipping dead nodes. Two sweeps: a coordination blip on one
        # candidate (an injected acquire/renew fault, or an ack-lost
        # acquire whose lease the same node re-acquires on its second
        # attempt) must not surface as a hard connection error.
        import zlib

        start = zlib.crc32(doc_id.encode()) % len(self.nodes)
        for _sweep in range(2):
            for i in range(len(self.nodes)):
                node = self.nodes[(start + i) % len(self.nodes)]
                if node.alive and self._try_own(node, doc_id):
                    return node
        raise ConnectionError(f"no live node could own {doc_id!r}")

    # -- load-driven rebalancing (VERDICT r2 Missing #3) ---------------------

    def loads(self) -> Dict[str, float]:
        return {n.name: n.load() for n in self.nodes if n.alive}

    def rebalance(
        self, imbalance: float = 2.0, max_moves: int = 4,
        decay: float = 0.5,
    ) -> List[Tuple[str, str, str]]:
        """One load-rebalance pass (the consumer-group rebalance analog,
        partitionManager.ts:25 + session-stickiness knobs config.json:59):
        while the hottest live node carries more than ``imbalance`` x the
        coldest, migrate its busiest documents over via voluntary lease
        surrender + normal epoch-fenced takeover — the same machinery as
        failover, so correctness is inherited (zero lost/duplicated ops).
        Counters age by ``decay`` afterward so bursts fade. Returns the
        migrations performed as (doc, from_node, to_node)."""
        moves: List[Tuple[str, str, str]] = []
        for _ in range(max_moves):
            live = [n for n in self.nodes if n.alive]
            if len(live) < 2:
                break
            hot = max(live, key=lambda n: n.load())
            cold = min(live, key=lambda n: n.load())
            # +1 keeps a zero-load cold node from making the ratio test
            # vacuous, and a node's LAST doc never migrates — moving it
            # cannot improve balance, only relocate the hotspot (and would
            # ping-pong a single busy document between nodes forever).
            if hot is cold or hot.load() <= imbalance * (cold.load() + 1):
                break
            if len(hot._docs) < 2:
                break
            # Pick by the window-normalized rate, not the raw accumulator:
            # raw values only compare between documents of equal age (an
            # aged steady writer holds ~r/(1-decay) while a new one holds
            # its first window's count), so the raw key mis-ranked young
            # hot documents below old lukewarm ones.
            doc_id = max(
                hot._docs, key=lambda d: hot.heat.rate(d)
            )
            # Export heat BEFORE release_doc forgets it: the migrated
            # document keeps its age-normalization on the new owner
            # instead of restarting cold.
            moved_heat = hot.heat.export(doc_id)
            if not hot.release_doc(doc_id):
                break
            if not cold.try_own(doc_id):  # pragma: no cover - cold is live
                # The voluntary surrender went through but the takeover
                # didn't: re-own on the hot node (or via the cluster's
                # normal owner() election) so the document is never left
                # unowned by a failed migration attempt.
                if not hot.try_own(doc_id):
                    self.owner(doc_id)
                break
            cold.heat.adopt(doc_id, *moved_heat)
            moves.append((doc_id, hot.name, cold.name))
        for n in self.nodes:
            n.heat.observe_window(decay)
        return moves


class MultiNodeConnection:
    """Client connection to the cluster: delivery is a watermark over the
    shared op log (the cross-node broadcaster; Redis pub/sub in the
    reference is an optimization over exactly this)."""

    def __init__(self, service: "MultiNodeFluidService", doc_id: str,
                 client_id: int, join_seq: int, conn_no: int):
        self.doc_id = doc_id
        self.client_id = client_id
        self.join_seq = join_seq
        self.conn_no = conn_no
        self.service = service
        self.inbox: List[SequencedDocumentMessage] = []
        self.signals: list = []
        self.nacks: List[NackMessage] = []
        self.on_nack = None
        self.initial_summary: Optional[tuple] = None
        self.delivered_seq = 0

    def submit(self, msg: DocumentMessage) -> None:
        self.service.submit(self.doc_id, self.client_id, msg)

    def submit_signal(self, content) -> None:
        self.service.submit_signal(self.doc_id, self.client_id, content)

    def take_inbox(self, n: Optional[int] = None):
        self.service._deliver(self.doc_id)
        n = len(self.inbox) if n is None else min(n, len(self.inbox))
        out, self.inbox[:] = self.inbox[:n], self.inbox[n:]
        return out

    def disconnect(self) -> None:
        self.service.disconnect(self.doc_id, self.client_id)


class MultiNodeFluidService:
    """LocalFluidService-compatible facade over a NodeCluster: documents
    shard across ordering nodes, survive node failure, and clients never
    see which node sequences them (the alfred/NodeManager routing role)."""

    def __init__(self, n_nodes: int = 3, clock: Callable[[], float] = None,
                 reservations=None, lease_ttl_s: float = 5.0,
                 rebalance_every: int = 256):
        from fluidframework_tpu.service.summary_store import SummaryStore

        self.clock = clock or time.monotonic
        self.cluster = NodeCluster(
            n_nodes, self.clock, reservations, lease_ttl_s
        )
        self.store = SummaryStore()
        self.rooms: Dict[str, List[MultiNodeConnection]] = {}
        self._scribe_state: Dict[str, dict] = {}
        self._signal_counters: Dict[str, int] = {}
        # Load-driven rebalance cadence: a pass every N submitted ops
        # (0 = manual only). Migrations are transparent to clients — the
        # next submit simply routes to the new lease holder.
        self.rebalance_every = rebalance_every
        self._ops_since_rebalance = 0
        self.migrations: List[Tuple[str, str, str]] = []

    # -- service surface -----------------------------------------------------

    def connect(self, doc_id: str, mode: str = "write", from_seq: int = 0):
        node = self.cluster.owner(doc_id)
        res = node.join(doc_id, mode)
        if isinstance(res, NackMessage):
            raise ConnectionError(res.message)
        conn = MultiNodeConnection(
            self, doc_id,
            client_id=res.contents["clientId"],
            join_seq=res.sequence_number,
            conn_no=res.contents.get("connNo", 0),
        )
        scribe = self._scribe_state.get(doc_id)
        if from_seq == 0 and scribe and scribe.get("latest"):
            conn.initial_summary = tuple(scribe["latest"])
            from_seq = scribe["latest"][1]
        self._check_retained(doc_id, from_seq)
        conn.delivered_seq = from_seq
        self.rooms.setdefault(doc_id, []).append(conn)
        self._deliver(doc_id)
        return conn

    def _check_retained(self, doc_id: str, from_seq: int) -> None:
        """Summary-gated truncation may have dropped ops a long-offline
        client would need: resuming below the retained window must fail
        loudly (the reference forces a reload from the latest snapshot)
        rather than silently skipping the gap."""
        log = self.cluster.op_log._log.get(doc_id)
        if log and from_seq + 1 < log[0].sequence_number:
            raise ConnectionError(
                f"resume point {from_seq} is below the retained op window "
                f"(starts at {log[0].sequence_number}); reload the document "
                "from the latest summary"
            )

    def disconnect(self, doc_id: str, client_id: int) -> None:
        self.rooms[doc_id] = [
            c for c in self.rooms.get(doc_id, []) if c.client_id != client_id
        ]
        node = self.cluster.owner(doc_id)
        node.leave(doc_id, client_id)
        self._deliver(doc_id)

    def submit(self, doc_id: str, client_id: int, msg: DocumentMessage) -> None:
        if not any(
            c.client_id == client_id for c in self.rooms.get(doc_id, [])
        ):
            raise ConnectionError(
                f"client {client_id} is not connected to {doc_id!r}"
            )
        self._ops_since_rebalance += 1
        if (
            self.rebalance_every
            and self._ops_since_rebalance >= self.rebalance_every
        ):
            self._ops_since_rebalance = 0
            self.migrations.extend(self.cluster.rebalance())
        node = self.cluster.owner(doc_id)
        res = node.ticket(doc_id, client_id, msg)
        if (
            isinstance(res, NackMessage)
            and res.content_code == 503
            and "lease" in res.message
        ):
            # Lease expired mid-flight: the epoch fence rejected the
            # stale owner's append (the op was never sequenced), so
            # requeue it with the NEW owner — whose log-replay rebuild
            # already carries this client — and it is ticketed exactly
            # once. Never silent: retry_attempts_total{lease.renew,fence}.
            retry.retry_counter().inc(site="lease.renew", outcome="fence")
            from fluidframework_tpu.telemetry import journal

            if journal._ON:
                # The flight recorder keeps the fence itself (which op
                # was rerouted, to which owner) — the counter only says
                # a fence happened somewhere.
                journal.record(
                    "lease.fence", doc=doc_id, client=client_id,
                    csn=msg.client_sequence_number,
                    new_owner=self.cluster.owner(doc_id).name,
                )
            node = self.cluster.owner(doc_id)
            res = node.ticket(doc_id, client_id, msg)
        if isinstance(res, NackMessage):
            for c in self.rooms.get(doc_id, []):
                if c.client_id == client_id:
                    c.nacks.append(res)
                    if c.on_nack:
                        c.on_nack(res)
        elif res is not None and res.type == MessageType.SUMMARIZE:
            self._scribe(doc_id, node, res)
        self._deliver(doc_id)

    def submit_signal(self, doc_id: str, client_id: int, content) -> None:
        from fluidframework_tpu.protocol.types import SignalMessage

        n = self._signal_counters.get(doc_id, 0) + 1
        self._signal_counters[doc_id] = n
        sig = SignalMessage(
            client_id=client_id, client_connection_number=n, content=content
        )
        for c in self.rooms.get(doc_id, []):
            c.signals.append(sig)

    def pump(self) -> None:
        """Bring every connection's inbox up to the shared op log. The
        socket server pumps once a drain tick, ahead of a delivery sweep
        that opens only the connections that hold something."""
        for doc_id in self.rooms:
            self._deliver(doc_id)

    def get_deltas(self, doc_id: str, from_seq: int = 0, to_seq=None):
        self._check_retained(doc_id, from_seq)
        return [
            m
            for m in self.cluster.op_log.read(doc_id, from_seq)
            if to_seq is None or m.sequence_number <= to_seq
        ]

    # -- internals -----------------------------------------------------------

    def _scribe(self, doc_id: str, node: OrderingNode,
                msg: SequencedDocumentMessage) -> None:
        from fluidframework_tpu.service.summary_store import scribe_decide

        st = self._scribe_state.setdefault(
            doc_id, {"protocol_head": 0, "latest": None}
        )
        ok, contents = scribe_decide(msg, st["protocol_head"], self.store)
        if ok:
            st["latest"] = (contents["handle"], contents["head"])
            st["protocol_head"] = msg.sequence_number
        ack = node._docs[doc_id]._sequence_system(
            MessageType.SUMMARY_ACK if ok else MessageType.SUMMARY_NACK,
            contents=contents,
        )
        node._emit(doc_id, ack)
        if ok:
            # Summary-gated log truncation: ops covered by the acked
            # summary AND below the collab window can never be needed again
            # (cold starts load the summary; live refs are >= MSN). Force a
            # fresh checkpoint first so crash-recovery replay never reaches
            # for truncated ops.
            seqr = node._docs[doc_id]
            cut = min(contents["head"], seqr.min_seq)
            if cut > 0:
                self.cluster.checkpoints.save(
                    doc_id, node._epochs[doc_id], seqr.checkpoint()
                )
                self.cluster.op_log.truncate(doc_id, cut)

    def _deliver(self, doc_id: str) -> None:
        for c in self.rooms.get(doc_id, []):
            for m in self.cluster.op_log.read(doc_id, c.delivered_seq):
                c.inbox.append(m)
                c.delivered_seq = m.sequence_number
