"""The read-path fan-out tier (r15): encode-once push broadcast,
batched snapshot gathers, and historian-backed catch-up.

Contracts under test (ISSUE 13 / docs/failure-semantics.md):

- frame/op wire bytes are built exactly ONCE per (doc, entry, sweep)
  regardless of subscriber count (the encode-once contract, shim-pinned
  at 1/10/100 subscribers);
- the batched multi-doc gather is bit-identical to per-doc ``doc_state``
  on the dense AND mesh fleets, and costs exactly ONE device→host
  transfer for N docs (the ``telemetry_slice`` one-readback rule);
- ``read.gather`` faults fall back to per-doc host gathers (counted,
  never a failed read) and ``push.fanout`` faults requeue only the
  failed subscriber's already-encoded tail (exactly-once per socket);
- SHED_READS sheds NEW push subscriptions while existing push sockets
  keep draining;
- 100 real-websocket subscribers each receive every sequenced op once.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from fluidframework_tpu.ops.segment_state import SEGMENT_LANES
from fluidframework_tpu.parallel.fleet import DocFleet, _SCALARS
from fluidframework_tpu.protocol.constants import (
    F_ARG,
    F_LEN,
    F_REF,
    F_SEQ,
    F_TYPE,
    OP_INSERT,
    OP_WIDTH,
)
from fluidframework_tpu.protocol.opframe import OpFrame, SeqFrame
from fluidframework_tpu.protocol.types import (
    MessageType,
    SequencedDocumentMessage,
)
from fluidframework_tpu.service import network_server as ns_mod
from fluidframework_tpu.service import wsproto
from fluidframework_tpu.service.admission import Tier
from fluidframework_tpu.service.device_backend import DeviceFleetBackend
from fluidframework_tpu.service.historian import HistorianReadTier
from fluidframework_tpu.service.network_server import (
    FluidNetworkServer,
    _Session,
)
from fluidframework_tpu.service.pipeline import PipelineFluidService
from fluidframework_tpu.service.summary_store import SummaryStore
from fluidframework_tpu.telemetry import metrics
from fluidframework_tpu.testing import faults

MINT = 1 << 14  # shared_string._MINT_STRIDE (content-id scoping)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _frame(conn, k: int, c0: int, ref: int, ch="x") -> OpFrame:
    origs = [conn.conn_no * MINT + c0 + j for j in range(k)]
    return OpFrame.build(
        "s", ["ins"] * k, [0] * k, origs, [ch] * k, csn0=c0, ref=ref
    )


class _Writer:
    """Duck-typed asyncio writer collecting fan-out bytes in-proc."""

    def __init__(self):
        self.chunks = []

    def write(self, data) -> None:
        self.chunks.append(bytes(data))

    def close(self) -> None:
        pass


def _push_session(server, doc, from_seq=0, frames=False) -> _Session:
    s = _Session(_Writer())
    s.push_doc = doc
    s.push_seq = from_seq
    s.frames_ok = frames
    server._sessions.append(s)
    return s


def _delivered_seqs(writer: _Writer):
    dec = wsproto.FrameDecoder()
    seqs = []
    for opcode, payload in dec.feed(b"".join(writer.chunks)):
        if opcode == wsproto.OP_TEXT:
            m = json.loads(payload.decode())
            if m.get("type") == "op":
                seqs.append(m["msg"]["sequence_number"])
        elif opcode == wsproto.OP_BINARY:
            sf = SeqFrame.decode(payload)
            seqs.extend(range(sf.first_seq, sf.last_seq + 1))
    return seqs


def _retry_total(site, outcome=None) -> float:
    c = metrics.REGISTRY.get("retry_attempts_total")
    if c is None:
        return 0.0
    total = 0.0
    for key, _suffix, value in c.samples():
        d = dict(key)
        if d.get("site") == site and (
            outcome is None or d.get("outcome") == outcome
        ):
            total += value
    return total


# ---------------------------------------------------------------------------
# Encode-once broadcast fan-out


class TestEncodeOnce:
    def _counts(self, monkeypatch, n_subs: int, frames: bool):
        """One sweep's encode-pass counts with n_subs subscribers."""
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        server = FluidNetworkServer(svc)
        conn = svc.connect("doc")
        subs = [
            _push_session(server, "doc", frames=frames)
            for _ in range(n_subs)
        ]
        json_calls = [0]
        frame_calls = [0]
        real_jsonable = ns_mod.to_jsonable
        real_encode = SeqFrame.encode

        def counting_jsonable(m):
            json_calls[0] += 1
            return real_jsonable(m)

        def counting_encode(self):
            frame_calls[0] += 1
            return real_encode(self)

        monkeypatch.setattr(ns_mod, "to_jsonable", counting_jsonable)
        monkeypatch.setattr(SeqFrame, "encode", counting_encode)
        conn.submit_frame(_frame(conn, 4, 1, svc.doc_head("doc")))
        server._drain_all()  # ONE sweep
        monkeypatch.setattr(ns_mod, "to_jsonable", real_jsonable)
        monkeypatch.setattr(SeqFrame, "encode", real_encode)
        return json_calls[0], frame_calls[0], subs

    @pytest.mark.parametrize("frames", [False, True])
    def test_bytes_built_once_per_entry_per_sweep(
        self, monkeypatch, frames
    ):
        """The encode-once contract: encode passes are FLAT across 1, 10
        and 100 subscribers — each entry's wire bytes build once per
        (doc, entry, sweep), then the same bytes write everywhere."""
        j1, f1, s1 = self._counts(monkeypatch, 1, frames)
        j10, f10, s10 = self._counts(monkeypatch, 10, frames)
        j100, f100, s100 = self._counts(monkeypatch, 100, frames)
        assert j1 == j10 == j100, (j1, j10, j100)
        assert f1 == f10 == f100, (f1, f10, f100)
        if frames:
            assert f100 == 1  # the one sequenced frame, encoded once
        else:
            assert f100 == 0
            assert j100 >= 4  # the frame's ops expanded once, not 100x
        # ...and every subscriber still received every sequenced op.
        for subs in (s1, s10, s100):
            for s in subs:
                got = _delivered_seqs(s.writer)
                assert got == sorted(got) and len(got) >= 5, got

    def test_same_bytes_every_subscriber(self):
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        server = FluidNetworkServer(svc)
        conn = svc.connect("doc")
        subs = [
            _push_session(server, "doc", frames=True) for _ in range(10)
        ]
        conn.submit_frame(_frame(conn, 4, 1, svc.doc_head("doc")))
        server._drain_all()
        base = subs[0].writer.chunks
        assert base, "no delivery"
        for s in subs[1:]:
            assert s.writer.chunks == base

    def test_dedupe_across_sweeps_and_watermarks(self):
        """Subscribers at different watermarks each see exactly the ops
        past their own watermark, exactly once, across multiple sweeps —
        one log read per (doc, sweep) notwithstanding."""
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        server = FluidNetworkServer(svc)
        conn = svc.connect("doc")
        conn.submit_frame(_frame(conn, 3, 1, svc.doc_head("doc")))
        head = svc.doc_head("doc")
        early = _push_session(server, "doc", from_seq=0)
        late = _push_session(server, "doc", from_seq=head)
        server._drain_all()
        server._drain_all()  # idle sweep: nothing redelivers
        conn.submit_frame(_frame(conn, 3, 4, svc.doc_head("doc")))
        server._drain_all()
        got_early = _delivered_seqs(early.writer)
        got_late = _delivered_seqs(late.writer)
        assert got_early == sorted(set(got_early)), got_early
        assert got_late == sorted(set(got_late)), got_late
        assert set(got_late) == {
            s for s in got_early if s > head
        }, (got_early, got_late, head)

    def test_group_read_is_one_log_read_per_sweep(self, monkeypatch):
        """N subscribers of one doc cost ONE durable-log read per sweep
        (the fan-out group read), not N per-session reads."""
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        server = FluidNetworkServer(svc)
        conn = svc.connect("doc")
        for _ in range(25):
            _push_session(server, "doc")
        reads = [0]
        real = svc.log_entries

        def counting(*a, **kw):
            reads[0] += 1
            return real(*a, **kw)

        monkeypatch.setattr(svc, "log_entries", counting)
        conn.submit_frame(_frame(conn, 4, 1, svc.doc_head("doc")))
        server._drain_all()
        assert reads[0] == 1, reads


def test_cold_subscriber_catches_up_in_bounded_slices(monkeypatch):
    """A cold subscriber (from_seq=0 against a deep log) streams the
    backlog in bounded per-sweep slices: it neither materializes the
    whole log in one sweep nor drags the caught-up group's shared read
    back to watermark zero."""
    svc = PipelineFluidService(n_partitions=1, device_backend=False)
    server = FluidNetworkServer(svc)
    server.PUSH_CATCHUP_SPAN = 4
    conn = svc.connect("doc")
    for r in range(3):
        conn.submit_frame(_frame(conn, 4, r * 4 + 1, svc.doc_head("doc")))
    head = svc.doc_head("doc")
    assert head >= 13
    near = _push_session(server, "doc", from_seq=head)
    cold = _push_session(server, "doc", from_seq=0)
    windows = []
    real = svc.log_entries

    def watching(doc, lo, hi):
        windows.append((lo, hi))
        return real(doc, lo, hi)

    monkeypatch.setattr(svc, "log_entries", watching)
    server._drain_all()
    first = _delivered_seqs(cold.writer)
    # One bounded slice (a frame straddling the slice edge delivers
    # whole — frames are atomic — so the bound is frame-granular).
    assert first and max(first) < head, first
    assert _delivered_seqs(near.writer) == []  # near group undisturbed
    for _ in range(6):
        server._drain_all()
    got = _delivered_seqs(cold.writer)
    assert got == sorted(set(got)) and got[-1] == head, got
    assert all(hi - lo + 1 <= 4 for lo, hi in windows), windows


class _MinimalService:
    """A service exposing ONLY get_deltas — no head probe, no ranged
    lookup, no frames (the regression surface the r12-era per-session
    scan gate served)."""

    def __init__(self):
        self.log = []

    def append(self, seq: int):
        self.log.append(SequencedDocumentMessage(
            client_id=0,
            sequence_number=seq,
            client_sequence_number=seq,
            reference_sequence_number=0,
            minimum_sequence_number=0,
            type=MessageType.OPERATION,
            contents={"address": "s", "contents": {}},
        ))

    def get_deltas(self, doc_id, from_seq=0, to_seq=None):
        return [m for m in self.log if m.sequence_number > from_seq]


def test_no_head_probe_service_streams_via_group_scan(monkeypatch):
    """Satellite regression: a service without ops_range/doc_head still
    serves push subscribers — ONE full-log get_deltas scan per (doc,
    sweep) for the whole group, and the old per-session
    ``push_scan_tick`` gating is gone (delivery no longer waits 8
    ticks)."""
    svc = _MinimalService()
    server = FluidNetworkServer(svc)
    subs = [_push_session(server, "d") for _ in range(5)]
    for seq in (1, 2, 3):
        svc.append(seq)
    scans = [0]
    real = svc.get_deltas

    def counting(*a, **kw):
        scans[0] += 1
        return real(*a, **kw)

    monkeypatch.setattr(svc, "get_deltas", counting)
    server._drain_all()  # FIRST sweep: everything delivers immediately
    for s in subs:
        assert _delivered_seqs(s.writer) == [1, 2, 3]
        assert not hasattr(s, "push_scan_tick")
    assert scans[0] == 1, scans  # one group scan, not one per session


# ---------------------------------------------------------------------------
# push.fanout chaos: per-subscriber requeue tails


class TestPushFanoutFaults:
    def _setup(self, n_subs=3):
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        server = FluidNetworkServer(svc)
        conn = svc.connect("doc")
        subs = [_push_session(server, "doc") for _ in range(n_subs)]
        return svc, server, conn, subs

    def test_fail_requeues_only_that_subscribers_tail(self):
        svc, server, conn, subs = self._setup()
        conn.submit_frame(_frame(conn, 3, 1, svc.doc_head("doc")))
        pre = _retry_total("push.fanout", "requeue")
        faults.arm("push.fanout", faults.FailN(1))
        server._drain_all()
        # The FIRST subscriber's first write failed: its already-encoded
        # tail requeued; the other subscribers drained fully.
        assert subs[0].push_tail, "failed subscriber kept no tail"
        assert _delivered_seqs(subs[0].writer) == []
        expect = _delivered_seqs(subs[1].writer)
        assert len(expect) >= 4
        assert _delivered_seqs(subs[2].writer) == expect
        assert _retry_total("push.fanout", "requeue") == pre + 1
        faults.disarm()
        server._drain_all()  # the tail drains — no re-read, no dup
        assert subs[0].push_tail == []
        assert _delivered_seqs(subs[0].writer) == expect

    def test_crash_after_is_exactly_once(self):
        """A crash AFTER a fan-out write: that payload reached the
        socket — the watermark advances past it and only the REMAINDER
        requeues, so the subscriber sees every op exactly once."""
        svc, server, conn, subs = self._setup(n_subs=2)
        conn.submit_frame(_frame(conn, 3, 1, svc.doc_head("doc")))
        faults.arm("push.fanout", faults.CrashAt("after", times=1))
        server._drain_all()
        faults.disarm()
        server._drain_all()
        expect = _delivered_seqs(subs[1].writer)
        got = _delivered_seqs(subs[0].writer)
        # Exactly once: the crashed-after write is NOT redelivered.
        assert got == expect, (got, expect)
        assert got == sorted(set(got))

    def test_stalled_subscriber_does_not_drag_group_watermark(
        self, monkeypatch
    ):
        """A subscriber with a requeued tail rides its tail, NOT the
        group read: the group's minimum watermark (and therefore the
        shared log read) never rewinds for a stalled socket."""
        svc, server, conn, subs = self._setup(n_subs=2)
        conn.submit_frame(_frame(conn, 3, 1, svc.doc_head("doc")))
        faults.arm("push.fanout", faults.FailN(1))
        server._drain_all()
        faults.disarm()
        assert subs[0].push_tail
        lows = []
        real = svc.log_entries

        def watching(doc, lo, hi):
            lows.append(lo)
            return real(doc, lo, hi)

        monkeypatch.setattr(svc, "log_entries", watching)
        conn.submit_frame(_frame(conn, 2, 4, svc.doc_head("doc")))
        server._drain_all()
        # The group read started past the healthy subscribers' shared
        # watermark — not at the stalled subscriber's 0.
        assert lows and min(lows) > 1, lows
        assert _delivered_seqs(subs[0].writer) == _delivered_seqs(
            subs[1].writer
        )


# ---------------------------------------------------------------------------
# Batched snapshot gathers


def _filled_fleet(mesh=None, n_docs=8, capacity=32):
    fleet = DocFleet(n_docs, capacity, mesh=mesh)
    k = 4
    for r in range(2):
        ops = np.zeros((n_docs, k, OP_WIDTH), np.int32)
        ops[:, :, F_TYPE] = OP_INSERT
        ops[:, :, F_LEN] = 1
        ops[:, :, F_SEQ] = r * k + 1 + np.arange(k)
        ops[:, :, F_ARG] = (
            np.arange(n_docs)[:, None] * 100 + r * k + 1 + np.arange(k)
        )
        fleet.apply(ops)
    return fleet


def _assert_state_equal(a, b, ctx=""):
    for name, x, y in zip(a._fields, a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            ctx, name, x, y
        )


class TestBatchedGather:
    def test_bit_parity_dense(self):
        fleet = _filled_fleet()
        docs = list(range(8))
        batched = fleet.doc_states(docs)
        for d in docs:
            _assert_state_equal(batched[d], fleet.doc_state(d), f"doc{d}")

    def test_bit_parity_across_pools(self):
        """Docs spanning two capacity tiers (one promoted) still gather
        in one batch, bit-identical per doc."""
        fleet = _filled_fleet(n_docs=4, capacity=8)
        # Push doc 0 over the high-water mark and promote it.
        k = 8
        ops = np.zeros((4, k, OP_WIDTH), np.int32)
        ops[0, :, F_TYPE] = OP_INSERT
        ops[0, :, F_LEN] = 1
        ops[0, :, F_SEQ] = 9 + np.arange(k)
        ops[0, :, F_ARG] = 900 + np.arange(k)
        fleet.apply(ops)
        assert fleet.check_and_migrate(), "expected a promotion"
        assert len(fleet.pools) > 1
        docs = list(range(4))
        batched = fleet.doc_states(docs)
        for d in docs:
            _assert_state_equal(batched[d], fleet.doc_state(d), f"doc{d}")

    def test_bit_parity_mesh(self):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()), ("docs",))
        fleet = _filled_fleet(mesh=mesh)
        docs = list(range(8))
        batched = fleet.doc_states(docs)
        for d in docs:
            _assert_state_equal(batched[d], fleet.doc_state(d), f"doc{d}")

    def test_one_readback_regardless_of_doc_count(self, monkeypatch):
        """The one-readback contract (the telemetry_slice rule on the
        read path): N docs' batched gather performs EXACTLY ONE
        device→host transfer."""
        from fluidframework_tpu.parallel import fleet as fleet_mod

        fleet = _filled_fleet()
        transfers = []
        real_np = fleet_mod.np

        class _CountingNp:
            def __getattr__(self, name):
                return getattr(np, name)

            @staticmethod
            def asarray(*a, **kw):
                if a and isinstance(a[0], jax.Array):
                    transfers.append("asarray")
                return real_np.asarray(*a, **kw)

            @staticmethod
            def array(*a, **kw):
                if a and isinstance(a[0], jax.Array):
                    transfers.append("array")
                return real_np.array(*a, **kw)

        monkeypatch.setattr(fleet_mod, "np", _CountingNp())
        for n in (1, 4, 8):
            before = len(transfers)
            fleet.doc_states(list(range(n)))
            assert len(transfers) - before == 1, transfers[before:]

    def test_backend_read_gather_fault_falls_back(self):
        """read.gather chaos: a faulted batched gather serves the batch
        through per-doc host gathers — same states, counted fallback,
        never a failed read."""
        be = DeviceFleetBackend(capacity=64)
        k = 4
        rows = np.zeros((3, k, OP_WIDTH), np.int32)
        rows[:, :, F_TYPE] = OP_INSERT
        rows[:, :, F_LEN] = 1
        rows[:, :, F_SEQ] = 1 + np.arange(k)
        rows[:, :, F_ARG] = 1 + np.arange(k)
        for i in range(3):
            be.enqueue_frame(
                f"d{i}", SeqFrame("s", 0, 1, rows[i], (), 0.0)
            )
        be.flush()
        keys = [(f"d{i}", "s") for i in range(3)]
        want = {key: be._doc_state(be._index[key]) for key in keys}
        for kind in ("fail", "crash_before", "crash_after"):
            pre = _retry_total("read.gather", "fallback")
            pre_fb = be.read_gather_fallbacks
            faults.arm("read.gather", (
                faults.FailN(1) if kind == "fail"
                else faults.CrashAt(kind.split("_")[1], times=1)
            ))
            got = be.doc_states(keys)
            faults.disarm()
            for key in keys:
                _assert_state_equal(got[key], want[key], f"{kind}/{key}")
            assert be.read_gather_fallbacks == pre_fb + 1
            assert _retry_total("read.gather", "fallback") == pre + 1

    def test_amortization_counter(self):
        be = DeviceFleetBackend(capacity=64)
        k = 4
        rows = np.zeros((4, k, OP_WIDTH), np.int32)
        rows[:, :, F_TYPE] = OP_INSERT
        rows[:, :, F_LEN] = 1
        rows[:, :, F_SEQ] = 1 + np.arange(k)
        rows[:, :, F_ARG] = 1 + np.arange(k)
        for i in range(4):
            be.enqueue_frame(
                f"d{i}", SeqFrame("s", 0, 1, rows[i], (), 0.0)
            )
        be.flush()
        be.doc_states([(f"d{i}", "s") for i in range(4)])
        assert be.reads_served == 4 and be.read_gathers == 1
        assert be.reads_per_device_dispatch == 4.0
        assert be.stats()["reads_per_device_dispatch"] == 4.0

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_docshard_batched_parity(self, backend):
        """The mesh DocShard (both engines) grows the same one-readback
        multi-doc gather, bit-identical per doc to the full state."""
        from fluidframework_tpu.parallel.mesh import DocShard

        shard = DocShard(8, 32, backend=backend)
        k = 4
        ops = np.zeros((8, k, OP_WIDTH), np.int32)
        ops[:, :, F_TYPE] = OP_INSERT
        ops[:, :, F_LEN] = 1
        ops[:, :, F_SEQ] = 1 + np.arange(k)
        ops[:, :, F_ARG] = (
            np.arange(8)[:, None] * 100 + 1 + np.arange(k)
        )
        shard.apply(ops)
        full = shard.unpacked_state()
        batched = shard.doc_states([1, 5, 6])
        for d in (1, 5, 6):
            for i, lane in enumerate(SEGMENT_LANES):
                assert np.array_equal(
                    np.asarray(batched[d][i]),
                    np.asarray(getattr(full, lane)[d]),
                ), (d, lane)
            for s in _SCALARS:
                assert int(getattr(batched[d], s)) == int(
                    np.asarray(getattr(full, s))[d]
                ), (d, s)


# ---------------------------------------------------------------------------
# Historian-backed catch-up


class _FakeLogService:
    """ops_range/doc_head/get_deltas over a fixed sequenced log, with a
    pump() that must never be called (the read tier's contract)."""

    def __init__(self, n: int):
        self.store = SummaryStore()
        self.pumps = 0
        self.range_reads = 0
        self._log = {}
        for seq in range(1, n + 1):
            self._log[seq] = SequencedDocumentMessage(
                client_id=0,
                sequence_number=seq,
                client_sequence_number=seq,
                reference_sequence_number=0,
                minimum_sequence_number=0,
                type=MessageType.OPERATION,
                contents={"address": "s", "contents": {"seq": seq}},
            )

    def pump(self):
        self.pumps += 1

    def doc_head(self, doc_id):
        return max(self._log) if self._log else 0

    def ops_range(self, doc_id, from_seq, to_seq, pump=True):
        if pump:
            self.pump()
        self.range_reads += 1
        return [
            self._log[s]
            for s in range(from_seq, to_seq + 1)
            if s in self._log
        ]

    def latest_summary_pointer(self, doc_id):
        return getattr(self, "_ptr", None)


class TestHistorianReadTier:
    def test_chunked_deltas_cache_and_counters(self):
        svc = _FakeLogService(600)
        rt = HistorianReadTier(svc, chunk=256)
        pre_h = metrics.REGISTRY.counter(
            "read_cache_hits_total", labelnames=("tier",)
        ).value(tier="deltas")
        cold = rt.deltas_payload("doc", from_seq=0)
        got = json.loads(cold.decode())
        assert [m["sequence_number"] for m in got] == list(range(1, 601))
        assert rt.misses == 2 and rt.hits == 0  # two full chunks built
        warm = rt.deltas_payload("doc", from_seq=0)
        assert warm == cold
        assert rt.hits == 2
        assert metrics.REGISTRY.counter(
            "read_cache_hits_total", labelnames=("tier",)
        ).value(tier="deltas") == pre_h + 2
        # And the whole thing never pumped the sequencing loop.
        assert svc.pumps == 0

    def test_range_edges_encode_fresh(self):
        svc = _FakeLogService(300)
        rt = HistorianReadTier(svc, chunk=256)
        got = json.loads(
            rt.deltas_payload("doc", from_seq=100, to_seq=280).decode()
        )
        assert [m["sequence_number"] for m in got] == list(
            range(101, 281)
        )
        assert rt.hits == rt.misses == 0  # edges only: nothing cached
        assert svc.pumps == 0

    def test_latest_summary_rides_the_cache(self):
        svc = _FakeLogService(1)
        rt = HistorianReadTier(svc)
        assert rt.latest_summary("doc") is None
        handle = svc.store.put_summary(
            {"seq": 1, "channels": {"c": {"x": 1}}}
        )
        svc._ptr = (handle, 1)
        first = rt.latest_summary("doc")
        assert first == svc.store.get_summary(handle)
        assert rt.misses == 1
        again = rt.latest_summary("doc")
        assert again == first and rt.hits == 1
        # A newer summary invalidates the inflated copy.
        handle2 = svc.store.put_summary(
            {"seq": 2, "channels": {"c": {"x": 2}}}
        )
        svc._ptr = (handle2, 2)
        assert rt.latest_summary("doc") == svc.store.get_summary(handle2)
        assert rt.misses == 2

    def test_pipeline_rest_deltas_ride_the_tier(self):
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        srv = FluidNetworkServer(svc)
        srv.start()
        try:
            conn = svc.connect("doc")
            conn.submit_frame(_frame(conn, 4, 1, svc.doc_head("doc")))
            # Shrink the chunk so this test-sized log spans full chunks
            # (a production log dwarfs the 256-op default).
            svc.read_tier.chunk = 2
            pre = svc.read_tier.hits + svc.read_tier.misses

            def get(path):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}{path}", timeout=5
                ) as r:
                    return json.loads(r.read().decode())

            a = get("/deltas/doc")
            b = get("/deltas/doc")
            assert a == b and len(a) >= 5
            assert svc.read_tier.hits + svc.read_tier.misses > pre
            seqs = [m["sequence_number"] for m in a]
            assert seqs == sorted(seqs)
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# The r17 writer-loop offload: push byte writes on the drainer thread


class TestWriterLoopOffload:
    """ROADMAP read-path remainder, shipped r17: once a push
    subscriber's raw socket is attached, its byte writes run on the
    server's drainer thread — the asyncio loop only forms/encodes. The
    r11/r15 exactly-once and requeue-tail contracts are re-pinned here
    THROUGH the drainer (the push.fanout matrix now injects on the
    drainer thread)."""

    def _drive(self, srv, sock, dec, want_n, deadline_s=15.0):
        """Read delivered op seqs, nudging sweeps with pings (the
        drain sweep fires on inbound socket traffic)."""
        got = []
        sock.settimeout(0.2)
        deadline = time.monotonic() + deadline_s
        while len(got) < want_n and time.monotonic() < deadline:
            try:
                data = sock.recv(65536)
            except TimeoutError:
                sock.sendall(wsproto.encode_frame(
                    wsproto.OP_PING, b"", mask=True
                ))
                continue
            if not data:
                break
            for opcode, payload in dec.feed(data):
                if opcode == wsproto.OP_TEXT:
                    m = json.loads(payload.decode())
                    if m.get("type") == "op":
                        got.append(m["msg"]["sequence_number"])
                elif opcode == wsproto.OP_BINARY:
                    sf = SeqFrame.decode(payload)
                    got.extend(range(sf.first_seq, sf.last_seq + 1))
        return got

    def _subscribed(self, srv, port, doc):
        sock, dec, _p = _ws_connect(port)
        _subscribe_push(sock, doc)
        sock.settimeout(5)
        while True:
            done = False
            for opcode, payload in dec.feed(sock.recv(65536)):
                if opcode == wsproto.OP_TEXT:
                    m = json.loads(payload.decode())
                    if m.get("type") == "subscribe_push_success":
                        done = True
            if done:
                return sock, dec

    def test_push_writes_run_on_drainer_thread(self):
        """The offload itself: delivered push bytes were written by the
        drainer thread, not the loop thread — and delivery is complete
        and in order."""
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        srv = FluidNetworkServer(svc)
        srv.start()
        sock = None
        try:
            conn = svc.connect("off")
            sock, dec = self._subscribed(srv, srv.port, "off")
            head = svc.doc_head("off")
            conn.submit_frame(_frame(conn, 4, 1, head))
            got = self._drive(srv, sock, dec, want_n=4)
            assert len(got) >= 4 and got == sorted(got), got
            # The drainer actually wrote: its thread set is non-empty
            # and disjoint from the socket loop's thread.
            dr = srv._push_drainer
            # The bytes arrive before the drainer counts the batch: wait
            # for its queue to drain, not for luck under a loaded run.
            assert dr.join(5.0)
            assert dr.batches >= 1
            assert dr.threads, "no write ran on the drainer"
            assert srv._thread.ident not in dr.threads
            # The raw socket was attached (the offload path, not the
            # inline fallback).
            sess = [s for s in srv._sessions if s.push_doc == "off"]
            assert sess and sess[0].push_sock is not None
        finally:
            if sock is not None:
                sock.close()
            srv.stop()

    def test_offload_fail_requeues_tail_then_delivers(self):
        """push.fanout FailN through the drainer: the failed
        subscriber's already-encoded tail requeues (counted) and drains
        on a later sweep — every op delivered exactly once."""
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        srv = FluidNetworkServer(svc)
        srv.start()
        sock = None
        try:
            conn = svc.connect("offf")
            sock, dec = self._subscribed(srv, srv.port, "offf")
            pre = _retry_total("push.fanout", "requeue")
            faults.arm("push.fanout", faults.FailN(1))
            conn.submit_frame(_frame(conn, 3, 1, svc.doc_head("offf")))
            got = self._drive(srv, sock, dec, want_n=3)
            faults.disarm()
            if len(got) < 3:  # the tail drains after disarm at latest
                got.extend(self._drive(srv, sock, dec, want_n=3 - len(got)))
            assert len(got) >= 3, got
            assert got == sorted(set(got)), got  # exactly once, in order
            assert _retry_total("push.fanout", "requeue") >= pre + 1
        finally:
            faults.disarm()
            if sock is not None:
                sock.close()
            srv.stop()

    def test_offload_crash_after_is_exactly_once(self):
        """push.fanout crash-AFTER through the drainer: the crashed
        write reached the socket — the watermark advances past it and
        the client sees NO duplicate (the r11 exactly-once rule, now on
        the drainer thread)."""
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        srv = FluidNetworkServer(svc)
        srv.start()
        sock = None
        try:
            conn = svc.connect("offc")
            sock, dec = self._subscribed(srv, srv.port, "offc")
            faults.arm("push.fanout", faults.CrashAt("after", times=1))
            conn.submit_frame(_frame(conn, 3, 1, svc.doc_head("offc")))
            got = self._drive(srv, sock, dec, want_n=3)
            faults.disarm()
            if len(got) < 3:
                got.extend(self._drive(srv, sock, dec, want_n=3 - len(got)))
            assert len(got) >= 3, got
            assert got == sorted(set(got)), got  # no dup, no gap
        finally:
            faults.disarm()
            if sock is not None:
                sock.close()
            srv.stop()

    def test_partial_stall_requeues_payload_suffix(self):
        """A bounded-write stall mid-payload must requeue the UNSENT
        SUFFIX bytes (same seq), never the whole payload — a full
        resend after a delivered prefix would tear the subscriber's
        frame stream. Driven on a real socketpair with a tiny send
        buffer so the kernel genuinely stalls the write."""
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        srv = FluidNetworkServer(svc)
        srv.PUSH_WRITE_TIMEOUT_S = 0.05
        a, b = socket.socketpair()
        try:
            a.setblocking(False)
            a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8192)
            s = _Session(_Writer())
            s.push_doc = "p"
            s.push_sock = a
            payload = bytes(range(256)) * 4096  # ~1MB >> SO_SNDBUF
            srv._push_send_sync(s, [(7, payload, False)])
            assert s.push_tail, "stalled write kept no tail"
            assert s.push_seq == 0  # watermark held below the payload
            seq, rest, _binary = s.push_tail[0]
            assert seq == 7
            assert 0 < len(rest) < len(payload), (
                "tail must be the unsent suffix, not the whole payload"
            )
            # Drain the peer while retrying the tail: the bytes that
            # arrive must reassemble EXACTLY the original payload.
            got = bytearray()
            b.setblocking(False)
            deadline = time.monotonic() + 10
            while s.push_tail and time.monotonic() < deadline:
                try:
                    got += b.recv(1 << 20)
                except BlockingIOError:
                    time.sleep(0.005)
                tail, s.push_tail = s.push_tail, []
                srv._push_send_sync(s, tail)
            deadline = time.monotonic() + 5
            while len(got) < len(payload) and time.monotonic() < deadline:
                try:
                    got += b.recv(1 << 20)
                except BlockingIOError:
                    time.sleep(0.005)
            assert bytes(got) == payload, (
                f"stream reassembled {len(got)} bytes != {len(payload)}"
            )
            assert s.push_seq == 7  # watermark advanced once complete
        finally:
            a.close()
            b.close()

    def test_busy_session_never_drags_group_or_double_enqueues(self):
        """While a batch is in flight on the drainer the sweep skips the
        session (no concurrent state access, no duplicate batch) and
        the group read never rewinds to its watermark."""
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        srv = FluidNetworkServer(svc)
        conn = svc.connect("busy")
        s = _push_session(server=srv, doc="busy")
        conn.submit_frame(_frame(conn, 3, 1, svc.doc_head("busy")))
        s.push_busy = True  # batch in flight on the drainer
        srv._drain_all()
        assert _delivered_seqs(s.writer) == []  # untouched while busy
        s.push_busy = False
        srv._drain_all()
        got = _delivered_seqs(s.writer)
        assert len(got) >= 3 and got == sorted(set(got)), got


# ---------------------------------------------------------------------------
# The server read path: batched REST snapshot reads + SHED_READS


def _ws_connect(port):
    sock = socket.create_connection(("127.0.0.1", port), timeout=10)
    req, _exp = wsproto.client_handshake(f"127.0.0.1:{port}", "/socket")
    sock.sendall(req)
    buf = b""
    while wsproto.read_http_head(buf) is None:
        buf += sock.recv(65536)
    _status, _headers, rest = wsproto.read_http_head(buf)
    dec = wsproto.FrameDecoder()
    pending = list(dec.feed(rest))
    return sock, dec, pending


def _subscribe_push(sock, doc, from_seq=0):
    sock.sendall(wsproto.encode_frame(
        wsproto.OP_TEXT,
        json.dumps({
            "type": "subscribe_push", "doc": doc, "from_seq": from_seq,
        }).encode(),
        mask=True,
    ))


class TestServerReadPath:
    def test_batched_rest_reads_amortize_device_dispatches(self):
        """N concurrent REST channel reads coalesce into ONE batched
        device gather (reads_per_device_dispatch > 1) and each returns
        the same text the per-doc path serves."""
        svc = PipelineFluidService(
            n_partitions=1, device_feed_deadline_ms=60.0,
        )
        srv = FluidNetworkServer(svc)
        srv.start()
        try:
            docs = [f"rd{i}" for i in range(6)]
            for i, d in enumerate(docs):
                conn = svc.connect(d)
                conn.submit_frame(OpFrame.build(
                    "s", ["ins"] * 3, [0] * 3,
                    [conn.conn_no * MINT + 1 + j for j in range(3)],
                    [chr(ord("a") + i)] * 3, csn0=1,
                    ref=svc.doc_head(d),
                ))
            svc.flush_device()
            want = {d: svc.device.text(d, "s") for d in docs}
            pre_gathers = svc.device.read_gathers
            results = {}

            def fetch(d):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}"
                    f"/documents/{d}/channels/s",
                    timeout=10,
                ) as r:
                    results[d] = json.loads(r.read().decode())["text"]

            threads = [
                threading.Thread(target=fetch, args=(d,)) for d in docs
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(15)
            assert results == want
            # The whole burst cost far fewer device gathers than reads:
            # the amortization the artifact gates on.
            gathers = svc.device.read_gathers - pre_gathers
            assert 1 <= gathers < len(docs), gathers
            assert svc.device.reads_per_device_dispatch > 1.0
            assert srv.read_batches >= 1
        finally:
            srv.stop()

    def test_shed_reads_blocks_new_subs_existing_keep_draining(self):
        """SHED_READS × push: a NEW subscription is shed with a
        retry-after; the EXISTING push socket keeps receiving ops (shed
        gates admission to the read tier, not delivery already
        admitted)."""
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        srv = FluidNetworkServer(svc)
        srv.start()
        sock = sock2 = None
        try:
            conn = svc.connect("sheddoc")
            sock, dec, _pending = _ws_connect(srv.port)
            _subscribe_push(sock, "sheddoc")
            # The subscription must be ADMITTED before the tier flips —
            # otherwise it is the new subscription being shed.
            sock.settimeout(5)
            admitted = False
            while not admitted:
                for opcode, payload in dec.feed(sock.recv(65536)):
                    if opcode == wsproto.OP_TEXT:
                        m = json.loads(payload.decode())
                        if m.get("type") == "subscribe_push_success":
                            admitted = True
                        else:
                            # catch-up ops racing the ack are fine
                            assert m.get("type") == "op"
            svc.overload.force(Tier.SHED_READS)
            # NEW subscription on a fresh socket: shed with retry-after.
            sock2, dec2, _p2 = _ws_connect(srv.port)
            _subscribe_push(sock2, "sheddoc")
            sock2.settimeout(5)
            shed = None
            buf_deadline = time.monotonic() + 10
            while shed is None and time.monotonic() < buf_deadline:
                for opcode, payload in dec2.feed(sock2.recv(65536)):
                    if opcode == wsproto.OP_TEXT:
                        m = json.loads(payload.decode())
                        if m.get("type") == "subscribe_push_error":
                            shed = m
            assert shed is not None and "shed" in shed["error"]
            assert shed["retry_after_ms"] > 0
            # The EXISTING subscriber still drains newly sequenced ops.
            conn.submit_frame(_frame(conn, 3, 1, svc.doc_head("sheddoc")))
            got = []
            sock.settimeout(0.3)
            deadline = time.monotonic() + 15
            while len(got) < 3 and time.monotonic() < deadline:
                try:
                    data = sock.recv(65536)
                except TimeoutError:
                    sock.sendall(wsproto.encode_frame(
                        wsproto.OP_PING, b"", mask=True
                    ))
                    continue
                if not data:
                    break
                for opcode, payload in dec.feed(data):
                    if opcode == wsproto.OP_TEXT:
                        m = json.loads(payload.decode())
                        if m.get("type") == "op":
                            got.append(m["msg"]["sequence_number"])
            assert len(got) >= 3, got
            svc.overload.force(Tier.NORMAL)
        finally:
            for s in (sock, sock2):
                if s is not None:
                    s.close()
            srv.stop()

    def test_100_subscriber_delivery(self):
        """100 real-websocket push subscribers on one doc each receive
        every sequenced op exactly once, in order — one log read and one
        encode per sweep serving the whole fan-out group."""
        import select

        n_subs = 100
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        srv = FluidNetworkServer(svc)
        srv.start()
        socks = []
        by_fd = {}
        try:
            conn = svc.connect("fan")
            for _ in range(n_subs):
                sock, dec, _pending = _ws_connect(srv.port)
                _subscribe_push(sock, "fan")
                entry = (sock, dec, [])
                socks.append(entry)
                by_fd[sock] = entry
            conn.submit_frame(_frame(conn, 4, 1, svc.doc_head("fan")))
            head = svc.doc_head("fan")
            assert head >= 5
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                undone = [
                    s for s, _dec, got in socks
                    if not (got and got[-1] >= head)
                ]
                if not undone:
                    break
                rlist, _w, _x = select.select(undone, [], [], 0.25)
                if not rlist:
                    # Tickle the drain tick (delivery rides it).
                    socks[0][0].sendall(wsproto.encode_frame(
                        wsproto.OP_PING, b"", mask=True
                    ))
                    continue
                for sock in rlist:
                    _s, dec, got = by_fd[sock]
                    data = sock.recv(65536)
                    if not data:
                        continue
                    for opcode, payload in dec.feed(data):
                        if opcode == wsproto.OP_TEXT:
                            m = json.loads(payload.decode())
                            if m.get("type") == "op":
                                got.append(
                                    m["msg"]["sequence_number"]
                                )
            for _sock, _dec, got in socks:
                assert got == sorted(set(got)), got[:10]
                assert got and got[-1] >= head, (len(got), head)
        finally:
            for sock, _dec, _got in socks:
                sock.close()
            srv.stop()
