"""Chaos suite: the seeded fault matrix over every named injection site.

The r11 robustness contract (docs/failure-semantics.md): with a fault
injected at any stage boundary the trace spine names — store append, queue
send, pump stage/feed/dispatch, websocket delivery, lease acquire/renew — the
pipeline's wired recovery (retry / fallback / requeue / drain / fence)
must reproduce the un-faulted run BIT-IDENTICALLY: same device text, same
device lane state, same sequenced-op identity list, zero lost and zero
duplicate sequenced ops. And no recovery is silent: every cell asserts
its ``retry_attempts_total{site,outcome}`` /
``faults_injected_total{site,kind}`` increments.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.ops.segment_state import SegmentState
from fluidframework_tpu.protocol.constants import (
    F_ARG,
    F_LEN,
    F_REF,
    F_SEQ,
    F_TYPE,
    MAX_WRITERS,
    OP_INSERT,
    OP_WIDTH,
)
from fluidframework_tpu.protocol.opframe import OpFrame, SeqFrame
from fluidframework_tpu.protocol.types import (
    DocumentMessage,
    MessageType,
    NackErrorType,
    NackMessage,
)
from fluidframework_tpu.service.device_backend import DeviceFleetBackend
from fluidframework_tpu.service.multinode import MultiNodeFluidService
from fluidframework_tpu.service.pipeline import PipelineFluidService
from fluidframework_tpu.telemetry import metrics
from fluidframework_tpu.testing import faults

MINT = 1 << 14  # shared_string._MINT_STRIDE: content ids scope per conn_no
ALPHA = "abcdefghijklmnopqrstuvwxyz"


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _flight_recorder(tmp_path):
    """The chaos harness's artifact contract (r14): the flight recorder
    dumps into the test artifact dir on any parity failure (and on the
    fatal/exhausted outcomes the matrix provokes), so "bit-exact
    assertion failed" ships with the event stream that explains it."""
    import os

    from fluidframework_tpu.telemetry import journal

    journal.enable()
    journal.configure(
        dump_dir=os.environ.get("TEST_ARTIFACT_DIR") or str(tmp_path)
    )
    journal.reset()
    yield
    journal.JOURNAL.dump_dir = None
    journal.reset()


def _assert_parity(state, ref, label):
    """Bit-exact post-recovery parity, with the r14 post-mortem: a miss
    auto-dumps the journal before failing the test."""
    if state != ref:
        from fluidframework_tpu.telemetry import journal

        path = journal.auto_dump("chaos-parity")
        raise AssertionError(
            f"{label} diverged from unfaulted run; journal dump: {path}"
        )


def _recovery_total(site, outcome=None) -> float:
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
# Primitives: the registry, policies, and the unified retry semantics


class TestPrimitives:
    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError):
            faults.arm("not.a.site", faults.FailN(1))
        with pytest.raises(ValueError):
            faults.inject_fault("not.a.site")

    def test_fail_prob_schedule_is_seeded(self):
        a = faults.FailProb(0.5, seed=3)
        b = faults.FailProb(0.5, seed=3)
        assert [a.plan() for _ in range(64)] == [
            b.plan() for _ in range(64)
        ]

    def test_retry_outcome_vocabulary(self):
        from fluidframework_tpu.service.retry import (
            RetryPolicy,
            call_with_retry,
        )
        from fluidframework_tpu.telemetry.metrics import MetricsRegistry

        reg = MetricsRegistry()
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionError("transient")
            return "done"

        out = call_with_retry(
            "queue.send", flaky, policy=RetryPolicy(max_attempts=4),
            sleep=lambda _d: None, registry=reg,
        )
        assert out == "done"
        c = reg.get("retry_attempts_total")
        # Only attempts that scheduled a follow-up count as ``retry``.
        assert c.value(site="queue.send", outcome="retry") == 2
        assert c.value(site="queue.send", outcome="ok") == 1

        def always():
            raise ConnectionError("down")

        with pytest.raises(ConnectionError):
            call_with_retry(
                "queue.send", always, policy=RetryPolicy(max_attempts=3),
                sleep=lambda _d: None, registry=reg,
            )
        assert c.value(site="queue.send", outcome="exhausted") == 1
        assert c.value(site="queue.send", outcome="retry") == 2 + 2

    def test_injected_crash_is_fatal_not_retried(self):
        from fluidframework_tpu.service.retry import call_with_retry
        from fluidframework_tpu.telemetry.metrics import MetricsRegistry

        reg = MetricsRegistry()
        calls = []

        def crashy():
            calls.append(1)
            raise faults.InjectedCrash("queue.send", "crash")

        with pytest.raises(faults.InjectedCrash):
            call_with_retry(
                "queue.send", crashy, sleep=lambda _d: None, registry=reg,
            )
        assert len(calls) == 1, "a crash must never retry in place"
        c = reg.get("retry_attempts_total")
        assert c.value(site="queue.send", outcome="fatal") == 1

    def test_deadline_budget_bounds_retries(self):
        from fluidframework_tpu.service.retry import (
            RetryPolicy,
            call_with_retry,
        )
        from fluidframework_tpu.telemetry.metrics import MetricsRegistry

        reg = MetricsRegistry()

        def always():
            raise ConnectionError("down")

        with pytest.raises(ConnectionError):
            call_with_retry(
                "queue.send", always,
                policy=RetryPolicy(
                    max_attempts=100, base_delay_s=10.0, deadline_s=0.001
                ),
                sleep=lambda _d: None, registry=reg,
            )
        c = reg.get("retry_attempts_total")
        assert c.value(site="queue.send", outcome="exhausted") == 1
        assert c.value(site="queue.send", outcome="retry") == 0

    def test_unarmed_site_passes_through(self):
        @faults.inject_fault("queue.send")
        def f(x):
            return x + 1

        assert f(1) == 2
        assert faults.REGISTRY.invocations.get("queue.send") is None


# ---------------------------------------------------------------------------
# The standard workload + capture (the parity oracle)


def _submit(conn, frame):
    """Submit with the documented crash recovery: the harness plays the
    restart supervisor / reconnecting client — resubmitting the SAME
    frame after an injected fault is the real client behavior, and csn
    dedup at deli absorbs whatever half-landed. An admission throttle
    (r13: the frame was DENIED ahead of sequencing and nacked, nothing
    half-landed) resubmits the same way — the nack-recovery client
    contract."""
    for _ in range(8):
        try:
            conn.submit_frame(frame)
        except faults.InjectedFault:
            continue
        if conn.nacks:
            throttles = [
                n for n in conn.nacks
                if n.error_type == NackErrorType.THROTTLING
            ]
            assert len(throttles) == len(conn.nacks), conn.nacks
            conn.nacks.clear()
            continue
        return
    raise AssertionError("fault policy did not clear within 8 resubmits")


def _run_chaos_workload(arm=None, n_rounds=4, k=3):
    """Three writers over two documents submit deterministic insert
    frames; returns the post-drain canonical state."""
    svc = PipelineFluidService(n_partitions=2, checkpoint_every=4)
    conns = {
        "chaos-a": [svc.connect("chaos-a"), svc.connect("chaos-a")],
        "chaos-b": [svc.connect("chaos-b")],
    }
    if arm is not None:
        arm()
    csn = {}
    for r in range(n_rounds):
        for doc, cs in conns.items():
            for ci, conn in enumerate(cs):
                c0 = csn.get((doc, ci), 0) + 1
                origs = [conn.conn_no * MINT + c0 + j for j in range(k)]
                texts = [
                    ALPHA[(r + ci + j) % 26] * (1 + (j % 2))
                    for j in range(k)
                ]
                frame = OpFrame.build(
                    "s", ["ins"] * k, [0] * k, origs, texts,
                    csn0=c0, ref=svc.doc_head(doc),
                )
                _submit(conn, frame)
                csn[(doc, ci)] = c0 + k - 1
    faults.disarm()
    svc.pump()
    svc.flush_device()
    return _capture(svc, ["chaos-a", "chaos-b"])


def _capture(svc, docs):
    state = {}
    for d in docs:
        deltas = svc.get_deltas(d)
        seqs = [m.sequence_number for m in deltas]
        head = svc.doc_head(d)
        # Zero lost, zero duplicate sequenced ops: the durable log is a
        # gapless 1..head run.
        assert seqs == list(range(1, head + 1)), (d, seqs[:5], head)
        state[d] = {
            "text": svc.device_text(d, "s"),
            "idents": [
                (m.client_id, m.client_sequence_number, m.type)
                for m in deltas
            ],
            "summary": svc.device.channel_summary(d, "s"),
            "head": head,
        }
    return state


_REF = {}


def _reference_state():
    if "state" not in _REF:
        _REF["state"] = _run_chaos_workload(None)
    return _REF["state"]


def _policy(kind: str) -> faults.FaultPolicy:
    if kind == "fail":
        return faults.FailN(1)
    return faults.CrashAt(kind.split("_", 1)[1], times=1)


MATRIX = [
    (site, kind)
    for site in (
        "store.append", "queue.send", "pump.stage", "pump.feed",
        "pump.dispatch",
        # r13, the overload envelope: a faulted admission check fails
        # CLOSED (the op is nacked and the client resubmits — never
        # silently admitted, never dropped), and a faulted tier
        # evaluation holds the last tier — both must reproduce the
        # un-faulted run bit-identically.
        "admission.decide", "shed.tier",
    )
    for kind in ("fail", "crash_before", "crash_after")
]


class TestChaosMatrix:
    @pytest.mark.parametrize("site,kind", MATRIX)
    def test_post_recovery_state_parity(self, site, kind):
        ref = _reference_state()
        pre_recovery = _recovery_total(site)
        state = _run_chaos_workload(
            arm=lambda: faults.arm(site, _policy(kind))
        )
        assert faults.REGISTRY.injected_total(site) == 1, faults.stats()
        _assert_parity(state, ref, f"{site}/{kind}")
        # No silent recovery: the unified counter family moved for this
        # site (retry/ok for retried sites, fallback/requeue for the
        # pump, fatal for crashes that propagate to the supervisor).
        assert _recovery_total(site) > pre_recovery, (
            site, kind, metrics.REGISTRY.snapshot().get("retry_attempts_total"),
        )

    def test_fault_mix_across_all_sites(self):
        """Seeded probabilistic mix on every retried/fallback site at
        once — the matrix cells compose."""
        ref = _reference_state()

        def arm():
            for i, site in enumerate(
                ("store.append", "queue.send", "pump.dispatch")
            ):
                faults.arm(site, faults.FailProb(0.15, seed=41 + i))

        state = _run_chaos_workload(arm=arm)
        _assert_parity(state, ref, "fault-mix")
        assert faults.REGISTRY.injected_total() > 0

    def test_crashed_admission_check_fails_closed_with_nack(self):
        """The r13 overload row, spelled out: a CRASHED admission check
        — even crash-after, where the inner decision computed and only
        the ack was lost — denies and NACKS with ThrottlingError +
        retry_after; the op is never silently admitted and never
        dropped (the resubmit sequences it exactly once)."""
        svc = PipelineFluidService(n_partitions=2)
        conn = svc.connect("fc-doc")
        head = svc.doc_head("fc-doc")
        frame = OpFrame.build(
            "s", ["ins"], [0], [conn.conn_no * MINT + 1], ["x"],
            csn0=1, ref=head,
        )
        faults.arm("admission.decide", faults.CrashAt("after"))
        conn.submit_frame(frame)
        faults.disarm()
        assert svc.doc_head("fc-doc") == head, "silently admitted"
        assert conn.nacks, "fail-closed denial must nack, not drop"
        nk = conn.nacks[0]
        assert nk.error_type == NackErrorType.THROTTLING
        assert nk.content_code == 429 and nk.retry_after_s > 0
        conn.nacks.clear()
        conn.submit_frame(frame)  # the client contract: resubmit
        assert svc.doc_head("fc-doc") == head + 1
        seqs = [
            m.sequence_number for m in svc.get_deltas("fc-doc")
        ]
        assert seqs == list(range(1, head + 2))

    def test_injected_faults_visible_on_metrics(self):
        faults.arm("queue.send", faults.FailN(1))
        _run_chaos_workload()
        rendered = metrics.REGISTRY.render()
        assert "faults_injected_total" in rendered
        assert 'site="queue.send"' in rendered


# ---------------------------------------------------------------------------
# Pump-specific recovery: backpressure × dispatch failure, crash requeue


N_CH, K = 24, 8


def _feed_backend(be, r: int, n_ch: int = N_CH, k: int = K) -> None:
    ar = np.arange(k, dtype=np.int32)
    for i in range(n_ch):
        rows = np.zeros((k, OP_WIDTH), np.int32)
        rows[:, F_TYPE] = OP_INSERT
        rows[:, F_LEN] = 1
        rows[:, F_SEQ] = r * k + 1 + ar
        rows[:, F_REF] = r * k
        rows[:, F_ARG] = r * k + 1 + ar
        be.enqueue_frame(f"d{i}", SeqFrame("s", 0, 1, rows, (), 0.0))


def _make_backend() -> DeviceFleetBackend:
    return DeviceFleetBackend(
        capacity=128, max_batch=1 << 20, pump_mode=True, ring_depth=1
    )


def _pool_parity(a: DeviceFleetBackend, b: DeviceFleetBackend) -> None:
    assert sorted(a.fleet.pools) == sorted(b.fleet.pools)
    for cap, pa in a.fleet.pools.items():
        pb = b.fleet.pools[cap]
        for name, x, y in zip(SegmentState._fields, pa.state, pb.state):
            assert bool(jnp.array_equal(x, y)), (
                f"faulted/unfaulted divergence: pool {cap} lane {name}"
            )


class TestPumpChaos:
    def _reference(self, rounds: int) -> DeviceFleetBackend:
        ref = _make_backend()
        for r in range(rounds):
            _feed_backend(ref, r)
            ref.pump_stage()
        ref.pump_drain()
        return ref

    def test_backpressure_with_dispatch_failure_keeps_boxcar(self):
        """The r11 audit: ring-full backpressure forces the oldest slot to
        dispatch first; when THAT dispatch faults, the fallback applies
        the slot from its retained host copy — the staged boxcar is never
        dropped, and both counters tell the story."""
        be = _make_backend()
        _feed_backend(be, 0)
        be.pump_stage()  # ring (depth 1) now full
        _feed_backend(be, 1)
        pre_bp = be.pump_backpressure
        pre_fb = _recovery_total("pump.dispatch", "fallback")
        faults.arm("pump.dispatch", faults.FailN(1))
        be.pump_stage()  # backpressure dispatch -> injected failure -> fallback
        faults.disarm()
        assert be.pump_backpressure == pre_bp + 1
        assert _recovery_total("pump.dispatch", "fallback") == pre_fb + 1
        be.pump_drain()
        stats = be.stats()
        assert stats["ops_applied"] == 2 * N_CH * K
        assert stats["docs_with_errors"] == 0
        _pool_parity(be, self._reference(2))

    def test_crash_before_dispatch_requeues_slot_for_drain(self):
        """Extend the r10 drain contract to the injected-crash case: a
        crash at the dispatch boundary (before the device step ran) puts
        the slot back at the ring head, and one drain replays it with no
        lost/dup ops."""
        be = _make_backend()
        _feed_backend(be, 0)
        be.pump_stage()
        pre_rq = _recovery_total("pump.dispatch", "requeue")
        faults.arm("pump.dispatch", faults.CrashAt("before"))
        with pytest.raises(faults.InjectedCrash):
            be.pump_dispatch()
        faults.disarm()
        assert len(be._ring) == 1, "crashed slot must be requeued"
        assert _recovery_total("pump.dispatch", "requeue") == pre_rq + 1
        be.pump_drain()
        stats = be.stats()
        assert stats["ops_applied"] == N_CH * K
        assert stats["docs_with_errors"] == 0
        _pool_parity(be, self._reference(1))

    def test_crash_after_dispatch_does_not_requeue(self):
        """A crash AFTER the device step leaves the applied state
        authoritative: requeueing would double-apply, so the slot is
        consumed and the drain just barriers the scan."""
        be = _make_backend()
        _feed_backend(be, 0)
        be.pump_stage()
        faults.arm("pump.dispatch", faults.CrashAt("after"))
        with pytest.raises(faults.InjectedCrash):
            be.pump_dispatch()
        faults.disarm()
        assert len(be._ring) == 0, "completed slot must not replay"
        be.pump_drain()
        assert be.stats()["ops_applied"] == N_CH * K
        _pool_parity(be, self._reference(1))

    @pytest.mark.parametrize("boundary", ["before", "after"])
    def test_crash_at_stage_boundary_drains_clean(self, boundary):
        be = _make_backend()
        _feed_backend(be, 0)
        faults.arm("pump.stage", faults.CrashAt(boundary))
        with pytest.raises(faults.InjectedCrash):
            be.flush()
        faults.disarm()
        be.pump_drain()
        assert be.stats()["ops_applied"] == N_CH * K
        _pool_parity(be, self._reference(1))

    def test_feed_tick_crash_leaves_rows_buffered_next_tick_refires(self):
        """The r12 ``pump.feed`` recovery contract: a crashed deadline
        tick leaves every row buffered, the crash is counted (requeue,
        never silent), and the NEXT tick re-fires over exactly those
        rows — no op lost, none duplicated, state bit-identical to an
        unfaulted run."""
        be = DeviceFleetBackend(
            capacity=128, max_batch=1 << 20, pump_mode=True,
            ring_depth=1, feed_deadline_ms=0.0,
        )
        _feed_backend(be, 0)
        pre_rq = _recovery_total("pump.feed", "requeue")
        faults.arm("pump.feed", faults.CrashAt("before"))
        with pytest.raises(faults.InjectedCrash):
            be.pump_feed_counted()
        faults.disarm()
        assert be.stats()["ops_applied"] == 0
        assert be.needs_flush(), "crashed tick must leave rows buffered"
        assert _recovery_total("pump.feed", "requeue") == pre_rq + 1
        be.pump_feed_counted()  # the next tick re-fires
        be.pump_drain()
        stats = be.stats()
        assert stats["ops_applied"] == N_CH * K
        assert stats["docs_with_errors"] == 0
        _pool_parity(be, self._reference(1))

    def test_feed_tick_crash_after_is_fatal_not_refired(self):
        """Crash AFTER the feed ran: the boxcar dispatched and only the
        ack was lost — counted fatal, nothing re-fires, and redelivered
        rows drop at the watermarks (no double-apply)."""
        be = DeviceFleetBackend(
            capacity=128, max_batch=1 << 20, pump_mode=True,
            ring_depth=1, feed_deadline_ms=0.0,
        )
        _feed_backend(be, 0)
        pre_ft = _recovery_total("pump.feed", "fatal")
        faults.arm("pump.feed", faults.CrashAt("after"))
        with pytest.raises(faults.InjectedCrash):
            be.pump_feed_counted()
        faults.disarm()
        assert _recovery_total("pump.feed", "fatal") == pre_ft + 1
        _feed_backend(be, 0)  # at-least-once redelivery of the same round
        be.pump_feed_counted()
        be.pump_drain()
        assert be.stats()["ops_applied"] == N_CH * K  # no dup
        _pool_parity(be, self._reference(1))


# ---------------------------------------------------------------------------
# Residency chaos: the r19 doc.hibernate / doc.wake recovery matrix
# (docs/failure-semantics.md §"Residency lifecycle") — fail / crash-before /
# crash-after at both commit boundaries, bit-identical post-recovery state.


class TestResidencyChaos:
    def _reference(self, rounds: int) -> DeviceFleetBackend:
        ref = _make_backend()
        for r in range(rounds):
            _feed_backend(ref, r)
            ref.pump_stage()
        ref.pump_drain()
        return ref

    def _resident(self, rounds: int = 1) -> DeviceFleetBackend:
        be = _make_backend()
        for r in range(rounds):
            _feed_backend(be, r)
            be.pump_stage()
        be.pump_drain()
        return be

    def test_hibernate_fail_stays_resident_retry_succeeds(self):
        """``doc.hibernate`` fail → fallback: the doc stays RESIDENT
        with its slot live (counted, never silent), and a clean retry
        hibernates it for real."""
        from fluidframework_tpu.service import residency

        be = self._resident()
        idx = be._index[("d0", "s")]
        pre = _recovery_total("doc.hibernate", "fallback")
        faults.arm("doc.hibernate", faults.FailN(1))
        assert be.hibernate_doc("d0") is False
        faults.disarm()
        assert _recovery_total("doc.hibernate", "fallback") == pre + 1
        assert be.residency.state("d0") == residency.RESIDENT
        assert be.fleet.placement[idx] is not None, "slot must stay live"
        assert be.hibernate_doc("d0") is True  # clean retry
        assert be.residency.state("d0") == residency.COLD
        assert be.fleet.placement[idx] is None
        _feed_backend(be, 1)  # first op wakes it back
        be.pump_stage()
        be.pump_drain()
        stats = be.stats()
        assert stats["ops_applied"] == 2 * N_CH * K
        assert stats["docs_with_errors"] == 0
        _pool_parity(be, self._reference(2))

    def test_hibernate_crash_before_stays_resident(self):
        """Crash BEFORE the eviction commit: nothing happened — the doc
        is RESIDENT, the slot live, and the next round serves it as if
        the sweep never ran."""
        from fluidframework_tpu.service import residency

        be = self._resident()
        idx = be._index[("d0", "s")]
        faults.arm("doc.hibernate", faults.CrashAt("before"))
        with pytest.raises(faults.InjectedCrash):
            be.hibernate_doc("d0")
        faults.disarm()
        assert be.residency.state("d0") == residency.RESIDENT
        assert be.fleet.placement[idx] is not None
        _feed_backend(be, 1)
        be.pump_stage()
        be.pump_drain()
        assert be.stats()["ops_applied"] == 2 * N_CH * K
        _pool_parity(be, self._reference(2))

    def test_hibernate_crash_after_is_durably_cold_wake_serves(self):
        """Crash AFTER the eviction commit: the slots are freed and the
        cold records landed — the manager records the doc COLD (the
        at-least-once window resolved toward reality), and the first op
        wakes it through the normal path with bit-identical state."""
        from fluidframework_tpu.service import residency

        be = self._resident()
        idx = be._index[("d0", "s")]
        faults.arm("doc.hibernate", faults.CrashAt("after"))
        with pytest.raises(faults.InjectedCrash):
            be.hibernate_doc("d0")
        faults.disarm()
        assert be.residency.state("d0") == residency.COLD
        assert be.fleet.placement[idx] is None, "eviction was durable"
        _feed_backend(be, 1)
        be.pump_stage()
        be.pump_drain()
        stats = be.stats()
        assert stats["ops_applied"] == 2 * N_CH * K
        assert stats["docs_with_errors"] == 0
        assert be.residency.stats()["wakes"].get("ok", 0) == 1
        _pool_parity(be, self._reference(2))

    def test_wake_fail_parks_rows_flush_retries(self):
        """``doc.wake`` fail → retry: the durable/cold state is
        untouched and the triggering rows PARK (bounded queue — counted
        into pressure, never dropped); the quiescence flush re-attempts
        the wake and every parked row applies in order."""
        from fluidframework_tpu.service import residency

        be = self._resident()
        assert be.hibernate_doc("d0") is True
        pre = _recovery_total("doc.wake", "retry")
        faults.arm("doc.wake", faults.FailN(1))
        _feed_backend(be, 1)  # d0's frame parks; the rest buffer
        faults.disarm()
        assert _recovery_total("doc.wake", "retry") == pre + 1
        assert be.residency.state("d0") == residency.WAKING
        assert be.stats()["parked_rows"] == K
        assert be.needs_flush(), "parked rows must demand a flush"
        be.flush()  # the quiescence backstop retries the wake
        be.pump_drain()
        stats = be.stats()
        assert stats["ops_applied"] == 2 * N_CH * K
        assert stats["parked_rows"] == 0
        assert stats["docs_with_errors"] == 0
        assert be.residency.state("d0") == residency.RESIDENT
        _pool_parity(be, self._reference(2))

    def test_wake_crash_before_parks_rows_flush_recovers(self):
        """Crash BEFORE the restore: cold state untouched, rows parked;
        the disarmed flush retries the wake from the unchanged durable
        state — no op lost, none duplicated."""
        from fluidframework_tpu.service import residency

        be = self._resident()
        assert be.hibernate_doc("d0") is True
        faults.arm("doc.wake", faults.CrashAt("before"))
        ar = np.arange(K, dtype=np.int32)
        rows = np.zeros((K, OP_WIDTH), np.int32)
        rows[:, F_TYPE] = OP_INSERT
        rows[:, F_LEN] = 1
        rows[:, F_SEQ] = K + 1 + ar
        rows[:, F_REF] = K
        rows[:, F_ARG] = K + 1 + ar
        with pytest.raises(faults.InjectedCrash):
            be.enqueue_frame("d0", SeqFrame("s", 0, 1, rows, (), 0.0))
        faults.disarm()
        assert be.residency.state("d0") == residency.WAKING
        assert be.stats()["parked_rows"] == K
        for i in range(1, N_CH):  # the rest of the round feeds normally
            r2 = rows.copy()
            be.enqueue_frame(f"d{i}", SeqFrame("s", 0, 1, r2, (), 0.0))
        be.flush()
        be.pump_drain()
        stats = be.stats()
        assert stats["ops_applied"] == 2 * N_CH * K
        assert stats["parked_rows"] == 0
        assert be.residency.state("d0") == residency.RESIDENT
        _pool_parity(be, self._reference(2))

    def test_wake_crash_after_restore_is_idempotent(self):
        """Crash AFTER the restore: the slot is live and the rows
        unparked — the wake finishes as completed before the crash
        propagates, and the retry path (had one raced in) would find no
        cold record and count ``noop`` instead of double-restoring."""
        from fluidframework_tpu.service import residency

        be = self._resident()
        assert be.hibernate_doc("d0") is True
        idx = be._index[("d0", "s")]
        faults.arm("doc.wake", faults.CrashAt("after"))
        ar = np.arange(K, dtype=np.int32)
        rows = np.zeros((K, OP_WIDTH), np.int32)
        rows[:, F_TYPE] = OP_INSERT
        rows[:, F_LEN] = 1
        rows[:, F_SEQ] = K + 1 + ar
        rows[:, F_REF] = K
        rows[:, F_ARG] = K + 1 + ar
        with pytest.raises(faults.InjectedCrash):
            be.enqueue_frame("d0", SeqFrame("s", 0, 1, rows, (), 0.0))
        faults.disarm()
        assert be.residency.state("d0") == residency.RESIDENT
        assert be.fleet.placement[idx] is not None
        assert be.stats()["parked_rows"] == 0, "completed wake unparked"
        assert ("d0", "s") not in be._cold
        for i in range(1, N_CH):
            r2 = rows.copy()
            be.enqueue_frame(f"d{i}", SeqFrame("s", 0, 1, r2, (), 0.0))
        be.flush()
        be.pump_drain()
        assert be.stats()["ops_applied"] == 2 * N_CH * K
        _pool_parity(be, self._reference(2))


# ---------------------------------------------------------------------------
# Websocket delivery: requeue recovery over real sockets


class TestWsDeliveryChaos:
    def _converged(self, runtimes, text, timeout=10.0):

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for rt in runtimes:
                rt.process_incoming()
            if all(
                rt.get_channel("text").get_text() == text for rt in runtimes
            ):
                return True
            time.sleep(0.02)
        return False

    @pytest.mark.parametrize("kind", ["fail", "crash_before", "crash_after"])
    def test_delivery_failure_exactly_once(self, kind):
        """A sweep's write of everything queued on a socket (PR 45: two
        flushes and a signal found by one sweep leave together) that
        fails requeues the whole batch (watermarks only advance on
        success), a crash-after write requeues NOTHING of what reached
        the socket — either way every client sees each op exactly
        once."""
        from fluidframework_tpu.drivers.network_driver import (
            NetworkFluidService,
        )
        from fluidframework_tpu.models.shared_string import SharedString
        from fluidframework_tpu.runtime.container import ContainerRuntime
        from fluidframework_tpu.service.network_server import (
            FluidNetworkServer,
        )

        srv = FluidNetworkServer(service=PipelineFluidService(n_partitions=2))
        srv.start()
        try:
            a = ContainerRuntime(
                NetworkFluidService("127.0.0.1", srv.port), "wsdoc",
                channels=(SharedString("text"),),
            )
            b = ContainerRuntime(
                NetworkFluidService("127.0.0.1", srv.port), "wsdoc",
                channels=(SharedString("text"),),
            )
            assert self._converged([a, b], "")  # settle the handshakes
            pre = _recovery_total("ws.deliver")
            faults.arm("ws.deliver", _policy(kind))
            writes, sent = srv.socket_writes, srv.ops_delivered

            # The loop stands still while two one-op flushes and a signal
            # arrive: one read, so ONE sweep finds all three queued.
            srv._loop.call_soon_threadsafe(time.sleep, 0.3)
            a.get_channel("text").insert_text(0, "hello")
            a.flush()
            a.get_channel("text").insert_text(5, " world")
            a.flush()
            a.connection.submit_signal({"cursor": 11})
            assert self._converged([a, b], "hello world"), (
                faults.stats(), kind,
            )
            assert faults.REGISTRY.injected_total("ws.deliver") == 1
            assert _recovery_total("ws.deliver") > pre
            # A requeued batch leaves with the next sweep, and a sweep
            # follows inbound traffic: any message brings it.
            b.connection.submit_signal({"cursor": 0})
            deadline = time.monotonic() + 5.0
            while srv.ops_delivered - sent < 4 and time.monotonic() < deadline:
                time.sleep(0.02)
            # Two ops to two sockets, each exactly once, in fewer writes
            # than the messages they carried: the faulted write was a batch.
            assert srv.ops_delivered - sent == 4
            assert srv.socket_writes - writes < 4 + 2 + 2
            assert self._converged([a, b], "hello world")
        finally:
            faults.disarm()
            srv.stop()


# ---------------------------------------------------------------------------
# Leases: coordination faults + the epoch-fence reroute


def _op(csn: int, ref: int) -> DocumentMessage:
    return DocumentMessage(
        client_sequence_number=csn,
        reference_sequence_number=ref,
        type=MessageType.OPERATION,
        contents=None,
    )


class TestLeaseChaos:
    @pytest.mark.parametrize("kind", ["fail", "crash_before", "crash_after"])
    def test_acquire_failure_retries_through_router(self, kind):
        """A coordination blip during acquire — including a crash AFTER
        the lease was written but before the caller saw the grant — is
        absorbed by the router's candidate sweep: the same node
        re-acquires its own lease on the retry pass."""
        svc = MultiNodeFluidService(n_nodes=3, rebalance_every=0)
        pre = _recovery_total("lease.acquire")
        faults.arm("lease.acquire", _policy(kind))
        conn = svc.connect("lease-doc")
        faults.disarm()
        assert faults.REGISTRY.injected_total("lease.acquire") == 1
        assert _recovery_total("lease.acquire") > pre
        conn.submit(_op(1, conn.join_seq))
        msgs = svc.get_deltas("lease-doc")
        assert [m.sequence_number for m in msgs] == [1, 2]

    def test_renew_failure_reowns_without_loss(self):
        svc = MultiNodeFluidService(n_nodes=3, rebalance_every=0)
        conn = svc.connect("renew-doc")
        conn.submit(_op(1, conn.join_seq))
        faults.arm("lease.renew", faults.FailN(1))
        conn.submit(_op(2, conn.join_seq))
        faults.disarm()
        assert faults.REGISTRY.injected_total("lease.renew") == 1
        seqs = [m.sequence_number for m in svc.get_deltas("renew-doc")]
        assert seqs == [1, 2, 3], "renew blip must not lose or dup ops"

    def test_lease_expiry_mid_flush_fenced_and_requeued(self, monkeypatch):
        """The epoch fence rejects a stale owner's mid-flight write and
        the service requeues the op with the NEW owner — sequenced
        exactly once, counted as {lease.renew,fence}."""
        t = [0.0]
        svc = MultiNodeFluidService(
            n_nodes=3, clock=lambda: t[0], lease_ttl_s=5.0,
            rebalance_every=0,
        )
        conn = svc.connect("fence-doc")
        conn.submit(_op(1, conn.join_seq))
        stale = next(
            n for n in svc.cluster.nodes if "fence-doc" in n._docs
        )
        # Lease lapses while the old owner still believes it owns the doc;
        # another node takes over (epoch bump fences the log).
        t[0] += 10.0
        other = next(n for n in svc.cluster.nodes if n is not stale)
        assert other.try_own("fence-doc")
        # The service races the stale owner once (the mid-flush window).
        orig_owner = svc.cluster.owner
        raced = []

        def racing_owner(doc_id):
            if not raced:
                raced.append(1)
                return stale
            return orig_owner(doc_id)

        monkeypatch.setattr(svc.cluster, "owner", racing_owner)
        pre = _recovery_total("lease.renew", "fence")
        conn.submit(_op(2, conn.join_seq))
        assert _recovery_total("lease.renew", "fence") == pre + 1
        seqs = [m.sequence_number for m in svc.get_deltas("fence-doc")]
        assert seqs == sorted(set(seqs)), "fenced op must not duplicate"
        ops = [
            m for m in svc.get_deltas("fence-doc")
            if m.type == MessageType.OPERATION
        ]
        assert [m.client_sequence_number for m in ops] == [1, 2]
        assert stale.op_rate.get("fence-doc") is None or (
            "fence-doc" not in stale._docs
        ), "stale owner must have forgotten the doc after the fence"


# ---------------------------------------------------------------------------
# The writer cap (MAX_WRITERS): nack-at-cap + slot-expiry reuse through the pipeline


class TestWriterCap:
    def test_nack_at_cap_and_slot_reuse(self):
        """ROADMAP open item: MAX_WRITERS is enforced END TO END — the
        writer past the cap gets a clean 429 nack through the full pipeline, and after a
        leave whose seq falls below the collab-window floor the freed
        slot readmits a new writer."""
        svc = PipelineFluidService(n_partitions=1, device_backend=False)
        conns = [svc.connect("cap-doc") for _ in range(MAX_WRITERS)]
        assert len({c.client_id for c in conns}) == MAX_WRITERS
        with pytest.raises(ConnectionError) as ei:
            svc.connect("cap-doc")
        assert "writer slots exhausted" in str(ei.value)
        # The nack is the sequencer's 429 LIMIT_EXCEEDED, delivered
        # through the broadcaster to the joining connection (pipeline
        # semantics, not just the DocumentSequencer unit contract).
        freed = conns[0]
        freed_slot = freed.client_id
        freed_conn_no = freed.conn_no
        freed.disconnect()
        # Before the floor advances past the leave, the cap still nacks:
        # the freed slot's stamps may still be inside a live collab
        # window.
        with pytest.raises(ConnectionError):
            svc.connect("cap-doc")
        # Every surviving writer submits against the current head; the
        # MSN floor advances past the leave seq and the slot recycles.
        for c in conns[1:]:
            c.submit(_op(1, svc.doc_head("cap-doc")))
        readmitted = svc.connect("cap-doc")
        assert readmitted.client_id == freed_slot
        assert readmitted.conn_no > freed_conn_no, (
            "recycled slot must carry a fresh never-recycled ordinal"
        )
        # And the readmitted writer can sequence ops.
        readmitted.submit(_op(1, svc.doc_head("cap-doc")))
        head = svc.doc_head("cap-doc")
        assert svc.get_deltas("cap-doc")[-1].sequence_number == head
