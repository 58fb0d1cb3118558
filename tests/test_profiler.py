"""Serving timeline profiler (telemetry/profiler.py): lane vocabulary,
bounded arm/disarm over a real websocket server, the zero-cost-disarmed
/ zero-readback contracts, Perfetto export, the derived-view equivalence
of the legacy counters, the /profilez shed-tier contract (NOT exempt),
and the two runtime watchdogs (loop-stall sentinel, gc pause hooks).

The r16 acceptance bar: a captured window decomposes the serving wall
into named lanes plus the derived per-boxcar ``loop_other`` host tax,
``pump_busy_s``/``flush_totals["staging_s"]`` are exact derived views of
the same interval clock reads, and /profilez sheds under overload while
/metrics and /debugz stay exempt.
"""

import gc
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

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
from fluidframework_tpu.service.device_backend import DeviceFleetBackend
from fluidframework_tpu.service.pipeline import PipelineFluidService
from fluidframework_tpu.telemetry import journal, metrics, profiler, tracing
from fluidframework_tpu.testing import faults

MINT = 1 << 14  # shared_string._MINT_STRIDE


@pytest.fixture(autouse=True)
def _clean_profiler():
    profiler.reset()
    journal.enable()
    journal.reset()
    faults.reset()
    metrics.REGISTRY.reset()
    yield
    faults.reset()
    profiler.reset()
    journal.enable()
    journal.reset()
    metrics.REGISTRY.reset()


def _feed(be, r: int, n_ch: int = 6, k: int = 8) -> None:
    ar = np.arange(k, dtype=np.int32)
    for i in range(n_ch):
        rows = np.zeros((k, OP_WIDTH), np.int32)
        rows[:, F_TYPE] = OP_INSERT
        rows[:, F_LEN] = 1
        rows[:, F_SEQ] = r * k + 1 + ar
        rows[:, F_REF] = r * k
        rows[:, F_ARG] = r * k + 1 + ar
        be.enqueue_frame(f"d{i}", SeqFrame("s", 0, 1, rows, (), 0.0))


def _pump_rounds(be, rounds: int = 4) -> None:
    for r in range(rounds):
        _feed(be, r)
        be.pump_stage()
        be.pump_dispatch()
    be.pump_drain()


def _one_frame(conn, svc, doc, k=3, c0=1):
    origs = [conn.conn_no * MINT + c0 + j for j in range(k)]
    return OpFrame.build(
        "s", ["ins"] * k, [0] * k, origs, ["x"] * k, csn0=c0,
        ref=svc.doc_head(doc),
    )


# ---------------------------------------------------------------------------
# Lane vocabulary


def test_lane_vocabulary_covers_the_trace_spine():
    """Every pump/feed sub-stage the r10/r12 trace spine names has a
    timeline lane (ring_stage's upload half is the ring_put lane), the
    deli ticket has its own lane, the derived gap and both watchdog
    lanes are declared, and the Perfetto tids are the deterministic
    declaration order."""
    spine_to_lane = {
        tracing.STAGE_DEVICE_STEP: "device_step",
        tracing.STAGE_SCAN_CONSUME: "scan_consume",
        tracing.STAGE_FEED_WAIT: "feed_wait",
        tracing.STAGE_RING_STAGE: "ring_put",
        tracing.STAGE_DELI: "ticket",
    }
    for stage, lane in spine_to_lane.items():
        assert stage in tracing.FRAME_STAGES
        assert lane in profiler.LANES, (stage, lane)
    for lane in ("host_stage", "dispatch", "loop_other", "loop_lag",
                 "gc_pause"):
        assert lane in profiler.LANES
    assert profiler.ROUND_LANES <= set(profiler.LANES)
    assert sorted(profiler.LANE_TIDS.values()) == list(
        range(len(profiler.LANES))
    )


def test_unknown_lane_raises():
    assert profiler.arm(5000)
    with pytest.raises(ValueError):
        profiler.PROFILER.record("not.a.lane", 0.0, 1.0)


def test_loop_other_is_derived_not_recordable():
    """loop_other is the SYNTHESIZED gap: recording it directly would
    double-count the host tax."""
    assert profiler.arm(5000)
    with pytest.raises(ValueError):
        profiler.PROFILER.record("loop_other", 0.0, 1.0)


def test_ring_is_bounded():
    p = profiler.Profiler(capacity=64)
    p._until = float("inf")
    for i in range(100):
        p.record("host_stage", float(i), float(i) + 0.5, boxcar=i)
    ivs = p.intervals()
    assert len(ivs) == 64
    assert [iv.iid for iv in ivs] == list(range(36, 100))
    assert p.seen == 100


# ---------------------------------------------------------------------------
# Deterministic test surface vs wall-timestamped export


def test_render_is_replica_deterministic():
    """Two profilers observing the same LOGICAL intervals at different
    wall times render byte-equal text — timestamps live only in the
    exported trace file."""
    a, b = profiler.Profiler(), profiler.Profiler()
    a._until = b._until = float("inf")
    for p, skew in ((a, 0.0), (b, 17.3)):
        t = 100.0 + skew
        p.record("host_stage", t, t + 0.001, boxcar=1, rows=48)
        p.record("ring_put", t + 0.001, t + 0.002, boxcar=1, rows=48)
        p.record("device_step", t + 0.002, t + 0.009, boxcar=1)
        p.record("gc_pause", t + 0.5, t + 0.51)
    assert a.render() == b.render()
    assert a.render().splitlines()[1] == "000000 host_stage boxcar=1 rows=48"
    # The export DOES carry the wall microseconds.
    ts_a = [
        e["ts"] for e in a.chrome_trace()["traceEvents"] if e["ph"] == "X"
    ]
    ts_b = [
        e["ts"] for e in b.chrome_trace()["traceEvents"] if e["ph"] == "X"
    ]
    assert ts_a != ts_b


def test_chrome_trace_schema_and_loop_other_synthesis():
    """The Perfetto export: valid JSON, pid=process / one metadata-named
    tid per lane, complete events with µs ts+dur, and the derived
    loop_other gaps synthesized per boxcar round."""
    import os

    p = profiler.Profiler()
    p._until = float("inf")
    # One round with a gap between ring_put and dispatch (the host tax).
    p.record("host_stage", 10.000, 10.001, boxcar=7, rows=8)
    p.record("ring_put", 10.001, 10.002, boxcar=7, rows=8)
    p.record("dispatch", 10.004, 10.005, boxcar=7)
    p.record("device_step", 10.005, 10.010, boxcar=7)
    doc = json.loads(json.dumps(p.chrome_trace()))
    evs = doc["traceEvents"]
    meta = {e["args"]["name"] for e in evs if e["ph"] == "M"
            if e["name"] == "thread_name"}
    assert meta == set(profiler.LANES)
    xs = [e for e in evs if e["ph"] == "X"]
    for e in xs:
        assert e["pid"] == os.getpid()
        assert e["tid"] == profiler.LANE_TIDS[e["name"]]
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert set(e["args"]) == {"boxcar", "rows"}
    gaps = [e for e in xs if e["name"] == "loop_other"]
    assert len(gaps) == 1
    assert gaps[0]["args"]["boxcar"] == 7
    # The synthesized gap is ring_put end -> dispatch start (2ms).
    assert abs(gaps[0]["dur"] - 2000.0) < 1.0


# ---------------------------------------------------------------------------
# Cost contracts


def test_zero_alloc_when_disarmed(monkeypatch):
    """Disarmed (the default), the profiler allocates NOTHING: every
    producer site is one predicate; the counting shim pins that no
    record call reaches the ring through a full pipeline workload."""
    calls = []
    orig = profiler.Profiler.record

    def counting(self, lane, t0, t1, boxcar=-1, rows=0):
        calls.append(lane)
        return orig(self, lane, t0, t1, boxcar=boxcar, rows=rows)

    monkeypatch.setattr(profiler.Profiler, "record", counting)
    assert not profiler.enabled()
    svc = PipelineFluidService(n_partitions=2)
    conn = svc.connect("off-doc")
    conn.submit_frame(_one_frame(conn, svc, "off-doc"))
    svc.pump()
    svc.flush_device()
    assert calls == []
    assert profiler.PROFILER.seen == 0
    assert profiler.arm(5000)
    conn.submit_frame(_one_frame(conn, svc, "off-doc", c0=4))
    svc.pump()
    svc.flush_device()
    assert "ticket" in calls and "host_stage" in calls, calls


def test_profiler_adds_zero_device_readbacks(monkeypatch):
    """The zero-readback contract: an armed capture performs EXACTLY the
    same device→host transfers as a disarmed run — device_step closes on
    the pump's existing one-boxcar-stale scan, never its own pull."""
    from fluidframework_tpu.parallel import fleet as fleet_mod
    from fluidframework_tpu.service import device_backend as db_mod

    def run() -> int:
        be = DeviceFleetBackend(
            capacity=128, max_batch=1 << 20, pump_mode=True
        )
        calls = []
        real = np.asarray

        class _CountingNp:
            def __getattr__(self, name):
                return getattr(np, name)

            @staticmethod
            def asarray(*a, **kw):
                calls.append(1)
                return real(*a, **kw)

            @staticmethod
            def array(*a, **kw):
                calls.append(1)
                return np.array(*a, **kw)

        monkeypatch.setattr(fleet_mod, "np", _CountingNp())
        monkeypatch.setattr(db_mod, "np", _CountingNp())
        try:
            for r in range(3):
                _feed(be, r, n_ch=4, k=4)
                be.flush()
            be.pump_drain()
        finally:
            monkeypatch.setattr(fleet_mod, "np", np)
            monkeypatch.setattr(db_mod, "np", np)
        return len(calls)

    profiler.disarm()
    off = run()
    assert profiler.arm(30_000)
    on = run()
    assert on == off, f"profiler added readbacks: on={on} off={off}"
    assert profiler.PROFILER.seen > 0


# ---------------------------------------------------------------------------
# The derived-view satellite: one clock, one record site


def test_legacy_counters_are_derived_views_pump():
    """``pump_busy_s`` and ``flush_totals['staging_s']`` accumulate from
    the SAME perf_counter reads the profiler intervals store — the
    legacy counters are derived views, not parallel instrumentation:
    busy ≡ Σ device_step exactly, staging ≡ Σ host_stage + Σ ring_put."""
    be = DeviceFleetBackend(capacity=128, max_batch=1 << 20, pump_mode=True)
    assert profiler.arm(60_000)
    busy0 = be.pump_busy_s
    stage0 = be.flush_totals["staging_s"]
    _pump_rounds(be, rounds=5)
    ivs = profiler.intervals()
    step_sum = sum(iv.dur for iv in ivs if iv.lane == "device_step")
    stage_sum = sum(
        iv.dur for iv in ivs if iv.lane in ("host_stage", "ring_put")
    )
    assert step_sum > 0 and stage_sum > 0
    assert be.pump_busy_s - busy0 == pytest.approx(step_sum, abs=1e-12)
    assert be.flush_totals["staging_s"] - stage0 == pytest.approx(
        stage_sum, abs=1e-9
    )
    # Fleet-side routing has its own bucket now — staging_s no longer
    # hides a component the timeline cannot see.
    assert "routing_s" in be.flush_totals


def test_legacy_counters_are_derived_views_oneshot():
    """The one-shot flush path holds the same derived-view equivalence
    (its host_stage/dispatch intervals bracket apply_sparse)."""
    be = DeviceFleetBackend(
        capacity=128, max_batch=1 << 20, pump_mode=False
    )
    assert profiler.arm(60_000)
    stage0 = be.flush_totals["staging_s"]
    for r in range(3):
        _feed(be, r)
        be.flush()
    be.collect_now()
    ivs = profiler.intervals()
    stage_sum = sum(iv.dur for iv in ivs if iv.lane == "host_stage")
    assert stage_sum > 0
    assert be.flush_totals["staging_s"] - stage0 == pytest.approx(
        stage_sum, abs=1e-9
    )


# ---------------------------------------------------------------------------
# summarize(): the host-tax attribution


def test_summarize_decomposes_the_window():
    """A captured pump window decomposes into named lanes + the derived
    loop_other gap (coverage ≈ 1 by construction — asserted ≥ 0.95),
    reports per-boxcar host tax percentiles, and derives a device-idle
    fraction that agrees with ``1 - pump_busy_s / wall``."""
    be = DeviceFleetBackend(capacity=128, max_batch=1 << 20, pump_mode=True)
    assert profiler.arm(60_000)
    busy0 = be.pump_busy_s
    t0 = time.perf_counter()
    _pump_rounds(be, rounds=5)
    wall = time.perf_counter() - t0
    s = profiler.summarize()
    assert s["boxcars"] == 5
    assert s["coverage_frac"] >= 0.95
    for lane in ("host_stage", "ring_put", "dispatch", "device_step",
                 "scan_consume"):
        assert s["lanes_ms"].get(lane, 0.0) > 0.0, (lane, s["lanes_ms"])
    tax = s["serving_host_tax_ms"]
    assert tax["p99"] >= tax["p50"] >= 0.0
    # Two instruments, one truth: the timeline-derived idle fraction
    # reconciles with the legacy busy-union instrument over the same
    # workload (the window extents differ slightly — tolerance).
    legacy_idle = max(0.0, 1.0 - (be.pump_busy_s - busy0) / wall)
    assert s["device_idle_frac"] == pytest.approx(legacy_idle, abs=0.05)


def test_capture_window_self_disarms():
    """A bounded window disarms itself once elapsed even if no surface
    calls disarm() — a crashed /profilez client cannot leave the
    profiler armed forever."""
    assert profiler.arm(1.0)  # 1 ms window
    assert profiler.enabled()
    time.sleep(0.01)
    now = time.perf_counter()
    profiler.record("gc_pause", now - 1e-4, now)  # past the deadline
    assert not profiler.enabled()


def test_arm_fault_is_counted_and_absorbed():
    """The ``profiler.arm`` site's contract (the journal.dump absorb
    shape): a failed arm is counted
    (retry_attempts_total{profiler.arm,fallback}) and returns False —
    never raised into the caller — and the next arm works."""
    faults.arm("profiler.arm", faults.FailN(1))
    assert profiler.arm(100) is False
    faults.disarm()
    c = metrics.REGISTRY.get("retry_attempts_total")
    assert c.value(site="profiler.arm", outcome="fallback") == 1
    assert not profiler.enabled()
    assert profiler.arm(100) is True


# ---------------------------------------------------------------------------
# /profilez over a real websocket server


def test_profilez_bounded_capture_over_real_server():
    """GET /profilez?duration_ms=N arms a bounded window, captures the
    traffic served DURING it, returns valid Perfetto JSON, and leaves
    the profiler disarmed."""
    from fluidframework_tpu.service.network_server import FluidNetworkServer

    svc = PipelineFluidService(n_partitions=2)
    conn = svc.connect("pz-doc")
    srv = FluidNetworkServer(service=svc)
    srv.start()
    try:
        result: dict = {}

        def fetch():
            result["body"] = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/profilez?duration_ms=400",
                timeout=10,
            ).read()

        t = threading.Thread(target=fetch)
        t.start()
        # Drive serving traffic while the window is armed (the profiler
        # is process-global; these submits run the instrumented seams).
        deadline = time.monotonic() + 3
        c0 = 1
        while not profiler.enabled() and time.monotonic() < deadline:
            time.sleep(0.005)
        for _ in range(4):
            conn.submit_frame(_one_frame(conn, svc, "pz-doc", c0=c0))
            c0 += 3
            svc.pump()
        svc.flush_device()
        t.join(10)
        assert "body" in result, "profilez request did not complete"
        doc = json.loads(result["body"])
        names = {
            e["name"] for e in doc["traceEvents"] if e["ph"] == "X"
        }
        assert {"ticket", "host_stage", "dispatch"} <= names, names
        assert not profiler.enabled(), "capture must disarm after window"
    finally:
        srv.stop()


def test_profilez_rejects_nonfinite_window_and_serializes_captures():
    """Two edge contracts on the untrusted surface: a NaN/inf
    duration_ms is rejected with 400 (NaN slips through min/max clamps
    and would defeat the self-disarm deadline AND hang the handler's
    sleep), and a second capture request while one is armed gets 409 —
    a concurrent arm would reset the ring mid-capture and the first
    disarm would truncate the second window."""
    from fluidframework_tpu.service.network_server import FluidNetworkServer

    svc = PipelineFluidService(n_partitions=2)
    srv = FluidNetworkServer(service=svc)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        for bad in ("nan", "inf", "-inf", "bogus"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{base}/profilez?duration_ms={bad}", timeout=5
                )
            assert ei.value.code == 400, bad
            assert not profiler.enabled(), bad
        # The in-process arm refuses non-finite windows too (counted,
        # absorbed — never armed-forever).
        assert profiler.arm(float("nan")) is False
        assert not profiler.enabled()
        result: dict = {}

        def fetch():
            result["body"] = urllib.request.urlopen(
                f"{base}/profilez?duration_ms=600", timeout=10
            ).read()

        t = threading.Thread(target=fetch)
        t.start()
        deadline = time.monotonic() + 3
        while not profiler.enabled() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert profiler.enabled()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{base}/profilez?duration_ms=5", timeout=5
            )
        assert ei.value.code == 409
        assert profiler.enabled(), "409 must not disturb the live capture"
        t.join(10)
        json.loads(result["body"])  # the first capture completes intact
    finally:
        srv.stop()


def test_arm_honors_long_inprocess_windows():
    """In-process callers (benches) may arm windows longer than the
    /profilez clamp — only the untrusted HTTP surface clamps to
    MAX_WINDOW_MS; a bench's 120s capture must not self-disarm after
    10s mid-workload."""
    assert profiler.arm(120_000)
    now = time.perf_counter()
    assert profiler.PROFILER._until - now > 100.0
    profiler.record("gc_pause", now, now + 0.001)  # well inside window
    assert profiler.enabled()


def test_profilez_is_not_shed_exempt():
    """The shed-tier contract, the OPPOSITE way from /metrics and
    /debugz: an armed capture allocates, so /profilez 503s with
    Retry-After at SHED_READS and every tier above — while the two
    exempt surfaces stay reachable through the whole walk (the tier-walk
    sibling of the SHED_READS push test)."""
    from fluidframework_tpu.service.admission import Tier
    from fluidframework_tpu.service.network_server import FluidNetworkServer

    svc = PipelineFluidService(n_partitions=2)
    srv = FluidNetworkServer(service=svc)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(
            f"{base}/profilez?duration_ms=5", timeout=5
        ).read()
        json.loads(body)  # NORMAL tier: capture served
        for tier in (
            Tier.SHED_READS, Tier.THROTTLE_WRITES, Tier.REFUSE_CONNECTIONS
        ):
            svc.overload.force(tier)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{base}/profilez?duration_ms=5", timeout=5
                )
            assert ei.value.code == 503, tier
            assert ei.value.headers.get("Retry-After") is not None, tier
            assert not profiler.enabled(), tier  # nothing armed
            # The exempt observability pair still serves at this tier.
            assert urllib.request.urlopen(
                f"{base}/metrics", timeout=5
            ).status == 200
            assert urllib.request.urlopen(
                f"{base}/debugz", timeout=5
            ).status == 200
        svc.overload.force(Tier.NORMAL)  # walk back down...
        svc.overload.force(None)  # ...and unpin
        body = urllib.request.urlopen(
            f"{base}/profilez?duration_ms=5", timeout=5
        ).read()
        json.loads(body)  # back to NORMAL: capture served again
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Runtime watchdogs


def test_loop_stall_sentinel_catches_a_blocking_call():
    """An injected blocking sleep on the socket loop overshoots the
    sentinel's expected tick: the stall is counted, journaled BY NAME
    (loop.stall), exported on the event_loop_lag_ms gauge, and — with a
    capture armed — recorded on the loop_lag timeline lane."""
    import asyncio

    from fluidframework_tpu.service.network_server import FluidNetworkServer

    svc = PipelineFluidService(n_partitions=2)
    srv = FluidNetworkServer(service=svc)
    srv.loop_lag_threshold_ms = 60.0
    srv.start()
    try:
        deadline = time.monotonic() + 5
        while srv.lag_ticks < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.lag_ticks >= 2, "sentinel never ticked"
        assert profiler.arm(5000)

        async def block():
            time.sleep(0.15)  # a synchronous stall ON the loop

        asyncio.run_coroutine_threadsafe(block(), srv._loop).result(5)
        deadline = time.monotonic() + 5
        while srv.stalls_seen == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.stalls_seen >= 1, "blocking call not caught"
        stalls = [
            e for e in journal.JOURNAL.events() if e.kind == "loop.stall"
        ]
        assert stalls, "stall must land in the flight recorder"
        assert dict(stalls[0].detail)["lag_ms"] >= 60.0
        lag_ivs = [
            iv for iv in profiler.intervals() if iv.lane == "loop_lag"
        ]
        assert lag_ivs and lag_ivs[0].dur >= 0.06
        # The gauge exists and was fed (healthy ticks may have already
        # overwritten the stall value — the journal carries the event).
        assert metrics.REGISTRY.get("event_loop_lag_ms") is not None
    finally:
        profiler.disarm()
        srv.stop()


def test_gc_pause_hooks_feed_metrics_and_timeline():
    """gc.callbacks pause hooks: every collection lands on the
    gc_pause_ms histogram and the gen-labelled gc_pauses_total counter,
    and on the gc_pause timeline lane while a capture is armed. The
    callback itself is LOCK-FREE by contract (a collection can trigger
    mid-allocation inside a metrics or ring lock on the same thread —
    taking any lock there deadlocks the thread against itself): it only
    buffers, and the read surfaces / the lag sentinel drain."""
    fresh = profiler.install_gc_hooks()
    try:
        assert profiler.arm(60_000)
        gc.collect(2)
        # The buffered pause is invisible until a drain runs (the
        # callback touched no metric); intervals() drains implicitly.
        pauses = [
            iv for iv in profiler.intervals() if iv.lane == "gc_pause"
        ]
        assert pauses and pauses[0].dur >= 0.0
        hist = metrics.REGISTRY.get("gc_pause_ms")
        assert hist is not None and hist.count() >= 1
        counter = metrics.REGISTRY.get("gc_pauses_total")
        assert counter is not None and counter.value(gen="2") >= 1
        # A drained buffer is empty; a second explicit drain is a no-op.
        assert profiler.drain_gc_events() == 0
        # Idempotent install: a second install is a no-op.
        assert profiler.install_gc_hooks() is False
    finally:
        profiler.disarm()
        if fresh:
            profiler.uninstall_gc_hooks()
