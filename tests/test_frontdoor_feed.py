"""The continuous front door (r12): streaming, time-bounded boxcar
formation — ``DeviceFleetBackend.pump_feed``'s hybrid size/deadline
trigger, fed from the pipeline pump sweep and the network server's
deadline ticker.

Pinned here: continuous-feed vs quiescence-flush bit parity (dense and
the 8-device mesh), the deadline trigger firing on sub-threshold rows
with NO further traffic, eager dispatch under ring backpressure never
dropping a staged boxcar, the one-scan-readback-per-round transfer
contract extended to the ticker's off-loop prefetch path, the
``feed_wait`` stage on the trace spine, and the pipeline/network-server
wiring end to end (lane-for-lane pool state + log head parity against
the quiescence path)."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from fluidframework_tpu.parallel.mesh import make_mesh
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
from fluidframework_tpu.telemetry import tracing


def _round_frames(n_ch, k, r):
    rows = np.zeros((n_ch, k, OP_WIDTH), np.int32)
    ar = np.arange(k, dtype=np.int32)
    rows[:, :, F_TYPE] = OP_INSERT
    rows[:, :, F_LEN] = 1
    rows[:, :, F_SEQ] = r * k + 1 + ar[None, :]
    rows[:, :, F_REF] = r * k
    rows[:, :, F_ARG] = r * k + 1 + ar[None, :]
    texts = tuple(chr(97 + (r * k + i) % 26) for i in range(k))
    return rows, texts


def _feed(be, n_ch, k, r):
    rows, texts = _round_frames(n_ch, k, r)
    for i in range(n_ch):
        be.enqueue_frame(f"d{i}", SeqFrame("s", 0, 1, rows[i], texts, 0.0))


def _assert_state_parity(a: DeviceFleetBackend, b: DeviceFleetBackend):
    assert sorted(a.fleet.pools) == sorted(b.fleet.pools)
    for cap, pool_a in a.fleet.pools.items():
        pool_b = b.fleet.pools[cap]
        for name, x, y in zip(
            pool_a.state._fields, pool_a.state, pool_b.state
        ):
            assert bool(jnp.array_equal(x, y)), (cap, name)


def _run_continuous(be, n_ch, k, rounds):
    """Feed each round through the streaming trigger (deadline 0 — every
    feed tick stages), never through flush(): the pure front-door path."""
    for r in range(rounds):
        _feed(be, n_ch, k, r)
        be.pump_feed()
    be.pump_drain()


def _run_quiescence(be, n_ch, k, rounds):
    for r in range(rounds):
        _feed(be, n_ch, k, r)
        be.flush()
    be.collect_now()


def test_feed_parity_dense():
    """Identical op streams through the continuous feed (deadline-
    triggered stage + eager dispatch, no flush on the hot path) and the
    quiescence flush converge to bit-identical pool states, totals, and
    served text."""
    n_ch, k, rounds = 6, 4, 5
    cont = DeviceFleetBackend(
        capacity=64, pump_mode=True, feed_deadline_ms=0.0
    )
    quiesce = DeviceFleetBackend(capacity=64, pump_mode=True)
    _run_continuous(cont, n_ch, k, rounds)
    _run_quiescence(quiesce, n_ch, k, rounds)
    assert cont.ops_applied == quiesce.ops_applied == n_ch * k * rounds
    assert cont.feed_triggers["deadline"] == rounds
    _assert_state_parity(cont, quiesce)
    assert cont.text("d0", "s") == quiesce.text("d0", "s")
    assert len(cont.text("d0", "s")) == k * rounds
    assert cont.stats()["docs_with_errors"] == 0


def test_feed_parity_mesh():
    """Same parity pin on the 8-device virtual mesh: the feed's AOT
    shard_map dispatches and the quiescence path produce bit-identical
    sharded pool states."""
    mesh = make_mesh()
    n_ch, k, rounds = 16, 4, 3
    cont = DeviceFleetBackend(
        capacity=64, mesh=mesh, pump_mode=True, feed_deadline_ms=0.0
    )
    quiesce = DeviceFleetBackend(capacity=64, mesh=mesh, pump_mode=True)
    _run_continuous(cont, n_ch, k, rounds)
    _run_quiescence(quiesce, n_ch, k, rounds)
    assert cont.ops_applied == quiesce.ops_applied == n_ch * k * rounds
    _assert_state_parity(cont, quiesce)
    assert cont.text("d3", "s") == quiesce.text("d3", "s")


def test_size_trigger_fires_mid_stream():
    """Boxcars stage the moment the buffers reach max_batch — no
    deadline wait, no quiescence: the size half of the hybrid trigger
    now owns the enqueue-time auto-flush in pump mode (a full boxcar
    rides the feed's stage + eager dispatch)."""
    n_ch, k = 4, 4
    be = DeviceFleetBackend(
        capacity=64, max_batch=n_ch * k, pump_mode=True,
        feed_deadline_ms=1e6,  # deadline can never fire in this test
    )
    _feed(be, n_ch, k, 0)
    # The last frame's enqueue filled the boxcar: the size trigger
    # staged and dispatched it mid-stream, no flush() anywhere.
    assert be.feed_triggers["size"] == 1
    assert be.ops_applied == n_ch * k
    _feed(be, n_ch - 1, k, 1)
    assert be.pump_feed() == []  # sub-threshold, deadline armed: no-op
    assert be.ops_applied == n_ch * k
    rows, texts = _round_frames(n_ch, k, 1)
    be.enqueue_frame(
        f"d{n_ch - 1}", SeqFrame("s", 0, 1, rows[n_ch - 1], texts, 0.0)
    )
    assert be.feed_triggers["size"] == 2
    assert be.ops_applied == 2 * n_ch * k
    be.pump_drain()
    assert len(be.text("d0", "s")) == 2 * k


class _SteppedClock:
    """Stands in for the ``time`` module inside device_backend: the feed
    edge and the deadline check read ``perf_counter`` from here, so the
    test — not the machine's load — decides how much time has passed."""

    def __init__(self):
        self.now = time.perf_counter()

    def perf_counter(self) -> float:
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


def test_deadline_trigger_fires_without_further_traffic(monkeypatch):
    """Sub-threshold rows dispatch once feed_deadline_ms elapses even if
    no further row ever arrives — the trigger needs no future traffic,
    only a tick (the network server's ticker supplies those)."""
    from fluidframework_tpu.service import device_backend

    n_ch, k = 2, 4
    be = DeviceFleetBackend(
        capacity=64, pump_mode=True, feed_deadline_ms=20.0
    )
    clock = _SteppedClock()
    monkeypatch.setattr(device_backend, "time", clock)
    _feed(be, n_ch, k, 0)
    clock.now += 0.019
    assert be.pump_feed() == []
    assert be.ops_applied == 0, "deadline not expired: rows must wait"
    assert be.needs_flush()
    clock.now += 0.002
    be.pump_feed()  # the next tick after the deadline stages + dispatches
    assert be.ops_applied == n_ch * k
    assert be.feed_triggers == {"size": 0, "deadline": 1}
    be.pump_drain()
    assert be.text("d0", "s") == be.text("d1", "s")
    assert len(be.text("d0", "s")) == k


def test_eager_dispatch_under_backpressure_keeps_boxcar():
    """Ring-full backpressure during a feed squeezes the oldest slot to
    the device first (pump_stage's contract) and the eager dispatch then
    drains the rest — every staged boxcar lands exactly once."""
    n_ch, k = 4, 4
    be = DeviceFleetBackend(
        capacity=64, pump_mode=True, ring_depth=1, feed_deadline_ms=0.0
    )
    for r in range(3):
        _feed(be, n_ch, k, r)
        be.pump_stage()  # stage only: ring (depth 1) squeezes each round
    assert be.pump_backpressure == 2
    _feed(be, n_ch, k, 3)
    be.pump_feed()  # deadline trigger over a full ring: backpressure + stage
    assert be.pump_backpressure == 3
    assert len(be._ring) == 0  # eager dispatch drained the staged slot
    be.pump_drain()
    assert be.ops_applied == n_ch * k * 4
    assert be.stats()["docs_with_errors"] == 0
    assert len(be.text("d0", "s")) == k * 4


def test_feed_round_is_one_scan_readback(monkeypatch):
    """The transfer contract extends to the feed and the ticker: a
    steady feed round performs EXACTLY one device→host transfer (the
    stale scan), and a round whose scan the ticker prefetched off-loop
    performs that SAME single transfer inside scan_transfer — zero new
    readbacks either way."""
    from fluidframework_tpu.parallel import fleet as fleet_mod
    from fluidframework_tpu.service import device_backend as db_mod

    n_ch, k = 4, 4
    be = DeviceFleetBackend(
        capacity=64, pump_mode=True, feed_deadline_ms=0.0
    )
    _feed(be, n_ch, k, 0)
    be.pump_feed()  # warm + leave a scan in flight

    transfers = []

    def _shim(mod):
        real_np = mod.np

        class _CountingNp:
            def __getattr__(self, name):
                return getattr(np, name)

            @staticmethod
            def asarray(*a, **kw):
                if a and isinstance(a[0], jax.Array):
                    transfers.append(("asarray", mod.__name__))
                return real_np.asarray(*a, **kw)

            @staticmethod
            def array(*a, **kw):
                if a and isinstance(a[0], jax.Array):
                    transfers.append(("array", mod.__name__))
                return real_np.array(*a, **kw)

        monkeypatch.setattr(mod, "np", _CountingNp())

    _shim(fleet_mod)
    _shim(db_mod)
    for r in range(1, 3):  # plain feed rounds: one stale-scan transfer
        before = len(transfers)
        _feed(be, n_ch, k, r)
        be.pump_feed()
        assert len(transfers) - before == 1, transfers[before:]
    for r in range(3, 5):  # ticker rounds: the prefetch IS the transfer
        before = len(transfers)
        token = be.prefetch_scan()
        assert token is not None
        be.scan_prefetched(token, be.scan_transfer(token))
        assert len(transfers) - before == 1, transfers[before:]
        # An installed, unconsumed prefetch dedups: an idle ticker must
        # never re-run the same token's transfer.
        assert be.prefetch_scan() is None
        _feed(be, n_ch, k, r)
        be.pump_feed()  # consumes the prefetch: no further transfer
        assert len(transfers) - before == 1, transfers[before:]


def test_stale_prefetch_is_dropped_not_consumed():
    """A prefetch raced by a drain (the quiescence flush consumed and
    replaced the scan) is discarded on token mismatch — never applied to
    the wrong boxcar's consume."""
    n_ch, k = 2, 4
    be = DeviceFleetBackend(
        capacity=64, pump_mode=True, feed_deadline_ms=0.0
    )
    _feed(be, n_ch, k, 0)
    be.pump_feed()
    token = be.prefetch_scan()
    host = be.scan_transfer(token)
    # A racing drain consumes the scan before the prefetch installs...
    be.collect_now()
    be.scan_prefetched(token, host)
    # ...and the next round's consume must ignore the stale prefetch.
    _feed(be, n_ch, k, 1)
    be.pump_feed()
    be.pump_drain()
    assert be.ops_applied == n_ch * k * 2
    assert be._scan_prefetch is None
    assert len(be.text("d0", "s")) == 2 * k


def test_feed_trace_spans_include_feed_wait():
    """Sampled frames riding the continuous feed carry the r12
    ``feed_wait`` span (enqueue → feed trigger) nested inside the device
    span, alongside the r10 pump vocabulary — and the registry accepts
    the new stage."""
    n_ch, k = 2, 4
    be = DeviceFleetBackend(
        capacity=64, pump_mode=True, feed_deadline_ms=0.0
    )
    traces: list = []
    tracing.stamp(traces, tracing.STAGE_DEVICE, "start")
    be.track_trace(traces)
    _feed(be, n_ch, k, 0)
    be.pump_feed()
    be.collect_now()
    sp = tracing.spans(traces)
    for stage in (
        tracing.STAGE_FEED_WAIT,
        tracing.STAGE_RING_STAGE,
        tracing.STAGE_DEVICE_STEP,
        tracing.STAGE_SCAN_CONSUME,
        tracing.STAGE_DEVICE,
        tracing.STAGE_DEVICE_COMMIT,
    ):
        assert f"{stage}_ms" in sp, (stage, sp)
    from fluidframework_tpu.telemetry import metrics

    reg = metrics.MetricsRegistry()
    metrics.observe_stage_spans(sp, reg)
    assert reg.get("serving_stage_ms").count(stage="feed_wait") == 1


def test_pipeline_feed_matches_oneshot_service():
    """Pipeline-level parity: identical client traffic through a
    continuously-fed service (deadline 0 — every in-sweep tick stages)
    and a one-shot (pump_mode=False) service serves identical device
    text, bit-identical pool lanes, and the same durable log head."""
    from fluidframework_tpu.models.shared_string import SharedString
    from fluidframework_tpu.runtime.container import ContainerRuntime
    from fluidframework_tpu.service.pipeline import PipelineFluidService

    svcs = {}
    for mode in ("continuous", "oneshot"):
        svc = PipelineFluidService(
            n_partitions=2,
            device_pump=(mode == "continuous"),
            device_feed_deadline_ms=0.0,
        )
        rt = ContainerRuntime(svc, "doc", channels=(SharedString("s"),))
        s = rt.get_channel("s")
        s.insert_text(0, "front door feed")
        rt.flush()
        while rt.process_incoming():
            pass
        s.remove_range(0, 6)
        rt.flush()
        while rt.process_incoming():
            pass
        svc.pump()
        svc.flush_device()
        svcs[mode] = svc
    cont, oneshot = svcs["continuous"], svcs["oneshot"]
    assert cont.device.feed_triggers["deadline"] > 0, (
        "the in-sweep feed never fired — the front door is not streaming"
    )
    assert cont.device_text("doc", "s") == oneshot.device_text("doc", "s")
    assert cont.device_text("doc", "s") == "door feed"
    assert cont.doc_head("doc") == oneshot.doc_head("doc")
    _assert_state_parity(cont.device, oneshot.device)


def test_ticker_dispatches_subthreshold_rows_without_client_reads():
    """The network server's deadline ticker: rows buffered behind a
    raised device_flush_min_rows dispatch within the feed deadline with
    NO socket traffic at all — the only actor left is the asyncio
    ticker (``_pump_tick`` task), whose scan consume runs off-loop."""
    from fluidframework_tpu.service.network_server import FluidNetworkServer
    from fluidframework_tpu.service.pipeline import PipelineFluidService

    svc = PipelineFluidService(
        n_partitions=2, device_flush_min_rows=10_000,
        device_feed_deadline_ms=5.0,
    )
    srv = FluidNetworkServer(service=svc)
    srv.start()
    try:
        rows, texts = _round_frames(1, 3, 0)
        # Enqueue straight into the backend: no websocket read ever
        # happens, so _drain_all's idle flush can never fire — only the
        # ticker can apply these rows.
        svc.device.enqueue_frame(
            "tick-doc", SeqFrame("s", 0, 1, rows[0, :3], texts[:3], 0.0)
        )
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and svc.device.ops_applied < 3:
            time.sleep(0.005)
        assert svc.device.ops_applied == 3, (
            srv.pump_ticks, svc.device.stats(),
        )
        assert svc.device.feed_triggers["deadline"] >= 1
        assert srv.pump_ticks >= 1
    finally:
        srv.stop()
