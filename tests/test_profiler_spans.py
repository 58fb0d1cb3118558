"""The profiler's one record site (``profiler.span`` / ``record``): lane
totals that are always on, spans that are also trace annotations on the
device trace's clock, and the producer sites of the served path —
pipeline stages, the socket front door, the read path, AOT builds, the
lag sentinel — each feeding the lane a benchmark reader takes deltas of.
"""

import asyncio
import glob
import os
import threading
import time

import jax
import pytest

from fluidframework_tpu.parallel import aot
from fluidframework_tpu.protocol.opframe import OpFrame
from fluidframework_tpu.service.device_backend import DeviceFleetBackend
from fluidframework_tpu.service.pipeline import PipelineFluidService
from fluidframework_tpu.telemetry import profiler

from test_profiler import MINT, _pump_rounds

PIPELINE_LANES = (
    "front_door", "deli", "scribe", "scriptorium", "broadcast",
    "device_stage",
)


@pytest.fixture(autouse=True)
def _clean_profiler():
    profiler.reset()
    yield
    profiler.reset()


def _frame(conn, svc, doc, k=3, c0=1):
    origs = [conn.conn_no * MINT + c0 + j for j in range(k)]
    return OpFrame.build(
        "s", ["ins"] * k, [0] * k, origs, ["x"] * k, csn0=c0,
        ref=svc.doc_head(doc),
    )


def _submit_frames(svc, doc: str, n: int, k: int = 3):
    conn = svc.connect(doc)
    for i in range(n):
        conn.submit_frame(_frame(conn, svc, doc, k=k, c0=1 + i * k))
    svc.pump()
    svc.flush_device()
    return conn


# ---------------------------------------------------------------------------
# span(): vocabulary, totals, the ring while armed


@pytest.mark.parametrize("lane", ["not.a.lane", "loop_other"])
def test_span_rejects_an_unknown_or_derived_lane(lane):
    with pytest.raises(ValueError):
        profiler.span(lane)
    with pytest.raises(ValueError):
        profiler.record(lane, 0.0, 1.0)
    assert lane not in profiler.totals()


def test_disarmed_span_updates_totals_without_interval_or_lock(monkeypatch):
    """Disarmed, a span costs its clock reads, its annotation and two
    adds: no Interval is allocated and the ring's lock is never taken."""
    made, locked = [], []

    class CountingInterval(profiler.Interval):
        def __init__(self, *a, **kw):
            made.append(1)
            super().__init__(*a, **kw)

    class CountingLock:
        def __enter__(self):
            locked.append(1)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(profiler, "Interval", CountingInterval)
    monkeypatch.setattr(profiler.PROFILER, "_lock", CountingLock())
    assert not profiler.enabled()
    with profiler.span("deli") as sp:
        pass
    profiler.record("feed_wait", 1.0, 1.5, boxcar=3, rows=2)
    n, seconds, own = profiler.totals()["deli"]
    assert (n, seconds, own) == (1, sp.t1 - sp.t0, sp.t1 - sp.t0)
    assert profiler.totals()["feed_wait"] == (1, 0.5, 0.5)
    assert made == [] and locked == []
    assert profiler.PROFILER.seen == 0


def test_armed_interval_holds_the_floats_the_totals_summed():
    assert profiler.arm(60_000)
    with profiler.span("host_stage", boxcar=7, rows=5) as sp:
        sp.rows = 4  # known only inside the block
    profiler.record("device_step", 2.0, 2.25, boxcar=7)
    ivs = {iv.lane: iv for iv in profiler.intervals()}
    assert (ivs["host_stage"].t0, ivs["host_stage"].t1) == (sp.t0, sp.t1)
    assert (ivs["host_stage"].boxcar, ivs["host_stage"].rows) == (7, 4)
    assert profiler.totals()["host_stage"][:2] == (1, sp.t1 - sp.t0)
    assert (ivs["device_step"].t0, ivs["device_step"].t1) == (2.0, 2.25)
    assert profiler.totals()["device_step"] == (1, 0.25, 0.25)


def test_own_seconds_leave_out_what_inner_spans_cover():
    """A stage that triggers a device feed is not charged the feed: own
    seconds are the span's less the spans opened inside it; a span on
    another thread nests in nothing here."""
    with profiler.span("device_stage") as outer:
        with profiler.span("host_stage") as inner:
            time.sleep(0.002)
        other = threading.Thread(
            target=lambda: profiler.span("deli").__enter__()
        )
        other.start()
        other.join(5)
    t = profiler.totals()
    assert t["device_stage"][1] == outer.t1 - outer.t0
    assert t["device_stage"][2] == pytest.approx(
        (outer.t1 - outer.t0) - (inner.t1 - inner.t0), abs=1e-12
    )
    assert t["host_stage"][1] == t["host_stage"][2] == inner.t1 - inner.t0


def test_held_span_commits_nothing_until_its_owner_records():
    with profiler.span("read_transfer", commit=False) as sp:
        pass
    assert profiler.totals()["read_transfer"][0] == 0
    profiler.record("read_transfer", sp.t0, sp.t1)
    assert profiler.totals()["read_transfer"][:2] == (1, sp.t1 - sp.t0)


def test_staging_seconds_are_the_lanes_own_floats_disarmed():
    """``staging_s`` = Σ host_stage + Σ ring_put and ``pump_busy_s`` =
    Σ device_step from the always-on totals too, no capture armed."""
    be = DeviceFleetBackend(capacity=128, max_batch=1 << 20, pump_mode=True)
    _pump_rounds(be, rounds=5)
    t = profiler.totals()
    assert t["host_stage"][0] == t["ring_put"][0] == be.pump_dispatches
    assert be.flush_totals["staging_s"] == pytest.approx(
        t["host_stage"][1] + t["ring_put"][1], abs=1e-9
    )
    assert be.pump_busy_s == pytest.approx(t["device_step"][1], abs=1e-9)
    assert profiler.PROFILER.seen == 0


# ---------------------------------------------------------------------------
# The producer sites


def test_real_rows_and_every_pipeline_lane_after_n_frames():
    svc = PipelineFluidService(n_partitions=2)
    _submit_frames(svc, "rows-doc", n=5, k=3)
    dev = svc.device
    assert dev.flush_totals["real_rows"] == dev.ops_applied == 15
    # Padded to the [B, K] bucket, the old counter reads more.
    assert dev.flush_totals["staged_rows"] > dev.flush_totals["real_rows"]
    t = profiler.totals()
    for lane in PIPELINE_LANES:
        assert t[lane][0] > 0 and t[lane][2] > 0, lane
    assert t["front_door"][0] == 5  # one per submitted frame


def test_oneshot_flush_counts_real_rows_too():
    svc = PipelineFluidService(n_partitions=2, device_pump=False)
    _submit_frames(svc, "oneshot-doc", n=3, k=2)
    assert svc.device.flush_totals["real_rows"] == svc.device.ops_applied == 6


def test_aot_build_seconds_grow_on_a_miss_and_stay_on_a_hit():
    key = ("test_profiler_spans", time.time())

    def build():
        return jax.jit(lambda x: x + 1)

    x = jax.numpy.zeros((4,), jax.numpy.int32)
    before = aot.stats()
    aot.call(key, build, x)
    missed = aot.stats()
    assert missed["builds"] == before["builds"] + 1
    assert missed["build_s"] > before["build_s"]
    assert profiler.totals()["aot_build"][0] == 1
    aot.call(key, build, x)
    hit = aot.stats()
    assert hit["build_s"] == missed["build_s"]
    assert hit["calls"] == missed["calls"] + 1
    assert profiler.totals()["aot_build"][1] == pytest.approx(
        missed["build_s"] - before["build_s"], abs=1e-12
    )


def test_lag_sum_grows_while_the_loop_is_blocked():
    from fluidframework_tpu.service.network_server import FluidNetworkServer

    srv = FluidNetworkServer(service=PipelineFluidService(n_partitions=2))
    srv.start()
    try:
        deadline = time.monotonic() + 5
        while srv.lag_ticks < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.lag_ticks >= 2, "sentinel never ticked"
        before, ticks = srv.lag_sum_ms, srv.lag_ticks

        async def block():
            time.sleep(0.2)  # a synchronous stall ON the loop

        asyncio.run_coroutine_threadsafe(block(), srv._loop).result(5)
        deadline = time.monotonic() + 5
        while srv.lag_ticks < ticks + 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.lag_sum_ms - before >= 100.0
        # Past the stall threshold the overshoot is a loop_lag interval
        # as well, armed or not.
        assert profiler.totals()["loop_lag"][0] >= 1
    finally:
        srv.stop()


def test_socket_lanes_over_real_websockets():
    """Websocket writers feed the socket front door's decode
    (``front_door``) and the delivery sweep (``socket_out``)."""
    from fluidframework_tpu.drivers.network_driver import NetworkFluidService
    from fluidframework_tpu.models.shared_string import SharedString
    from fluidframework_tpu.runtime.container import ContainerRuntime
    from fluidframework_tpu.service.network_server import FluidNetworkServer

    from test_network import drain_networked

    srv = FluidNetworkServer(service=PipelineFluidService(n_partitions=2))
    srv.start()
    try:
        rts = [
            ContainerRuntime(
                NetworkFluidService("127.0.0.1", srv.port), "sock-doc",
                channels=(SharedString("s"),),
            )
            for _ in range(2)
        ]
        for i, rt in enumerate(rts):
            for j in range(3):  # >= 2 same-channel ops: the frame wire
                rt.get_channel("s").insert_text(0, chr(97 + 3 * i + j))
        drain_networked(rts)
        assert len({rt.get_channel("s").get_text() for rt in rts}) == 1
        t = profiler.totals()
        # A decode and a submit per frame received.
        assert t["front_door"][0] >= 2 * srv.frames_received >= 4
        assert t["socket_out"][0] >= 1 and t["socket_out"][2] > 0
        for rt in rts:
            rt.disconnect()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# On the device trace's clock


def _host_events(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    path, = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
    ]


def test_spans_land_in_the_xplane_inside_their_sweep(tmp_path):
    """Under ``jax.profiler.start_trace`` a pipeline sweep and one REST
    read leave ``fluid.*`` events in the ``.xplane.pb``, the stage spans
    inside the wall of the sweep that ran them (one clock, no offset),
    a boxcar's spans with its id as an event stat."""
    from fluidframework_tpu.drivers.network_driver import NetworkFluidService
    from fluidframework_tpu.service.network_server import FluidNetworkServer

    svc = PipelineFluidService(n_partitions=2)
    srv = FluidNetworkServer(service=svc)
    srv.start()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    try:
        conn = asyncio.run_coroutine_threadsafe(
            _on_loop(svc.connect, "xp-doc"), srv._loop
        ).result(60)
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            def sweep():
                with jax.profiler.TraceAnnotation("test.sweep"):
                    conn.submit_frame(_frame(conn, svc, "xp-doc"))
                    svc.flush_device()

            asyncio.run_coroutine_threadsafe(
                _on_loop(sweep), srv._loop
            ).result(120)
            text = NetworkFluidService(
                "127.0.0.1", srv.port
            ).get_channel_text("xp-doc", "s")
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.stop()
    assert text == "xxx"
    events = _host_events(str(tmp_path))
    by_name: dict = {}
    for name, t0, t1, stats in events:
        by_name.setdefault(name, []).append((t0, t1, stats))
    (lo, hi, _), = by_name["test.sweep"]
    for lane in (*PIPELINE_LANES, "host_stage", "ring_put", "dispatch"):
        spans = by_name.get(f"fluid.{lane}")
        assert spans, f"no fluid.{lane} event in the trace"
        assert any(lo <= t0 and t1 <= hi for t0, t1, _ in spans), lane
    for lane in ("read_settle", "read_gather", "read_transfer",
                 "read_finish"):
        assert by_name.get(f"fluid.{lane}"), f"no fluid.{lane} event"
    # The flush a read forces runs its sweep inside the read's span.
    (r0, r1, _), = by_name["fluid.read_settle"]
    assert any(r0 <= t0 and t1 <= r1 for t0, t1, _ in by_name["fluid.deli"])
    staged = [s for _, _, s in by_name["fluid.ring_put"] if "boxcar" in s]
    assert staged and all(int(s["rows"]) >= 1 for s in staged)
    # The waits are intervals without an annotation.
    assert "fluid.read_wait" not in by_name
    assert profiler.totals()["read_wait"][0] >= 1


async def _on_loop(fn, *args):
    return fn(*args)
