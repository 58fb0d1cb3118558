"""Serving-path observability (r9): the unified metrics registry, the
frame-granular trace spine, the single-readback device telemetry lanes,
and both ``/metrics`` exposition surfaces.

Reference: every sequenced message may ride an ``ITrace[]``
(``protocol-definitions/src/protocol.ts``, sampled by alfred's
``numberOfMessagesPerTrace``) and every service lambda completes a
``Lumberjack`` metric — here all of it reduces into one process
registry (``telemetry/metrics.py``) rendered in Prometheus text format,
with the device lanes scraped in exactly ONE batched readback
(telemetry/README.md contract)."""

import socket
import time
import urllib.request

import numpy as np
import pytest

from fluidframework_tpu.models.shared_string import SharedString
from fluidframework_tpu.protocol.types import DocumentMessage, MessageType
from fluidframework_tpu.runtime.container import ContainerRuntime
from fluidframework_tpu.service.local_server import LocalFluidService
from fluidframework_tpu.service.pipeline import PipelineFluidService
from fluidframework_tpu.telemetry import metrics, tracing
from fluidframework_tpu.telemetry.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Every test sees an empty process registry (the module-global is
    shared state by design; tests must not see each other's tallies)."""
    metrics.REGISTRY.reset()
    yield
    metrics.REGISTRY.reset()


# ---------------------------------------------------------------------------
# The registry primitives


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", labelnames=("op",))
    c.inc(op="get")
    c.inc(2, op="get")
    c.inc(op="put")
    assert c.value(op="get") == 3
    assert c.value(op="put") == 1
    assert c.value(op="absent") == 0
    with pytest.raises(ValueError):
        c.inc(-1, op="get")  # counters only go up
    with pytest.raises(ValueError):
        c.inc(opp="typo")  # undeclared label set

    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.inc(-2)
    assert g.value() == 5

    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 3
    assert h.sum() == pytest.approx(55.5)

    # get-or-create is idempotent; re-registering under another kind or
    # label set is a programming error.
    assert reg.counter("reqs_total", labelnames=("op",)) is c
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")
    with pytest.raises(ValueError):
        reg.counter("reqs_total", labelnames=("other",))


def test_histogram_exposition_is_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("h", "", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.render()
    assert 'h_bucket{le="1"} 1' in text
    assert 'h_bucket{le="10"} 2' in text
    assert 'h_bucket{le="+Inf"} 3' in text
    assert "h_count 3" in text
    assert "h_sum 55.5" in text


def test_registry_render_is_replica_deterministic():
    """Two replicas that observed the same values in DIFFERENT orders
    render byte-equal text and equal snapshots — the graftlint
    determinism bar applied to telemetry."""

    def feed(reg, order):
        for op, n in order:
            reg.counter("ops_total", "ops", labelnames=("op",)).inc(n, op=op)
        reg.gauge("occ", "occupancy", labelnames=("shard",)).set(4, shard="1")
        reg.gauge("occ", "occupancy", labelnames=("shard",)).set(9, shard="0")
        for v in (3.0, 0.2):
            reg.histogram("st_ms", "stage", labelnames=("stage",)).observe(
                v, stage="deli"
            )

    a, b = MetricsRegistry(), MetricsRegistry()
    feed(a, [("get", 2), ("put", 1)])
    feed(b, [("put", 1), ("get", 1), ("get", 1)])
    assert a.render() == b.render()
    assert a.snapshot() == b.snapshot()
    # And the order is actually sorted: families by name, samples by label.
    lines = [l for l in a.render().splitlines() if not l.startswith("#")]
    assert lines == sorted(lines) or lines.index(
        'occ{shard="0"} 9'
    ) < lines.index('occ{shard="1"} 4')


def test_render_escapes_label_values():
    """Label values can carry request-derived strings: backslash, quote,
    and newline must render escaped (Prometheus text format), never as
    injected exposition lines."""
    reg = MetricsRegistry()
    reg.counter("c", "", labelnames=("k",)).inc(k='a"} 1\nfake_metric 2')
    text = reg.render()
    assert 'c{k="a\\"} 1\\nfake_metric 2"} 1' in text
    assert "\nfake_metric" not in text


def test_store_unknown_op_collapses_to_one_label():
    """The store socket is unauthenticated: client-supplied op strings
    must not mint registry label sets — unknown ops count as one
    'unknown' label."""
    from fluidframework_tpu.service.store_server import StoreServer

    srv = StoreServer(port=0, n_partitions=2)
    for op in ("x0", "x1", "x2"):
        resp, _ = srv.dispatch({"op": op}, b"")
        assert not resp["ok"]
    ctr = metrics.REGISTRY.get("store_requests_total")
    assert ctr.value(op="unknown") == 3
    assert 'op="x0"' not in metrics.REGISTRY.render()


def test_lumber_completion_feeds_registry():
    from fluidframework_tpu.telemetry import (
        CollectingEngine,
        LumberEventName,
        Lumberjack,
    )

    Lumberjack.setup([CollectingEngine()])
    try:
        m = Lumberjack.new_metric(
            LumberEventName.DeliHandler, {"tenantId": "t", "documentId": "d"}
        )
        m.success("ok")
        m2 = Lumberjack.new_metric(
            LumberEventName.DeliHandler, {"tenantId": "t", "documentId": "d"}
        )
        m2.error("bad")
    finally:
        Lumberjack.reset()
    ctr = metrics.REGISTRY.get("lumber_events_total")
    assert ctr.value(event=LumberEventName.DeliHandler, outcome="ok") == 1
    assert ctr.value(event=LumberEventName.DeliHandler, outcome="error") == 1
    hist = metrics.REGISTRY.get("lumber_duration_ms")
    assert hist.count(event=LumberEventName.DeliHandler) == 2


def test_stage_span_reduction_and_summary():
    reg = MetricsRegistry()
    metrics.observe_stage_spans({"deli_ms": 2.0, "total_ms": 5.0}, reg)
    metrics.observe_stage_spans({"deli_ms": 4.0, "total_ms": 7.0}, reg)
    assert metrics.stage_span_summary(reg) == {"deli": 3.0, "total": 6.0}
    # On the process registry with nothing observed: empty, not an error.
    assert metrics.stage_span_summary() == {}


# ---------------------------------------------------------------------------
# Satellite bugfix: the per-op path must close the alfred span at
# broadcast — without it spans() can never produce alfred_ms.


def _submit_one_traced(svc):
    conn = svc.connect("doc")
    join_seq = conn.take_inbox()[-1].sequence_number
    conn.submit(
        DocumentMessage(
            client_sequence_number=1,
            reference_sequence_number=join_seq,
            type=MessageType.OPERATION,
            contents={"x": 1},
        )
    )
    [msg] = [m for m in conn.take_inbox() if m.type == MessageType.OPERATION]
    return msg


def test_per_op_alfred_end_stamped_at_broadcast_local():
    msg = _submit_one_traced(LocalFluidService(messages_per_trace=1))
    assert tracing.has_stamp(msg.traces, tracing.STAGE_ALFRED, "end")
    sp = tracing.spans(msg.traces)
    assert sp["alfred_ms"] >= 0  # the span the bug kept unreachable
    assert sp["alfred_ms"] >= sp["deli_ms"]  # alfred brackets the ticket
    # ... and the completed trace reduced into the shared stage histogram.
    hist = metrics.REGISTRY.get("serving_stage_ms")
    assert hist.count(stage="alfred") == 1


def test_per_op_alfred_end_stamped_at_broadcast_pipeline():
    msg = _submit_one_traced(
        PipelineFluidService(n_partitions=2, messages_per_trace=1)
    )
    assert tracing.has_stamp(msg.traces, tracing.STAGE_ALFRED, "end")
    assert tracing.spans(msg.traces)["alfred_ms"] >= 0
    assert metrics.REGISTRY.get("serving_stage_ms").count(stage="alfred") >= 1


def test_forged_client_traces_cannot_mint_stage_labels():
    """``traces`` is a protocol wire field a client controls: a forged
    list must not mint new label sets in the process registry (unbounded
    growth) — only the known stage vocabulary is ever observed."""
    svc = PipelineFluidService(n_partitions=2)  # server sampling OFF
    conn = svc.connect("doc")
    join_seq = conn.take_inbox()[-1].sequence_number
    conn.submit(
        DocumentMessage(
            client_sequence_number=1,
            reference_sequence_number=join_seq,
            type=MessageType.OPERATION,
            contents={"x": 1},
            traces=[
                {"service": "alfred", "action": "start", "timestamp": 1.0},
                {"service": "evil-42", "action": "start", "timestamp": 1.0},
                {"service": "evil-42", "action": "end", "timestamp": 9.0},
            ],
        )
    )
    # With server sampling off, NOTHING client-supplied reaches the
    # registry at all...
    assert metrics.REGISTRY.get("serving_stage_ms") is None


def test_out_of_range_spans_are_not_observed():
    """Trace timestamps are cooperative: an absolute-epoch or skewed
    stamp (span of ~1e12 ms, or negative) must not poison the histogram
    sums even when sampling is on."""
    reg = MetricsRegistry()
    metrics.observe_stage_spans(
        {"alfred_ms": 1.7e12, "deli_ms": -5.0, "total_ms": 3.0}, reg
    )
    hist = reg.get("serving_stage_ms")
    assert hist.count(stage="alfred") == 0
    assert hist.count(stage="deli") == 0
    assert hist.count(stage="total") == 1


def test_replayed_sequenced_op_observes_once():
    """A deli crash/replay re-emits the same sequenced op downstream:
    the broadcaster must not re-stamp alfred end or double-observe."""
    from fluidframework_tpu.service.lambdas import BroadcasterLambda

    bl = BroadcasterLambda({}, observe_traces=True)
    traces: list = []
    tracing.stamp(traces, tracing.STAGE_ALFRED, "start")  # real clock: stays under the sanity clamp
    msg = type("M", (), {"traces": traces, "sequence_number": 1})()
    bl.handler("doc", {"t": "seq", "msg": msg})
    bl.handler("doc", {"t": "seq", "msg": msg})  # the replayed copy
    assert [
        t for t in traces
        if (t["service"], t["action"]) == (tracing.STAGE_ALFRED, "end")
    ] == traces[-1:]
    assert metrics.REGISTRY.get("serving_stage_ms").count(stage="alfred") == 1


def test_untraced_per_op_observes_nothing():
    msg = _submit_one_traced(LocalFluidService())  # sampling off
    assert msg.traces == []
    assert metrics.REGISTRY.get("serving_stage_ms") is None


# ---------------------------------------------------------------------------
# The TraceBook ledger


def test_trace_book_completion_rules():
    reg = MetricsRegistry()
    book = tracing.TraceBook(expect_device=True, registry=reg)
    t = book.open()
    tracing.stamp(t, tracing.STAGE_ALFRED, "start", 1.0)
    tracing.stamp(t, tracing.STAGE_BROADCAST, "start", 1.01)
    tracing.stamp(t, tracing.STAGE_BROADCAST, "end", 1.02)
    # Broadcast done but the frame reached the device stage: incomplete
    # until the commit readback lands.
    tracing.stamp(t, tracing.STAGE_DEVICE, "start", 1.03)
    assert book.reap() == 0 and book.live == 1
    tracing.stamp(t, tracing.STAGE_DEVICE, "end", 1.04)
    tracing.stamp(t, tracing.STAGE_DEVICE_COMMIT, "start", 1.04)
    tracing.stamp(t, tracing.STAGE_DEVICE_COMMIT, "end", 1.06)
    assert book.reap() == 1 and book.live == 0
    [sp] = book.completed
    assert sp["device_commit_ms"] == pytest.approx(20.0, abs=1e-6)
    assert reg.get("serving_stage_ms").count(stage="device_commit") == 1

    # A frame that never reached the device completes at broadcast.
    t2 = book.open()
    tracing.stamp(t2, tracing.STAGE_BROADCAST, "end", 2.0)
    assert book.reap() == 1

    # Without a device stage, broadcast alone completes.
    host_book = tracing.TraceBook(expect_device=False, registry=reg)
    t3 = host_book.open()
    tracing.stamp(t3, tracing.STAGE_BROADCAST, "end", 3.0)
    tracing.stamp(t3, tracing.STAGE_DEVICE, "start", 3.0)  # ignored
    assert host_book.reap() == 1


def test_trace_book_bounds_incomplete_stragglers():
    book = tracing.TraceBook(max_live=4, keep_completed=2)
    for _ in range(10):
        book.open()  # nacked/dup frames never complete
    assert book.live == 4 and book.dropped == 6
    for i in range(5):
        t = book.open()
        tracing.stamp(t, tracing.STAGE_BROADCAST, "end", float(i))
    book.reap()
    assert len(book.completed) == 2  # bounded tail for benches/tests


# ---------------------------------------------------------------------------
# The frame spine end-to-end over real websockets


def _drain(runtimes, timeout=10.0):
    for rt in runtimes:
        rt.flush()
    deadline = time.monotonic() + timeout
    quiet = 0
    while time.monotonic() < deadline and quiet < 3:
        if any(rt.process_incoming() for rt in runtimes):
            quiet = 0
        else:
            quiet += 1
            time.sleep(0.02)


def _run_frame_clients(svc, n_clients=3):
    from fluidframework_tpu.drivers.network_driver import NetworkFluidService
    from fluidframework_tpu.service.network_server import FluidNetworkServer

    srv = FluidNetworkServer(service=svc)
    srv.start()
    try:
        rts = [
            ContainerRuntime(
                NetworkFluidService("127.0.0.1", srv.port),
                "fd",
                channels=(SharedString("s"),),
            )
            for _ in range(n_clients)
        ]
        for i, rt in enumerate(rts):
            ch = rt.get_channel("s")
            for j in range(4):  # >=2 same-channel ops: frame-eligible
                ch.insert_text(0, chr(97 + (i * 4 + j) % 26))
        _drain(rts)
        svc.flush_device()
        assert srv.frames_received >= n_clients, "frame wire not taken"
        texts = {rt.get_channel("s").get_text() for rt in rts}
        assert len(texts) == 1  # observability must not perturb convergence
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ).read().decode()
        for rt in rts:
            rt.disconnect()
        return body
    finally:
        srv.stop()


def test_frame_trace_e2e_over_real_sockets():
    """A sampled frame crossing the real-websocket multi-client harness
    yields the COMPLETE stage decomposition — every frame-spine stage
    stamped, reduced into the registry, visible on GET /metrics."""
    svc = PipelineFluidService(n_partitions=2, messages_per_trace=1)
    body = _run_frame_clients(svc)

    # Every SEQUENCED sampled frame completed. A client retry can land a
    # fully-duplicate frame that deli's MSN dedup drops whole — its trace
    # legitimately never passes the ticket (the TraceBook's documented
    # straggler case, bounded by max_live), so it must show no stage
    # after deli.
    for t in svc.trace_book._live:
        assert not tracing.has_stamp(t, tracing.STAGE_SCRIPTORIUM, "start")
        assert not tracing.has_stamp(t, tracing.STAGE_BROADCAST, "start")
    assert len(svc.trace_book.completed) >= 3
    for sp in svc.trace_book.completed:
        for stage in tracing.FRAME_STAGES:
            assert f"{stage}_ms" in sp, f"stage {stage} missing: {sorted(sp)}"
        assert sp["total_ms"] >= 0
    summary = metrics.stage_span_summary()
    assert set(tracing.FRAME_STAGES) <= set(summary)

    # The exposition carries the spine histogram AND the per-shard device
    # lanes the scrape's single readback produced.
    assert "# TYPE serving_stage_ms histogram" in body
    assert 'serving_stage_ms_bucket{stage="device_commit",le="+Inf"}' in body
    assert "# TYPE device_shard_telemetry gauge" in body
    assert 'col="rows_in_use"' in body and 'col="err_docs"' in body
    assert 'device_backend_totals{key="flushes"}' in body


def test_unsampled_frames_allocate_no_trace_lists():
    """With sampling off the spine costs nothing: no trace lists, no
    ledger entries, no stage histogram — the sampler gate is the only
    per-frame branch."""
    svc = PipelineFluidService(n_partitions=2)  # messages_per_trace=0
    body = _run_frame_clients(svc)
    assert svc.trace_sampler is None
    assert svc.trace_book.live == 0 and svc.trace_book.completed == []
    assert metrics.REGISTRY.get("serving_stage_ms") is None
    assert "serving_stage_ms" not in body
    # The device lanes still publish: scrape telemetry is sampling-independent.
    assert "device_shard_telemetry" in body


# ---------------------------------------------------------------------------
# Device telemetry lanes: one batched readback per scrape


def _collab(svc, doc="doc", n=6):
    rts = [
        ContainerRuntime(svc, doc, channels=(SharedString("s"),))
        for _ in range(2)
    ]
    for i in range(n):
        rts[i % 2].get_channel("s").insert_text(0, chr(97 + i))
    for rt in rts:
        rt.flush()
    while any(rt.process_incoming() for rt in rts):
        pass
    svc.flush_device()
    return rts


def test_telemetry_slice_is_one_readback(monkeypatch):
    """The /metrics device contract: a scrape's fleet telemetry comes
    back as ONE np.asarray readback no matter how many pools are
    resident — never a per-pool or per-lane pull."""
    from fluidframework_tpu.parallel import fleet as fleet_mod

    svc = PipelineFluidService(n_partitions=2)
    _collab(svc)

    calls = []
    real = fleet_mod.np.asarray

    class _CountingNp:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

    monkeypatch.setattr(fleet_mod, "np", _CountingNp())
    tel = svc.device.fleet.telemetry_slice()
    assert len(calls) == 1, f"{len(calls)} readbacks for one scrape"

    from fluidframework_tpu.parallel.fleet import TELEMETRY_COLS

    assert sorted(tel) == sorted(svc.device.fleet.pools)
    occ_i = TELEMETRY_COLS.index("rows_in_use")
    err_i = TELEMETRY_COLS.index("err_docs")
    stats = svc.device.fleet.stats()
    assert sum(int(a[:, occ_i].sum()) for a in tel.values()) == stats[
        "rows_in_use"
    ]
    assert sum(int(a[:, err_i].sum()) for a in tel.values()) == stats[
        "docs_with_errors"
    ]


def test_publish_metrics_populates_shard_gauges():
    svc = PipelineFluidService(n_partitions=2)
    _collab(svc)
    tel = svc.device.publish_metrics()
    g = metrics.REGISTRY.get("device_shard_telemetry")
    for cap, arr in tel["shards"].items():
        for shard in range(arr.shape[0]):
            for i, col in enumerate(tel["cols"]):
                assert g.value(
                    pool=str(cap), shard=str(shard), col=col
                ) == int(arr[shard, i])
    totals = metrics.REGISTRY.get("device_backend_totals")
    assert totals.value(key="ops_applied") == svc.device.ops_applied
    assert totals.value(key="flushes") == svc.device._flushes


def test_backend_scrape_is_one_readback(monkeypatch):
    """The WHOLE backend scrape — fleet pools plus any sharded-overflow
    rows — comes back as one np.asarray, not one per group."""
    from fluidframework_tpu.service import device_backend as db_mod

    svc = PipelineFluidService(n_partitions=2)
    _collab(svc)

    calls = []
    real = db_mod.np.asarray

    class _CountingNp:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

    monkeypatch.setattr(db_mod, "np", _CountingNp())
    tel = svc.device.telemetry()
    assert len(calls) == 1, f"{len(calls)} readbacks for one scrape"
    assert "sharded" not in tel["shards"]  # no overflow docs in this run


def test_sharded_overflow_docs_visible_in_scrape():
    """Docs promoted off the top fleet tier into ShardedDocs must NOT go
    dark: the scrape carries a 'sharded' pool row with their per-mesh-
    shard occupancy, inside the same single readback."""
    from fluidframework_tpu.parallel.fleet import TELEMETRY_COLS

    svc = PipelineFluidService(
        n_partitions=2, device_capacity=8, device_max_capacity=8,
        device_sharded_overflow=True,
    )
    a = ContainerRuntime(svc, "doc", channels=(SharedString("s"),))
    s = a.get_channel("s")
    for i in range(14):  # crosses the 8-row top tier mid-session
        s.insert_text(0, chr(ord("a") + i % 26))
        if i % 4 == 3:
            a.flush()
            while a.process_incoming():
                pass
    a.flush()
    while a.process_incoming():
        pass
    svc.flush_device()
    assert svc.device.stats()["sharded_docs"] == 1

    tel = svc.device.publish_metrics()
    arr = tel["shards"]["sharded"]
    occ_i = TELEMETRY_COLS.index("rows_in_use")
    live_i = TELEMETRY_COLS.index("live_slots")
    assert int(arr[:, occ_i].sum()) == 14
    assert (arr[:, live_i] == 1).all()  # the one doc spans every shard
    g = metrics.REGISTRY.get("device_shard_telemetry")
    assert g.value(pool="sharded", shard="0", col="rows_in_use") == int(
        arr[0, occ_i]
    )


def test_mesh_shard_telemetry_layout():
    """DocShard.telemetry_slice: per-mesh-shard rows in the shared
    TELEMETRY_COLS layout, one batched readback."""
    from fluidframework_tpu.parallel.fleet import TELEMETRY_COLS
    from fluidframework_tpu.parallel.mesh import DocShard, make_mesh

    mesh = make_mesh()
    n_docs = mesh.devices.size * 2
    shard = DocShard(n_docs, 64, mesh=mesh)
    out = shard.telemetry_slice()
    assert out.shape == (mesh.devices.size, len(TELEMETRY_COLS))
    occ_i = TELEMETRY_COLS.index("live_slots")
    assert int(out[:, occ_i].sum()) == n_docs


# ---------------------------------------------------------------------------
# /metrics exposition surfaces


def test_store_server_metrics_endpoint():
    from fluidframework_tpu.service.store_server import (
        RemoteBlobBackend,
        StoreServer,
    )

    node = StoreServer(port=0, n_partitions=2).serve_background()
    try:
        be = RemoteBlobBackend(node.host, node.port)
        be.put_blob(b"observable")
        with socket.create_connection((node.host, node.port), timeout=5) as s:
            s.sendall(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
            buf = b""
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                buf += chunk
        head, _, body = buf.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.1 200 OK")
        assert b"text/plain; version=0.0.4" in head
        text = body.decode()
        assert "# TYPE store_requests_total counter" in text
        assert 'store_requests_total{op="blob.put"} 1' in text
    finally:
        node.close()


# ---------------------------------------------------------------------------
# Satellite: the tree fallback burn-down is visible on /metrics


def test_tree_fallback_counters_reach_registry():
    from fluidframework_tpu.tree import marks as M
    from fluidframework_tpu.tree.edit_manager import Commit, EditManager

    em = EditManager(session=1)
    tiny = []
    for i in range(2):  # below DEVICE_MIN_BATCH -> host, reason=min_batch
        cells = [(900_000 + i * 10 + j, i * 10 + j) for j in range(2)]
        tiny.append(
            Commit(
                session=9,
                seq=i + 1,
                ref=i,
                change=M.normalize([M.insert(cells)]),
            )
        )
    em.add_sequenced_batch(tiny, min_seq=0)
    assert em.host_fallback_reason["min_batch"] == len(tiny)

    ctr = metrics.REGISTRY.get("tree_ingest_commits_total")
    assert ctr is not None, "fallback counters never reached the registry"
    assert ctr.value(path="host", reason="min_batch") == len(tiny)
    # ... and the rendered exposition names the bucket.
    text = metrics.REGISTRY.render()
    assert (
        'tree_ingest_commits_total{path="host",reason="min_batch"} 2' in text
    )


def test_tree_device_commits_reach_registry():
    from fluidframework_tpu.tree import marks as M
    from fluidframework_tpu.tree.edit_manager import Commit, EditManager

    em = EditManager(session=1)
    log = []
    for i in range(8):  # >= DEVICE_MIN_BATCH, caught-up -> device path
        cells = [(800_000 + i * 10 + j, i * 10 + j) for j in range(2)]
        log.append(
            Commit(
                session=9,
                seq=i + 1,
                ref=i,
                change=M.normalize([M.insert(cells)]),
            )
        )
    em.add_sequenced_batch(log, min_seq=len(log))
    assert em.device_commits == len(log)
    ctr = metrics.REGISTRY.get("tree_ingest_commits_total")
    assert ctr.value(path="device", reason="") == len(log)


# ---------------------------------------------------------------------------
# r14 satellites: trace-drop accounting + stage-span quantiles


def test_trace_book_drop_accounting_reaches_registry():
    """Traces that age out of the ledger (max_live eviction) used to
    vanish into a host-side int; the registry now counts them
    (trace_frames_dropped_total{reason="max_live"}) — a regression here
    would silently re-blind the sampled-trace loss signal."""
    reg = MetricsRegistry()
    book = tracing.TraceBook(max_live=4, registry=reg)
    for _ in range(10):
        book.open()
    assert book.dropped == 6
    ctr = reg.get("trace_frames_dropped_total")
    assert ctr is not None
    assert ctr.value(reason="max_live") == 6
    # The default-registry TraceBook feeds the process registry.
    book2 = tracing.TraceBook(max_live=2)
    for _ in range(3):
        book2.open()
    assert metrics.trace_dropped_counter().value(reason="max_live") == 1


def test_stage_span_summary_quantiles():
    """p50/p95/p99 estimates from the existing fixed-bucket histogram:
    interpolated within the bucket, ordered, bounded by the bucket edges
    — and the default (mean-only) shape is unchanged."""
    reg = MetricsRegistry()
    hist = reg.histogram(
        "serving_stage_ms", "spans", labelnames=("stage",)
    )
    # 100 observations spread 1..100 ms for one stage; a tight cluster
    # for another.
    for v in range(1, 101):
        hist.observe(float(v), stage="deli")
    for _ in range(10):
        hist.observe(0.05, stage="broadcast")
    # Default shape: plain means (the r9 artifact contract).
    means = metrics.stage_span_summary(registry=reg)
    assert means["deli"] == pytest.approx(50.5, abs=0.01)
    assert isinstance(means["deli"], float)
    q = metrics.stage_span_summary(
        registry=reg, quantiles=(0.5, 0.95, 0.99)
    )
    deli = q["deli"]
    assert set(deli) == {"mean", "p50", "p95", "p99"}
    assert deli["mean"] == means["deli"]
    # Ordered and inside the right buckets: the median of 1..100 falls
    # in the (25, 50] bucket, the p99 in the (50, 100] bucket.
    assert deli["p50"] <= deli["p95"] <= deli["p99"]
    assert 25.0 < deli["p50"] <= 50.0
    assert 50.0 < deli["p99"] <= 100.0
    # A cluster entirely inside the first bucket stays there.
    assert q["broadcast"]["p99"] <= 0.1


def test_quantile_interpolation_exact_cases():
    """The interpolation arithmetic, pinned: counts concentrated in one
    bucket interpolate linearly across it; ranks past the last finite
    bucket clamp to its bound (the honest fixed-bucket answer)."""
    buckets = (1.0, 2.0, 4.0)
    # 4 observations in the (1, 2] bucket: p50 lands mid-bucket.
    assert metrics._bucket_quantile(buckets, [0, 4, 0, 0], 0.5) == (
        pytest.approx(1.5)
    )
    # Empty histogram: 0.
    assert metrics._bucket_quantile(buckets, [0, 0, 0, 0], 0.99) == 0.0
    # Everything in +Inf: clamp to the last finite bound.
    assert metrics._bucket_quantile(buckets, [0, 0, 0, 5], 0.5) == 4.0


def test_stage_p99_rides_the_spans_histogram():
    """Per-stage p99 comes from the same histogram the means come
    from."""
    metrics.observe_stage_spans({"deli_ms": 3.0, "total_ms": 9.0})
    metrics.observe_stage_spans({"deli_ms": 4.0, "total_ms": 12.0})
    q = metrics.stage_span_summary(quantiles=(0.99,))
    p99 = {stage: row["p99"] for stage, row in q.items()}
    assert set(p99) == {"deli", "total"}
    assert p99["deli"] <= 5.0 and p99["total"] <= 25.0
