"""The continuous device pump (r10): double-buffered ingest ring + AOT
donated dispatch in ``DeviceFleetBackend``.

Pinned here: pump-vs-one-shot state parity on identical op streams (dense
and mesh fleets), ring-full backpressure, the in-flight-dispatch shutdown
drain (no lost, no duplicated ops), the zero-per-flush-tracing AOT
contract (entries built once per shape bucket, never per flush), the
one-health-scan-readback-per-round transfer contract, and the pump stage
vocabulary on the frame trace spine."""

import jax
import jax.numpy as jnp
import numpy as np

from fluidframework_tpu.parallel import aot
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
from fluidframework_tpu.protocol.opframe import SeqFrame
from fluidframework_tpu.service.device_backend import DeviceFleetBackend
from fluidframework_tpu.telemetry import tracing


def _round_frames(n_ch, k, r):
    """One round's insert frames: contiguous seqs r*k+1..(r+1)*k per
    channel, inserts at position 0 (text reads back reversed)."""
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


def _run_rounds(be, n_ch, k, rounds, continuous):
    for r in range(rounds):
        _feed(be, n_ch, k, r)
        if continuous:
            be.pump_stage()
            be.pump_dispatch()
        else:
            be.flush()
    if continuous:
        be.pump_drain()
    else:
        be.flush()
        be.collect_now()


def test_pump_parity_dense():
    """Identical op streams through the pump (continuous stage/dispatch)
    and the legacy one-shot flush path converge to bit-identical pool
    states, the same applied totals, and the same served text."""
    n_ch, k, rounds = 6, 4, 5
    pump = DeviceFleetBackend(capacity=64, pump_mode=True)
    oneshot = DeviceFleetBackend(capacity=64, pump_mode=False)
    _run_rounds(pump, n_ch, k, rounds, continuous=True)
    _run_rounds(oneshot, n_ch, k, rounds, continuous=False)
    assert pump.ops_applied == oneshot.ops_applied == n_ch * k * rounds
    _assert_state_parity(pump, oneshot)
    assert pump.text("d0", "s") == oneshot.text("d0", "s")
    assert len(pump.text("d0", "s")) == k * rounds
    assert pump.stats()["docs_with_errors"] == 0


def test_pump_parity_mesh():
    """Same parity pin on the mesh fleet (the 8-device virtual CPU mesh
    from conftest): the pump's AOT shard_map dispatch and the one-shot
    path produce bit-identical sharded pool states."""
    mesh = make_mesh()
    n_ch, k, rounds = 16, 4, 3
    pump = DeviceFleetBackend(capacity=64, mesh=mesh, pump_mode=True)
    oneshot = DeviceFleetBackend(capacity=64, mesh=mesh, pump_mode=False)
    _run_rounds(pump, n_ch, k, rounds, continuous=True)
    _run_rounds(oneshot, n_ch, k, rounds, continuous=False)
    assert pump.ops_applied == oneshot.ops_applied == n_ch * k * rounds
    _assert_state_parity(pump, oneshot)
    assert pump.text("d3", "s") == oneshot.text("d3", "s")


def test_ring_full_backpressure():
    """Staging past the ring depth dispatches the oldest slot first: at
    most ``ring_depth`` uploads are ever in flight, the backpressure
    counter records the squeeze, and nothing is lost."""
    n_ch, k = 4, 4
    be = DeviceFleetBackend(capacity=64, pump_mode=True, ring_depth=2)
    for r in range(3):
        _feed(be, n_ch, k, r)
        be.pump_stage()  # stage only — no dispatch between rounds
    assert len(be._ring) == 2  # third stage squeezed the oldest slot out
    assert be.pump_backpressure == 1
    assert be.pump_dispatches == 1
    be.pump_drain()
    assert len(be._ring) == 0
    assert be._scan_token is None
    assert be.ops_applied == n_ch * k * 3
    assert be.text("d0", "s") == be.text("d1", "s")
    assert len(be.text("d0", "s")) == k * 3


def test_drain_with_inflight_dispatch_no_lost_or_dup_ops():
    """Shutdown drain with a dispatch in flight: rows staged behind an
    unconsumed health scan all land exactly once, and at-least-once
    redelivery of already-staged rows is dropped by the watermarks (no
    duplicate application)."""
    n_ch, k = 3, 4
    be = DeviceFleetBackend(capacity=64, pump_mode=True)
    ref = DeviceFleetBackend(capacity=64, pump_mode=False)
    _feed(be, n_ch, k, 0)
    be.pump_stage()
    be.pump_dispatch()  # dispatch round 0; its scan is now in flight
    assert be._scan_token is not None
    _feed(be, n_ch, k, 0)  # full replay of round 0: must drop whole
    _feed(be, n_ch, k, 1)  # fresh round staged behind the in-flight scan
    be.pump_stage()
    be.pump_drain()
    assert be.ops_applied == n_ch * k * 2  # no lost, no duplicated rows
    for r in range(2):
        _feed(ref, n_ch, k, r)
        ref.flush()
    ref.collect_now()
    _assert_state_parity(be, ref)


def test_aot_entries_built_once_per_shape_bucket():
    """The zero-per-flush-tracing contract: after one warm flush per
    shape bucket, steady-state flushes are pure AOT cache hits — calls
    grow, builds do not."""
    n_ch, k = 4, 4
    be = DeviceFleetBackend(capacity=64, pump_mode=True)
    _feed(be, n_ch, k, 0)
    be.flush()  # warm: builds the fused entry for this bucket
    warm = aot.stats()
    rounds = 5
    for r in range(1, rounds + 1):
        _feed(be, n_ch, k, r)
        be.flush()
    steady = aot.stats()
    assert steady["builds"] == warm["builds"], (
        "steady-state flushes must not build AOT entries "
        f"(warm={warm}, steady={steady})"
    )
    assert steady["calls"] >= warm["calls"] + rounds  # pure cache hits


def test_pump_round_is_one_scan_readback(monkeypatch):
    """The pump's transfer contract: a steady round performs EXACTLY one
    device→host transfer — consuming the previous round's health scan —
    and no synchronous np.asarray readback anywhere in the dispatch
    path."""
    from fluidframework_tpu.parallel import fleet as fleet_mod
    from fluidframework_tpu.service import device_backend as db_mod

    n_ch, k = 4, 4
    be = DeviceFleetBackend(capacity=64, pump_mode=True)
    _feed(be, n_ch, k, 0)
    be.flush()  # warm + leave a scan in flight

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
    for r in range(1, 4):
        before = len(transfers)
        _feed(be, n_ch, k, r)
        be.pump_stage()
        be.pump_dispatch()
        got = transfers[before:]
        assert len(got) == 1, f"round {r}: {got}"  # the one stale scan


def test_pump_trace_spans_cover_stage_vocabulary():
    """Sampled frames riding the pump carry the r10 stage vocabulary:
    ring_stage (host assembly + async upload), device_step (the AOT
    dispatch call), scan_consume (the stale-scan readback wait) — and
    the legacy device/device_commit spans still bracket them."""
    n_ch, k = 2, 4
    be = DeviceFleetBackend(capacity=64, pump_mode=True)
    traces: list = []
    tracing.stamp(traces, tracing.STAGE_DEVICE, "start")
    be.track_trace(traces)
    _feed(be, n_ch, k, 0)
    be.flush()
    be.collect_now()  # consumes the scan: closes scan_consume + commit
    sp = tracing.spans(traces)
    for stage in (
        tracing.STAGE_RING_STAGE,
        tracing.STAGE_DEVICE_STEP,
        tracing.STAGE_SCAN_CONSUME,
        tracing.STAGE_DEVICE,
        tracing.STAGE_DEVICE_COMMIT,
    ):
        assert f"{stage}_ms" in sp, (stage, sp)
    # The observability registry accepts the new vocabulary.
    from fluidframework_tpu.telemetry import metrics

    reg = metrics.MetricsRegistry()
    metrics.observe_stage_spans(sp, reg)
    hist = reg.get("serving_stage_ms")
    assert hist.count(stage="ring_stage") == 1
    assert hist.count(stage="device_step") == 1
    assert hist.count(stage="scan_consume") == 1


def test_pipeline_pump_matches_oneshot_service():
    """Pipeline-level parity: the same client traffic through a pump
    service and a one-shot service serves identical device text (the
    production wiring of ``device_pump``)."""
    from fluidframework_tpu.models.shared_string import SharedString
    from fluidframework_tpu.runtime.container import ContainerRuntime
    from fluidframework_tpu.service.pipeline import PipelineFluidService

    texts = {}
    for pump in (True, False):
        svc = PipelineFluidService(n_partitions=2, device_pump=pump)
        rt = ContainerRuntime(svc, "doc", channels=(SharedString("s"),))
        s = rt.get_channel("s")
        s.insert_text(0, "pump parity")
        rt.flush()
        while rt.process_incoming():
            pass
        s.remove_range(0, 5)
        rt.flush()
        while rt.process_incoming():
            pass
        assert svc.device.pump_mode is pump
        texts[pump] = svc.device_text("doc", "s")
    assert texts[True] == texts[False] == "parity"


def test_pump_promotion_reroutes_staged_rows():
    """A doc that crosses its tier's high-water mark mid-stream promotes
    off the one-boxcar-stale scan, and rows staged before the promotion
    was consumed re-route to the new pool at dispatch time (slots resolve
    at dispatch, not at stage)."""
    n_ch, k, rounds = 2, 8, 8
    pump = DeviceFleetBackend(capacity=16, max_capacity=256, pump_mode=True)
    oneshot = DeviceFleetBackend(
        capacity=16, max_capacity=256, pump_mode=False
    )
    _run_rounds(pump, n_ch, k, rounds, continuous=True)
    _run_rounds(oneshot, n_ch, k, rounds, continuous=False)
    assert pump.fleet.migrations > 0  # the stream really promoted
    assert pump.ops_applied == oneshot.ops_applied == n_ch * k * rounds
    _assert_state_parity(pump, oneshot)
    assert len(pump.text("d0", "s")) == k * rounds


# -- the step's own scan and the dirty-set compaction on the pump (S9) ---------


def _spy_scans(be, monkeypatch):
    """Every scan ``_consume_scan`` is handed, in order: cap -> the
    scanned slots, counts and errs as lists."""
    seen = []
    consume = be._consume_scan

    def spy(scans, newly):
        seen.append(
            {c: tuple(x.tolist() for x in s) for c, s in scans.items()}
        )
        consume(scans, newly)

    monkeypatch.setattr(be, "_consume_scan", spy)
    return seen


def test_capacity_error_reaches_the_nack_path_one_boxcar_stale(monkeypatch):
    """A channel overflows the only tier in its ninth boxcar: that
    boxcar's dispatch reports nothing, the consume at the next dispatch
    is handed the boxcar's one slot with the err bit on, and the channel
    is reported once."""
    from fluidframework_tpu.protocol.constants import ERR_CAPACITY

    be = DeviceFleetBackend(capacity=16, max_capacity=16, pump_mode=True)
    for i in range(64):
        be.ensure(f"d{i}", "s")  # a pool far larger than a boxcar
    seen = _spy_scans(be, monkeypatch)
    reported = []
    for r in range(10):  # two rows a boxcar: the tier's chunk limit
        rows, texts = _round_frames(2, 2, r)
        be.enqueue_frame("d9", SeqFrame("s", 0, 1, rows[0], texts, 0.0))
        if r < 2:  # d40 stays healthy and is in the first two boxcars
            be.enqueue_frame("d40", SeqFrame("s", 0, 1, rows[1], texts, 0.0))
        be.pump_stage()
        reported.append(be.pump_dispatch())
    reported.append(be.pump_drain())
    # Boxcar r's scan is consumed at boxcar r+1's dispatch.
    assert reported == [[]] * 9 + [[("d9", "s")], []]
    assert seen[0] == {16: ([9, 40], [2, 2], [0, 0])}
    assert seen[1] == {16: ([9, 40], [4, 4], [0, 0])}
    assert seen[2] == {16: ([9], [6], [0])}
    # The eighth boxcar's cadence compacted slots 9 and 40: the pass's
    # scan rides the ninth boxcar's token, the newer reading of 9 stands.
    assert seen[8] == {16: ([40, 9], [4, 16], [0, ERR_CAPACITY])}
    assert seen[9] == {16: ([9], [16], [ERR_CAPACITY])}
    assert be.take_errors() == [("d9", "s")]
    assert be.stats()["docs_with_errors"] == 1
    bucket = be.fleet.pools[16].compact_bucket
    assert be.flush_totals["compact_slots"] == bucket == 64
    assert be.flush_totals["scan_slots"] == 2 + 2 + 8 * 1 + bucket


def test_hot_document_promotes_off_the_boxcars_own_scan(monkeypatch):
    """The promotion fires on the count the step returned for the
    boxcar's slot, at the next dispatch, and nothing else is walked: the
    other 63 documents of the pool are in no scan."""
    be = DeviceFleetBackend(capacity=16, max_capacity=64, pump_mode=True)
    for i in range(64):
        be.ensure(f"d{i}", "s")
    seen = _spy_scans(be, monkeypatch)
    idx = be._index[("d5", "s")]
    tiers = []
    for r in range(8):
        rows, texts = _round_frames(1, 2, r)
        # Chunk limit: two rows a boxcar at this tier (half the headroom).
        be.enqueue_frame("d5", SeqFrame("s", 0, 1, rows[0], texts, 0.0))
        be.pump_stage()
        be.pump_dispatch()
        tiers.append(be.fleet.placement[idx][0])
    be.pump_drain()
    # 14 rows > 0.75 * 16 after boxcar 7 (r=6); consumed at boxcar 8's dispatch.
    assert tiers == [16] * 7 + [32]
    assert [list(s) for s in seen[:7]] == [[16]] * 7
    assert seen[6][16] == ([5], [14], [0])
    assert all(s[16][0] == [5] for s in seen[:7])
    assert be.fleet.migrations == 1 and be.stats()["docs_with_errors"] == 0
    assert len(be.text("d5", "s")) == 16


def test_pump_and_oneshot_agree_through_compaction_and_demotion():
    """Three compaction cadences with removes below the window, a
    promotion and the demotion that follows it: the pump's dirty-set
    compaction through the AOT entries and the one-shot flush's through
    the jitted ones leave bit-identical pools, and the same counters."""
    from fluidframework_tpu.ops import encode as E
    from fluidframework_tpu.protocol.constants import F_MSN

    def drive(be, continuous):
        for i in range(32):
            be.ensure(f"d{i}", "s")
        seq = 0
        for r in range(26):
            rows = np.zeros((2, OP_WIDTH), np.int32)
            if r < 9:  # 18 one-character inserts: past the 16-row tier
                for j in range(2):
                    seq += 1
                    rows[j] = E.insert(0, seq, 1, seq=seq, ref=seq - 1,
                                       client=1)
            else:  # then the text shrinks to two characters
                n = 18 - (r - 9)
                seq += 1
                rows[0] = E.remove(n - 1, n, seq=seq, ref=seq - 1,
                                   client=1) if n > 2 else 0
                if n <= 2:
                    rows[0] = E.insert(0, seq, 1, seq=seq, ref=seq - 1,
                                       client=1)
                rows[0, F_MSN] = seq
                rows = rows[:1]
            texts = tuple("x" for _ in range(len(rows)))
            be.enqueue_frame("d3", SeqFrame("s", 0, 1, rows, texts, 0.0))
            if continuous:
                be.pump_stage()
                be.pump_dispatch()
            else:
                be.flush()
        if continuous:
            be.pump_drain()
        else:
            be.flush()
            be.collect_now()

    pump = DeviceFleetBackend(capacity=16, max_capacity=64, pump_mode=True)
    oneshot = DeviceFleetBackend(capacity=16, max_capacity=64,
                                 pump_mode=False)
    drive(pump, True)
    drive(oneshot, False)
    for be in (pump, oneshot):
        assert be.fleet.migrations == 1 and be.fleet.demotions == 1
        assert be.stats()["docs_with_errors"] == 0
    for name in ("compact_slots", "scan_slots", "step_docs"):
        assert pump.flush_totals[name] == oneshot.flush_totals[name], name
    assert pump.flush_totals["compact_slots"] >= 3 * 8
    _assert_state_parity(pump, oneshot)
    assert pump.text("d3", "s") == oneshot.text("d3", "s")
