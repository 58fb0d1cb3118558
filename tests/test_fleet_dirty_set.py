"""Compaction and the health scan follow the boxcar's slots, not the
pool's (ROADMAP S9): the dirty-set ``fluid_compact``, the step's own
``[2, B]`` scan, the host's walk over the scanned slots, and the two
counters that say it engaged. The fleet's other cases are in
``tests/test_fleet.py``, whose helpers these use (a file of their own so
that the two run on two workers)."""

import jax
import numpy as np
import pytest
from test_fleet import (
    _STEP_CAP,
    _assert_states_equal,
    _edit_rows,
    _host,
)

from fluidframework_tpu.ops import encode as E
from fluidframework_tpu.ops.merge_kernel import batched_compact
from fluidframework_tpu.parallel import fleet as F
from fluidframework_tpu.parallel.fleet import DocFleet
from fluidframework_tpu.protocol.constants import F_MSN, F_SEQ, OP_WIDTH

# ``_Pool.compact_dirty`` runs the tier's compact engine over the slots
# written since the last compaction (``fleet._compact_entry``: gather,
# compact on [D, capacity], scatter back in place). Its contract is the
# whole-pool engine's result, lane for lane over EVERY slot: a slot not
# written since its last compaction has nothing new to reclaim.


def _reclaimable(rows):
    """``rows`` with the collab window moved up to each document's last
    op, so what the batch removed is below it: compaction has work."""
    rows = rows.copy()
    rows[:, -1, F_MSN] = rows[:, -1, F_SEQ]
    return rows


def _whole_pool_compact(state):
    """The reference: XLA's compaction over every slot of a host copy."""
    return _host(jax.jit(batched_compact)(jax.device_put(_host(state))))


def _mixed_pool(kernel, cap, n_slots, sharding, n_dirty, seed=0):
    """A pool whose every slot took reclaimable ops and was compacted,
    of which ``n_dirty`` slots (the last among them) then took more
    through the busy-set step: mixed dirty and clean. Returns the pool
    and the dirty slots."""
    rng = np.random.default_rng(seed)
    pool = F._Pool(cap, n_slots, kernel, sharding)
    n = pool.n_slots
    pool.doc_of_slot[:] = np.arange(n)
    for first in (1, 9):
        rows = _reclaimable(_edit_rows(rng, n, 8, first))
        pool.sparse_step(jax.device_put(rows), np.arange(n, dtype=np.int32))
    pool.compact_dirty()
    assert pool._dirty == []
    dirty = np.append(rng.choice(n - 1, n_dirty - 1, replace=False), n - 1)
    b = F._pow2_at_least(n_dirty)
    rows = np.zeros((b, 8, OP_WIDTH), np.int32)
    rows[:n_dirty] = _reclaimable(_edit_rows(rng, n_dirty, 8, 17))
    slots = np.full(b, n, np.int32)
    slots[:n_dirty] = dirty
    pool.sparse_step(jax.device_put(rows), slots)
    return pool, np.sort(dirty)


def _mesh_sharding(on_mesh):
    if not on_mesh:
        return None
    from jax.sharding import NamedSharding, PartitionSpec

    from fluidframework_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    return NamedSharding(make_mesh(), PartitionSpec("docs"))


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one_device", "mesh"])
@pytest.mark.parametrize(
    "kernel,cap",
    [("pallas", 64), ("pallas", 2 * F._PALLAS_COMPACT_MAX_CAP), ("xla", 64)],
    ids=["pallas_tier", "xla_tier_of_pallas_fleet", "xla_fleet"],
)
def test_dirty_set_compaction_equals_whole_pool_engine(kernel, cap, on_mesh):
    """Over a pool of mixed dirty and clean slots the dirty-set
    compaction leaves every lane of every slot as the whole-pool engine
    does, reclaims something, and compacting what is compacted is the
    identity (through the engine and through the dirty set alike)."""
    n_slots, n_dirty = 32, 5
    pool, dirty = _mixed_pool(
        kernel, cap, n_slots, _mesh_sharding(on_mesh), n_dirty
    )
    before = _host(pool.state)
    assert np.array_equal(np.unique(np.concatenate(pool._dirty)), dirty)
    want = _whole_pool_compact(before)
    assert (want.count[dirty] < before.count[dirty]).any()  # work was done
    ran = pool.compact_dirty()
    assert ran == pool.compact_bucket == 32 and pool._dirty == []
    _assert_states_equal(pool.state, want, "dirty set")
    clean = np.setdiff1d(np.arange(n_slots), dirty)
    _assert_states_equal(
        [np.asarray(x)[clean] for x in pool.state],
        [x[clean] for x in before], "clean slots",
    )
    # The passes' scan: the counts compaction left, in the dirty set's order.
    (dev, at, slots), = pool._scans[-1:]
    assert np.array_equal(slots, dirty)
    scan = np.asarray(dev).reshape(-1, 2, pool.compact_bucket).sum(axis=0)
    assert np.array_equal(scan[0, at], want.count[dirty])
    assert not scan[:, len(dirty):].any()
    # Identity on the compacted: the engine over the pool, and the dirty
    # set's entry over every slot.
    _assert_states_equal(_whole_pool_compact(want), want, "engine twice")
    pool.mark_dirty(np.arange(n_slots))
    pool.compact_dirty()
    _assert_states_equal(pool.state, want, "dirty set twice")


@pytest.mark.parametrize("n_dirty", [8, 9, 30])
def test_dirty_set_larger_than_its_bucket_compacts_in_passes(
    monkeypatch, n_dirty
):
    """A bucket of 8 slots and up to 30 dirty: the same program in one,
    two and four passes, to the whole-pool engine's bytes."""
    monkeypatch.setattr(F, "_COMPACT_CELLS", 8 * _STEP_CAP)
    pool, dirty = _mixed_pool("xla", _STEP_CAP, 64, None, n_dirty, seed=n_dirty)
    assert pool.compact_bucket == 8
    want = _whole_pool_compact(pool.state)
    n_scans = len(pool._scans)
    passes = -(-n_dirty // 8)
    assert pool.compact_dirty() == 8 * passes
    assert len(pool._scans) == n_scans + passes
    _assert_states_equal(pool.state, want, n_dirty)
    got = np.concatenate([s for _dev, _at, s in pool._scans[n_scans:]])
    assert np.array_equal(got, dirty)


def test_compact_bucket_follows_the_tier_not_the_pool():
    """At most ``_COMPACT_CELLS`` cells a lane a pass, at least
    ``_MIN_STEP_SLOTS`` slots, and past the tier's bucket no larger
    however many slots the pool has."""
    assert F._Pool(128, 16, "xla").compact_bucket == 16
    assert F._Pool(128, 1, "xla").compact_bucket == F._MIN_STEP_SLOTS
    assert F._Pool(128, 4096, "xla").compact_bucket == 1024
    assert F._Pool(128, 1 << 15, "xla").compact_bucket == 1024
    assert F._Pool(16384, 64, "xla").compact_bucket == F._MIN_STEP_SLOTS


def _lifecycle(fleet):
    """One consumed scan's promotion pass."""
    return fleet.check_and_migrate(fleet.finish_scan(fleet.begin_scan()))


def _fill(fleet, doc, n, seq0=0, per=8, grow=False):
    """``n`` one-character inserts into ``doc`` through the busy-set
    step, ``per`` a boxcar; ``grow`` runs the promotion pass between
    boxcars."""
    for lo in range(0, n, per):
        rows = np.zeros((1, 8, OP_WIDTH), np.int32)
        for i in range(min(per, n - lo)):
            seq = seq0 + lo + i + 1
            rows[0, i] = E.insert(0, seq, 1, seq=seq, ref=seq - 1, client=0)
        fleet.apply_sparse([doc], rows)
        if grow:
            _lifecycle(fleet)


def _remove_all_but(fleet, doc, keep, length, seq):
    """One remove of ``[keep, length)`` with the window moved past it."""
    rows = np.zeros((1, 8, OP_WIDTH), np.int32)
    rows[0, 0] = E.remove(keep, length, seq=seq, ref=seq - 1, client=0)
    rows[0, 0, F_MSN] = seq
    fleet.apply_sparse([doc], rows)


@pytest.mark.parametrize("what", ["err", "hot", "reassigned"])
def test_step_scan_delivers_what_the_whole_pool_scan_did(what):
    """The ``[2, B]`` scan of a boxcar, finished, names the boxcar's
    slots with the (count, err) a readback of the whole pool shows for
    them: a capacity error, a hot document, and nothing for a slot whose
    occupant changed between begin and finish (the generation mask)."""
    fleet = DocFleet(4, capacity=8, max_capacity=8 if what == "err" else 64)
    pool = fleet.pools[8]
    _fill(fleet, 1, 4)
    fleet.finish_scan(fleet.begin_scan())
    _fill(fleet, 2, 7 if what != "err" else 8)
    if what == "err":
        _fill(fleet, 2, 4, seq0=8)  # four more than the table holds
    token = fleet.begin_scan()
    devs, at, slots, gens = token[8]
    assert slots.tolist() == [2] * len(devs) and len(gens) == len(devs)
    assert all(d.shape == (2, 1) for d in devs)
    count = np.asarray(pool.state.count)
    err = np.asarray(pool.state.err)
    if what == "reassigned":
        fleet.evict_docs([2])
        fleet.restore_doc(2, fleet.evict_docs([1])[1])
        assert fleet.placement[2] == (8, 1)
    scans = fleet.finish_scan(token)
    s, c, e = (x.tolist() for x in scans[8])
    if what == "reassigned":
        assert (s, c, e) == ([], [], [])
        assert fleet.check_and_migrate(scans) == []
        return
    assert (s, c, e) == ([2], [int(count[2])], [int(err[2])])
    if what == "err":
        assert e == [F.ERR_CAPACITY] and fleet.check_and_migrate(scans) == []
    else:
        assert c == [7] and fleet.check_and_migrate(scans) == [2]
        assert fleet.placement[2][0] == 16
        # A pool no scan names is not walked: nothing more to promote.
        assert fleet.check_and_migrate({}) == []


def test_cold_candidate_beyond_max_moves_is_demoted_by_a_later_pass():
    """Three documents cool in the 32-row tier in one boxcar's scan and a
    pass may move one: the others are remembered on the host, and later
    passes whose scans name none of them demote them, unless a newer scan
    says the document heated up again."""
    fleet = DocFleet(4, capacity=8, max_capacity=64)
    for d in range(3):
        _fill(fleet, d, 24, per=1, grow=True)
    assert [fleet.placement[d][0] for d in range(4)] == [32, 32, 32, 8]
    assert fleet.stats()["docs_with_errors"] == 0
    for d in range(3):
        _remove_all_but(fleet, d, 1, 24, 25)
    assert fleet.compact() == 3 * 8  # one pass a pool, vacated slots too
    scans = fleet.finish_scan(fleet.begin_scan())
    slots, counts, _errs = scans[32]
    assert sorted(slots.tolist()) == [0, 1, 2] and counts.tolist() == [1] * 3
    assert fleet.check_and_demote(scans, max_moves=1) == [0]
    assert sorted(fleet.pools[32].cold_left) == [1, 2]
    # Document 0 landed in a slot of the 16-row tier that the same scan
    # had read empty: cold there too (one row), and left over as well.
    assert fleet.placement[0] == (16, 0) and list(fleet.pools[16].cold_left) == [0]
    # Document 2 heats up again: its newer reading stands.
    _fill(fleet, 2, 12, seq0=25)
    scans = fleet.finish_scan(fleet.begin_scan())
    # (the demotion's own compaction scanned slot 0 before it was vacated)
    assert scans[32][0].tolist() == [0, 2] and scans[32][1].tolist() == [1, 13]
    assert fleet.check_and_demote(scans, max_moves=1) == [1]
    assert fleet.pools[32].cold_left == {}
    # No scan at all: what is left over is still taken up, once.
    assert fleet.check_and_demote({}, max_moves=1) == [0]
    assert fleet.check_and_demote({}, max_moves=1) == []
    assert [fleet.placement[d][0] for d in range(3)] == [8, 16, 32]
    assert fleet.demotions == 3 and fleet.stats()["docs_with_errors"] == 0


@pytest.mark.parametrize("n_docs", [16, 4096, 16384])
@pytest.mark.parametrize("busy", [1, 8, 128])
def test_compact_and_scan_slots_follow_the_boxcar_not_the_pool(
    busy, n_docs, monkeypatch
):
    """Sixteen boxcars of ``busy`` documents (two compaction cadences):
    ``scan_slots`` is Σ B and ``compact_slots`` Σ D, the pool's bucket a
    pass, whatever the pool's size; and nothing the scan path holds on
    the host is as long as the pool."""
    from fluidframework_tpu.protocol.opframe import SeqFrame
    from fluidframework_tpu.service.device_backend import DeviceFleetBackend

    n_docs = max(n_docs, busy)
    be = DeviceFleetBackend(capacity=64, pump_mode=True)
    for i in range(n_docs):
        be.ensure(f"d{i}", "s")
    pool = be.fleet.pools[64]
    assert pool.n_slots == n_docs
    docs = np.linspace(0, n_docs - 1, busy).astype(int).tolist()
    seen = []
    consume = be._consume_scan
    monkeypatch.setattr(
        be, "_consume_scan", lambda scans, newly: (
            seen.append(scans), consume(scans, newly)
        ),
    )
    for r in range(16):
        for i in docs:
            rows = np.zeros((2, OP_WIDTH), np.int32)
            for j in range(2):
                seq = 2 * r + j + 1
                rows[j] = E.insert(0, seq, 1, seq=seq, ref=seq - 1, client=1)
            be.enqueue_frame(
                f"d{i}", SeqFrame("s", 0, 1, rows, ("a", "b"), 0.0)
            )
        be.pump_stage()
        be.pump_dispatch()
        # The step's scan, and after a cadence the compaction pass's too.
        devs = be._scan_token[64][0]
        assert len(devs) == (2 if r and r % 8 == 0 else 1)
        assert all(len(x) == busy * len(devs) for x in be._scan_token[64][1:])
    be.pump_drain()
    bucket = pool.compact_bucket
    assert bucket == min(n_docs, F._COMPACT_CELLS // 64) and busy <= bucket
    stats = be.stats()
    assert stats["compact_slots"] == be.flush_totals["compact_slots"]
    assert stats["compact_slots"] == 2 * bucket
    # The second cadence's pass waits in the pool for the next boxcar.
    assert stats["scan_slots"] == 16 * busy + bucket
    assert [dev.shape for dev, _at, _slots in pool._scans] == [(2, bucket)]
    assert be.flush_totals["step_docs"] == 16 * busy
    assert len(seen) == 16
    for scans in seen:
        assert all(len(x) == busy for x in scans[64])
    assert stats["docs_with_errors"] == 0 and pool._dirty == []
    assert len(be.text(f"d{docs[-1]}", "s")) == 32


@pytest.mark.parametrize(
    "through", ["restore_doc", "promotion", "grow_slots", "demotion"]
)
def test_dirty_state_survives_a_move(through):
    """What a slot has not had compacted moves with the document: after a
    wake, a promotion, a demotion's landing or the pool's growth, the
    next dirty-set compaction leaves what the whole-pool engine would."""
    fleet = DocFleet(2, capacity=32, max_capacity=128, high_water=0.75)
    _fill(fleet, 0, 20)
    _remove_all_but(fleet, 0, 15, 20, 21)  # five rows to reclaim
    cap = 32
    if through == "restore_doc":
        fleet.compact()  # only document 0's wake is dirty afterwards
        _remove_all_but(fleet, 0, 10, 15, 22)
        state = fleet.evict_docs([0])[0]
        fleet.pools[32]._dirty = []
        fleet.restore_doc(0, state)
    elif through == "promotion":
        _fill(fleet, 0, 8, seq0=21)  # 28 rows with the tombstones: hot
        assert fleet.check_and_migrate(
            fleet.finish_scan(fleet.begin_scan())
        ) == [0]
        cap = 64
    elif through == "demotion":
        fleet = DocFleet(2, capacity=16, max_capacity=128)
        _fill(fleet, 0, 14)
        fleet.check_and_migrate(fleet.finish_scan(fleet.begin_scan()))
        _remove_all_but(fleet, 0, 2, 14, 15)
        fleet.compact()
        assert fleet.check_and_demote(
            fleet.finish_scan(fleet.begin_scan())
        ) == [0]
        cap = 16
        _remove_all_but(fleet, 0, 1, 2, 16)
    else:
        fleet.pools[32].grow_slots()
        assert fleet.pools[32].n_slots == 4
    pool = fleet.pools[cap]
    slot = fleet.placement[0][1]
    assert fleet.placement[0][0] == cap
    assert slot in np.concatenate(pool._dirty).tolist()
    want = _whole_pool_compact(pool.state)
    assert want.count[slot] < np.asarray(pool.state.count)[slot]
    fleet.compact()
    _assert_states_equal(pool.state, want, through)


def test_dirty_state_survives_crash_device_replay():
    """The replay after ``crash_device`` writes every slot through the
    busy-set step, so the rebuilt pools' dirty sets name them: the
    cadence compaction that follows leaves the whole-pool engine's
    bytes."""
    from fluidframework_tpu.models.shared_string import SharedString
    from fluidframework_tpu.runtime.container import ContainerRuntime
    from fluidframework_tpu.service.pipeline import PipelineFluidService

    svc = PipelineFluidService(n_partitions=2)
    rt = ContainerRuntime(svc, "doc", channels=(SharedString("s"),))
    s = rt.get_channel("s")
    for i in range(6):
        s.insert_text(0, "abcdef"[i])
        rt.flush()
        svc.pump()
        rt.process_incoming()
    s.remove_range(1, 4)
    rt.flush()
    svc.pump()
    rt.process_incoming()
    want_text = s.get_text()
    assert svc.device_text("doc", "s") == want_text
    svc.crash_device()
    assert svc.device_text("doc", "s") == want_text
    be = svc.device
    (pool,) = be.fleet.pools.values()
    slot = be.fleet.placement[be._index[("doc", "s")]][1]
    dirty = np.concatenate(pool._dirty).tolist() if pool._dirty else []
    assert slot in dirty or be.flush_totals["compact_slots"] > 0
    want = _whole_pool_compact(pool.state)
    be.fleet.compact()
    _assert_states_equal(pool.state, want, "after replay")
    assert svc.device_text("doc", "s") == want_text
