"""Fleet capacity lifecycle: pooled blocks + host-driven promotion
(VERDICT r1 #5; reference growth analog mergeTree.ts:1268 updateRoot)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.ops import encode as E
from fluidframework_tpu.ops.segment_state import SegmentState, materialize
from fluidframework_tpu.parallel import fleet as F
from fluidframework_tpu.parallel.fleet import DocFleet
from fluidframework_tpu.protocol.constants import OP_WIDTH
from fluidframework_tpu.testing.oracle import OracleDoc
from fluidframework_tpu.protocol.constants import NO_CLIENT


def grow_stream(n_docs, rounds, k, insert_bias=0.9, seed=0):
    """Per-round op batches that keep documents growing (no trailing
    whole-doc remove), tracked against oracles."""
    rng = np.random.default_rng(seed)
    oracles = [OracleDoc(NO_CLIENT) for _ in range(n_docs)]
    payloads = {}
    seqs = [0] * n_docs
    lens = [0] * n_docs
    next_orig = 1
    batches = []
    for _r in range(rounds):
        ops = np.zeros((n_docs, k, OP_WIDTH), np.int32)
        for d in range(n_docs):
            for i in range(k):
                seqs[d] += 1
                if lens[d] > 4 and rng.random() > insert_bias:
                    a = int(rng.integers(0, lens[d] - 2))
                    op = E.remove(a, a + 2, seq=seqs[d], ref=seqs[d] - 1,
                                  client=int(rng.integers(0, 4)))
                    lens[d] -= 2
                else:
                    n = int(rng.integers(1, 4))
                    payloads[next_orig] = "x" * n
                    op = E.insert(int(rng.integers(0, lens[d] + 1)),
                                  next_orig, n, seq=seqs[d],
                                  ref=seqs[d] - 1,
                                  client=int(rng.integers(0, 4)))
                    next_orig += 1
                    lens[d] += n
                ops[d, i] = op
                oracles[d].apply(op)
        batches.append(ops)
    return batches, oracles, payloads


def test_doc_grows_past_initial_capacity_zero_drops():
    # VERDICT "Done": a load drives docs past their initial capacity with
    # zero dropped ops.
    fleet = DocFleet(n_docs=4, capacity=32, high_water=0.7)
    batches, oracles, payloads = grow_stream(4, rounds=12, k=8)
    for ops in batches:
        stats = fleet.apply(ops)
        assert stats["docs_with_errors"] == 0, stats
        fleet.check_and_migrate()
    assert fleet.migrations >= 4  # every doc outgrew the 32-row tier
    assert max(fleet.pools) > 32
    for d in range(4):
        assert materialize(fleet.doc_state(d), payloads) == oracles[d].text(
            payloads
        )


def test_promotion_preserves_pending_free_slots_and_stats():
    fleet = DocFleet(n_docs=2, capacity=16, high_water=0.6)
    batches, oracles, payloads = grow_stream(2, rounds=6, k=6, seed=3)
    for ops in batches:
        fleet.apply(ops)
        fleet.check_and_migrate()
    stats = fleet.stats()
    assert stats["docs_with_errors"] == 0
    # Vacated slots are reusable: the base pool has free slots now.
    base = fleet.pools[16]
    assert base.free_slot() is not None
    for d in range(2):
        assert materialize(fleet.doc_state(d), payloads) == oracles[d].text(
            payloads
        )


def test_without_migration_capacity_trips():
    # The round-1 failure mode still exists if the lifecycle never runs —
    # pinning that the migration is what prevents it.
    fleet = DocFleet(n_docs=1, capacity=16, high_water=0.7)
    batches, _o, _p = grow_stream(1, rounds=10, k=8, seed=1)
    errs = 0
    for ops in batches:
        stats = fleet.apply(ops)  # no check_and_migrate
        errs = stats["docs_with_errors"]
    assert errs == 1  # ERR_CAPACITY tripped without the lifecycle


def test_compaction_runs_per_pool():
    fleet = DocFleet(n_docs=2, capacity=32, high_water=0.7)
    batches, oracles, payloads = grow_stream(
        2, rounds=8, k=6, insert_bias=0.6, seed=5
    )
    for ops in batches:
        # Advance the window so compaction has tombstones to reclaim.
        ops[:, -1, 9] = ops[:, -1, 3]  # F_MSN := F_SEQ on the last op
        for d in range(2):
            oracles[d].min_seq = int(ops[d, -1, 3])
        fleet.apply(ops)
        fleet.compact()
        fleet.check_and_migrate()
    assert fleet.stats()["docs_with_errors"] == 0
    for d in range(2):
        assert materialize(fleet.doc_state(d), payloads) == oracles[d].text(
            payloads
        )


def test_apply_sparse_matches_dense_and_reads_one_doc():
    """The gathered serving-path staging (`apply_sparse`: upload only the
    busy channels' rows + slot indices, scatter on device) produces
    byte-identical state to the dense `apply`, including across tier
    promotions, and `doc_state` reads one document without pulling the
    pool (VERDICT r3 Weak #3)."""
    dense = DocFleet(n_docs=5, capacity=16, high_water=0.7)
    sparse = DocFleet(n_docs=5, capacity=16, high_water=0.7)
    batches, oracles, payloads = grow_stream(5, rounds=6, k=6, seed=7)
    rng = np.random.default_rng(3)
    for ops in batches:
        # A random subset of docs is busy each round; the rest get no rows
        # at all on the sparse path (the dense path ships their zeros).
        busy = sorted(rng.choice(5, size=int(rng.integers(1, 6)),
                                 replace=False))
        dense_ops = np.zeros_like(ops)
        dense_ops[busy] = ops[busy]
        dense.apply(dense_ops)
        sparse.apply_sparse(list(map(int, busy)), ops[busy])
        for f in (dense, sparse):
            f.compact()
            f.check_and_migrate()
    from fluidframework_tpu.ops.segment_state import SEGMENT_LANES

    assert dense.stats() == sparse.stats()
    for d in range(5):
        s1, s2 = dense.doc_state(d), sparse.doc_state(d)
        for lane in SEGMENT_LANES:
            assert np.array_equal(getattr(s1, lane), getattr(s2, lane)), (
                d, lane,
            )
        for s in ("count", "min_seq", "cur_seq", "self_client", "err"):
            assert int(getattr(s1, s)) == int(getattr(s2, s)), (d, s)


def test_apply_sparse_pads_and_drops_out_of_range():
    """B pads to a pow2 bucket; padding rows carry an out-of-range slot
    index and must scatter to nowhere (not corrupt slot 0)."""
    fleet = DocFleet(n_docs=3, capacity=16, high_water=0.9)
    ops = np.zeros((1, 8, OP_WIDTH), np.int32)
    ops[0, 0] = E.insert(0, 1, 3, seq=1, ref=0, client=0)
    payloads = {1: "abc"}
    fleet.apply_sparse([1], ops)  # B=1, no pad needed
    ops2 = np.zeros((3, 8, OP_WIDTH), np.int32)
    ops2[0, 0] = E.insert(0, 2, 2, seq=2, ref=1, client=0)
    ops2[1, 0] = E.insert(0, 3, 1, seq=1, ref=0, client=0)
    ops2[2, 0] = E.insert(0, 4, 1, seq=1, ref=0, client=0)
    payloads.update({2: "de", 3: "f", 4: "g"})
    fleet.apply_sparse([1, 0, 2], ops2)  # B=3 pads to 4
    assert materialize(fleet.doc_state(1), payloads) == "deabc"
    assert materialize(fleet.doc_state(0), payloads) == "f"
    assert materialize(fleet.doc_state(2), payloads) == "g"
    assert fleet.stats()["docs_with_errors"] == 0


def test_stale_scan_dropped_for_reassigned_slots():
    """A health scan begun before a slot's occupant changed must not
    attribute the departed doc's count/err to the new occupant
    (ADVICE r4: placement generation per slot)."""
    fleet = DocFleet(1, capacity=8, max_capacity=64)
    # Fill doc 0 hot (above high water in the base tier).
    ops = np.zeros((1, 8, OP_WIDTH), np.int32)
    for i in range(7):
        ops[0, i] = E.insert(0, i + 1, 1, seq=i + 1, ref=i, client=0)
    fleet.apply_sparse([0], ops)
    token = fleet.begin_scan()  # the step's scan: slot 0 hot, gen G
    assert [d.shape for d in token[8][0]] == [(2, 1)]
    # Occupant changes: doc 0 promotes out, doc 1 lands in its slot.
    fleet.check_and_migrate()
    assert fleet.placement[0][0] == 16
    d1 = fleet.add_doc()
    assert fleet.placement[d1] == (8, 0)  # reused the vacated slot
    scans = fleet.finish_scan(token)
    # The stale column (old occupant's count 7) is left out.
    assert [x.tolist() for x in scans[8]] == [[], [], []]
    # Consuming the stale scan must not re-promote the NEW occupant.
    promoted = fleet.check_and_migrate(scans)
    assert d1 not in promoted


# -- the busy-set device step (ROADMAP S2) ------------------------------------
#
# ``fleet._fused_sparse_step`` gathers the boxcar's documents, applies on
# [B, capacity] and scatters back in place. Its contract is the dense
# engine's on the scattered batch, over the WHOLE pool.

_STEP_CAP = 64


def _edit_rows(rng, n, k, first_seq):
    """[n, k, OP_WIDTH] real op rows for documents that hold
    ``first_seq - 1`` one-character inserts: inserts at random positions,
    a two-character remove now and then."""
    rows = np.zeros((n, k, OP_WIDTH), np.int32)
    for d in range(n):
        length = first_seq - 1
        for i in range(k):
            seq = first_seq + i
            if length > 3 and rng.random() < 0.3:
                a = int(rng.integers(0, length - 2))
                rows[d, i] = E.remove(a, a + 2, seq=seq, ref=seq - 1,
                                      client=int(rng.integers(0, 4)))
                length -= 2
            else:
                rows[d, i] = E.insert(int(rng.integers(0, length + 1)),
                                      seq, 1, seq=seq, ref=seq - 1,
                                      client=int(rng.integers(0, 4)))
                length += 1
    return rows


def _host(state):
    return SegmentState(*[np.array(x) for x in state])


@functools.lru_cache(maxsize=None)
def _seeded_pool(kernel, n_slots):
    """A pool's state after every slot took four real ops, as host
    numpy (the engines donate: each use device_puts it anew)."""
    pool = F._Pool(_STEP_CAP, n_slots, kernel)
    rows = _edit_rows(np.random.default_rng(n_slots), n_slots, 4, 1)
    return _host(pool._step(pool.state, jnp.asarray(rows)))


def _assert_states_equal(got, want, what=""):
    for name, x, y in zip(SegmentState._fields, got, want):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (what, name)


def _step_cases():
    """Every kernel, pool size, boxcar bucket and K of the issue, and the
    pools under one sublane tile (1, 2, 4 slots: the step pads them)."""
    for kernel in ("xla", "pallas"):
        for n_slots in (8, 64, 4096):
            for b in (1, 3, 8, 64):
                for k in (8, 16):
                    yield kernel, n_slots, b, k
        for n_slots in (1, 2, 4):
            for b in (1, 8):
                yield kernel, n_slots, b, 8


@pytest.mark.parametrize("kernel,n_slots,b,k", list(_step_cases()))
def test_busy_set_step_equals_dense_engine(kernel, n_slots, b, k):
    """Lane for lane and scalar for scalar over the whole pool, so an
    untouched slot is proved untouched. Every row of the padded boxcar
    carries REAL ops; the rows that are not this pool's (padding, another
    tier) carry slot ``n_slots`` and must change nothing, also when slot
    ``n_slots - 1`` (what a clipped gather reads for them) is itself busy
    in the same boxcar."""
    rng = np.random.default_rng(1000 * n_slots + 10 * b + k)
    bucket = F._pow2_at_least(b)
    rows_b = _edit_rows(rng, bucket, k, 5)
    n_busy = max(1, min(b, n_slots) - (1 if b > 1 else 0))
    busy = rng.choice(n_slots - 1, size=n_busy - 1, replace=False)
    busy = np.append(busy, n_slots - 1)
    assert len(set(busy.tolist())) == n_busy  # unique within a boxcar
    slots = np.full(bucket, n_slots, np.int32)
    at = rng.choice(bucket, size=n_busy, replace=False)
    slots[at] = busy
    if b > 1:
        assert (slots == n_slots).any() and rows_b[slots == n_slots].any()

    seeded = _seeded_pool(kernel, n_slots)
    pool = F._Pool(_STEP_CAP, n_slots, kernel)
    dense = np.zeros((n_slots, k, OP_WIDTH), np.int32)
    dense[busy] = rows_b[at]
    want = _host(pool._step(jax.device_put(seeded), jnp.asarray(dense)))
    got, scan = F._fused_sparse_step(kernel, None)(
        jax.device_put(seeded), jnp.asarray(rows_b), jnp.asarray(slots)
    )
    _assert_states_equal(got, want)
    # The step's own [2, B] health scan: the busy rows' (count, err) as
    # the pool now holds them, 0 in the columns the scatter dropped.
    scan = np.asarray(scan)
    assert scan.shape == (2, bucket)
    assert np.array_equal(scan[0, at], want.count[busy])
    assert np.array_equal(scan[1, at], want.err[busy])
    assert not scan[:, slots == n_slots].any()
    untouched = np.setdiff1d(np.arange(n_slots), busy)
    _assert_states_equal(
        [np.asarray(x)[untouched] for x in got],
        [x[untouched] for x in seeded], "untouched",
    )
    assert (np.asarray(got.cur_seq)[busy] == 4 + k).all()


def _two_tier_fleets(n):
    """``n`` equal fleets of six documents, documents 0-2 grown into the
    64-row tier."""
    batches, _oracles, _payloads = grow_stream(6, rounds=4, k=8, seed=11)
    fleets = [DocFleet(n_docs=6, capacity=32, high_water=0.7)
              for _ in range(n)]
    for f in fleets:
        for ops in batches:
            ops = ops.copy()
            ops[3:] = 0  # documents 3-5 stay empty, in the base tier
            f.apply(ops)
            f.check_and_migrate()
        assert sorted(f.pools) == [32, 64], f.stats()
    return fleets


def _next_rows(seq, k=8):
    rows = np.zeros((k, OP_WIDTH), np.int32)
    for i in range(2):
        rows[i] = E.insert(0, 900 + seq + i, 1, seq=seq + i,
                           ref=seq + i - 1, client=1)
    return rows


def test_two_tier_boxcar_lands_in_each_documents_own_pool():
    """One boxcar with documents of two tiers through ``dispatch_staged``
    and through ``apply_sparse`` (the fault fallback): both equal the
    dense ``apply`` over every slot of both pools, so no pool took
    another tier's rows."""
    dense, staged, fallback = _two_tier_fleets(3)
    docs = [4, 1, 5, 0]  # tiers interleaved: 32, 64, 32, 64
    ops_b = np.stack([
        _next_rows(33 if d < 3 else 1) for d in docs
    ])
    full = np.zeros((6, 8, OP_WIDTH), np.int32)
    full[docs] = ops_b
    dense.apply(full)
    staged.dispatch_staged(docs, jax.device_put(ops_b))
    fallback.apply_sparse(docs, ops_b)
    assert staged.last_step_docs == fallback.last_step_docs == 2 * 4
    for cap in (32, 64):
        for f in (staged, fallback):
            _assert_states_equal(
                f.pools[cap].state, dense.pools[cap].state, cap
            )
    assert dense.stats()["docs_with_errors"] == 0


@pytest.mark.parametrize("spread", ["several_shards", "one_shard"])
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_mesh_sharded_step_equals_unsharded(kernel, spread):
    """On the 8 host devices: each device takes the replicated boxcar,
    keeps the slots of its own slice and drops the rest."""
    from fluidframework_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    n_docs = 64  # 8 slots a device
    docs = [3, 9, 12, 40, 47, 63] if spread == "several_shards" else [
        16, 18, 23]
    rng = np.random.default_rng(5)
    one = DocFleet(n_docs, _STEP_CAP, kernel=kernel)
    mesh = DocFleet(n_docs, _STEP_CAP, kernel=kernel, mesh=make_mesh())
    for first_seq, via in ((1, "apply_sparse"), (9, "dispatch_staged")):
        ops_b = _edit_rows(rng, len(docs), 8, first_seq)
        for f in (one, mesh):
            if via == "apply_sparse":
                f.apply_sparse(docs, ops_b)
            else:
                rows = np.zeros((8, 8, OP_WIDTH), np.int32)
                rows[: len(docs)] = ops_b
                f.dispatch_staged(docs, jax.device_put(rows))
        _assert_states_equal(
            mesh.pools[_STEP_CAP].state, one.pools[_STEP_CAP].state, via
        )
    state = mesh.pools[_STEP_CAP].state
    assert len(state.kind.sharding.device_set) == 8
    assert (np.asarray(state.cur_seq)[docs] == 16).all()
    assert int(np.asarray(state.cur_seq).sum()) == 16 * len(docs)


def test_step_docs_counts_the_bucket_not_the_pool():
    """A boxcar of 3 documents on a 4,096-slot pool runs the kernel over
    4 documents: ``flush_totals["step_docs"]`` is Σ B, not Σ n_slots."""
    from fluidframework_tpu.protocol.opframe import SeqFrame
    from fluidframework_tpu.service.device_backend import DeviceFleetBackend

    be = DeviceFleetBackend(capacity=64, pump_mode=True)
    for i in range(4096):
        be.ensure(f"d{i}", "s")
    assert be.fleet.pools[64].n_slots == 4096
    for busy in ([5, 700, 4095], [9]):
        for i in busy:
            rows = np.zeros((2, OP_WIDTH), np.int32)
            for j in range(2):
                rows[j] = E.insert(0, j + 1, 1, seq=j + 1, ref=j, client=1)
            be.enqueue_frame(
                f"d{i}", SeqFrame("s", 0, 1, rows, ("a", "b"), 0.0)
            )
        be.pump_stage()
        be.pump_dispatch()
    be.pump_drain()
    assert be.pump_dispatches == 2
    assert be.flush_totals["real_rows"] == 8
    assert be.flush_totals["step_docs"] == 4 + 1
    assert be.text("d700", "s") == "ba" and be.text("d9", "s") == "ba"
    assert be.stats()["docs_with_errors"] == 0
