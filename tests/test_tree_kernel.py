"""Device tree-changeset kernel vs the host mark algebra.

Every law pinned by ``test_tree_marks.py`` re-checks here THROUGH the dense
device kernel (vmapped/jitted), plus direct parity: random host changesets
lowered to the dense IR must produce identical documents through apply/
rebase/invert/compose on both implementations — INCLUDING move-bearing
changesets (r7: mout/min lower into the dense move lanes; the four laws
are re-fuzzed on move-bearing inputs below). On CI this runs on the
virtual CPU backend.
"""

import numpy as np
import pytest

from fluidframework_tpu.ops import tree_kernel as TK
from fluidframework_tpu.tree import marks as M
from test_tree_marks import (
    random_change,
    random_change_with_moves,
    random_state,
)

LC, PC = 48, 48


def dense(c):
    return TK.from_marks(c, LC, PC)


def run_apply(doc, c):
    ids, L = TK.doc_to_dense(doc, LC)
    dc, _ = dense(c)
    out, out_L = TK.batched_apply(
        ids[None], np.asarray([L], np.int32), tree_map_batch(dc)
    )
    return TK.dense_to_doc(out[0], out_L[0])


def tree_map_batch(dc):
    return TK.DenseChange(*[x[None] for x in dc])


@pytest.mark.parametrize("seed", range(25))
def test_apply_parity(seed):
    rng = np.random.default_rng(seed)
    s = random_state(rng)
    c = random_change(rng, s)
    assert run_apply(s, c) == M.apply(s, c)


@pytest.mark.parametrize("seed", range(25))
def test_invert_roundtrip_on_device(seed):
    rng = np.random.default_rng(seed + 500)
    s = random_state(rng)
    c = random_change(rng, s)
    ids, L = TK.doc_to_dense(s, LC)
    dc, _ = dense(c)
    Lb = np.asarray([L], np.int32)
    out, out_L = TK.batched_apply(ids[None], Lb, tree_map_batch(dc))
    inv = TK.batched_invert(ids[None], Lb, tree_map_batch(dc))
    back, back_L = TK.batched_apply(out, out_L, inv)
    assert TK.dense_to_doc(back[0], back_L[0]) == s


@pytest.mark.parametrize("seed", range(25))
def test_rebase_convergence_on_device(seed):
    """Two-client law through the device kernel: apply(a) + rebase(b, a)
    equals apply(b) + rebase(a, b, mirrored tie)."""
    rng = np.random.default_rng(seed + 3000)
    s = random_state(rng)
    a = random_change(rng, s)
    b = random_change(rng, s)
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    da, db = tree_map_batch(dense(a)[0]), tree_map_batch(dense(b)[0])
    sa, La_ = TK.batched_apply(ids[None], Lb, da)
    b_on_a = TK.batched_rebase(db, da, Lb, False)
    via_a, via_a_L = TK.batched_apply(sa, La_, b_on_a)
    sb, Lb_ = TK.batched_apply(ids[None], Lb, db)
    a_on_b = TK.batched_rebase(da, db, Lb, True)
    via_b, via_b_L = TK.batched_apply(sb, Lb_, a_on_b)
    got_a = TK.dense_to_doc(via_a[0], via_a_L[0])
    got_b = TK.dense_to_doc(via_b[0], via_b_L[0])
    assert got_a == got_b
    # And both match the host algebra.
    assert got_a == M.apply(M.apply(s, a), M.rebase(b, a))


@pytest.mark.parametrize("seed", range(25))
def test_compose_parity(seed):
    rng = np.random.default_rng(seed + 1000)
    s = random_state(rng)
    a = random_change(rng, s)
    mid = M.apply(s, a)
    b = random_change(rng, mid)
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    da = tree_map_batch(dense(a)[0])
    db = tree_map_batch(dense(b)[0])
    ab, ovf = TK.batched_compose(da, db, Lb)
    assert int(ovf[0]) == 0
    out, out_L = TK.batched_apply(ids[None], Lb, ab)
    assert TK.dense_to_doc(out[0], out_L[0]) == M.apply(s, M.compose(a, b))


@pytest.mark.parametrize("seed", range(15))
def test_compose_associative_on_device(seed):
    rng = np.random.default_rng(seed + 2000)
    s = random_state(rng)
    a = random_change(rng, s)
    s1 = M.apply(s, a)
    b = random_change(rng, s1)
    s2 = M.apply(s1, b)
    c = random_change(rng, s2)
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    da, db, dc = (tree_map_batch(dense(x)[0]) for x in (a, b, c))
    ab, _ = TK.batched_compose(da, db, Lb)
    left, _ = TK.batched_compose(ab, dc, Lb)
    La1 = TK.out_len(TK.DenseChange(*[x[0] for x in da]), np.int32(L))
    bc, _ = TK.batched_compose(db, dc, np.asarray([La1], np.int32))
    right, _ = TK.batched_compose(da, bc, Lb)
    o1, l1 = TK.batched_apply(ids[None], Lb, left)
    o2, l2 = TK.batched_apply(ids[None], Lb, right)
    assert TK.dense_to_doc(o1[0], l1[0]) == TK.dense_to_doc(o2[0], l2[0])
    assert TK.dense_to_doc(o1[0], l1[0]) == M.apply(
        s, M.compose(M.compose(a, b), c)
    )


def test_rebase_insert_tie_later_lands_left_on_device():
    s = [1, 2]
    a = [M.skip(1), M.insert([10])]
    b = [M.skip(1), M.insert([20])]
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    da, db = tree_map_batch(dense(a)[0]), tree_map_batch(dense(b)[0])
    sa, La_ = TK.batched_apply(ids[None], Lb, da)
    merged, mL = TK.batched_apply(sa, La_, TK.batched_rebase(db, da, Lb, False))
    assert TK.dense_to_doc(merged[0], mL[0]) == [1, 20, 10, 2]


def test_rebase_insert_inside_deleted_range_slides_on_device():
    s = [1, 2, 3, 4]
    o = [M.skip(1), M.delete([2, 3])]
    c = [M.skip(2), M.insert([9])]
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    do, dc = tree_map_batch(dense(o)[0]), tree_map_batch(dense(c)[0])
    so, Lo = TK.batched_apply(ids[None], Lb, do)
    out, oL = TK.batched_apply(so, Lo, TK.batched_rebase(dc, do, Lb, False))
    assert TK.dense_to_doc(out[0], oL[0]) == [1, 9, 4]


def test_revive_restores_identical_ids():
    """Revive semantics (reference Revive/ReturnTo marks): del marks carry
    values, so inverting a delete re-inserts the SAME ids at the same
    spots — the detached-content round-trip, through the device kernel."""
    s = [11, 22, 33, 44]
    c = [M.skip(1), M.delete([22, 33])]
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    dc = tree_map_batch(dense(c)[0])
    out, out_L = TK.batched_apply(ids[None], Lb, dc)
    assert TK.dense_to_doc(out[0], out_L[0]) == [11, 44]
    inv = TK.batched_invert(ids[None], Lb, dc)
    back, back_L = TK.batched_apply(out, out_L, inv)
    # Identity, not just equal values: the revived cells ARE 22 and 33.
    assert TK.dense_to_doc(back[0], back_L[0]) == [11, 22, 33, 44]


def test_unknown_mark_kind_is_rejected_loudly():
    """Foreign (non-IR) mark kinds must be refused by the dense lowering —
    mout/min are device-native since r7, so only kinds outside the wire
    vocabulary reject, and they reject LOUDLY, never a silent miscompile."""
    with pytest.raises(ValueError, match="outside the sequence-field IR"):
        TK.from_marks([("mvout", [1, 2])], LC, PC)
    # The host algebra rejects them too — never silently insert-coerced,
    # never hung (compose's reader used to spin on zero-length heads).
    with pytest.raises(ValueError, match="outside the sequence-field IR"):
        M.apply([1, 2], [("mvout", [1])])
    with pytest.raises(ValueError, match="outside the sequence-field IR"):
        M.invert([("revive", [1])])
    with pytest.raises(ValueError, match="outside the sequence-field IR"):
        M.compose([M.skip(1)], [("mvout", [9])])
    with pytest.raises(ValueError, match="outside the sequence-field IR"):
        M.rebase([("mvout", [5])], [M.skip(1)])


def test_foreign_mark_kind_falls_back_to_host_path():
    """EditManager's device prefix excludes commits with FOREIGN mark
    kinds (outside the wire IR): they take the host path by contract and
    the fallback is attributed. Move-bearing commits, by contrast, are
    device-eligible since r7 — the has_moves gate is retired."""
    from fluidframework_tpu.tree.edit_manager import Commit, EditManager

    em = EditManager(session=1)
    commits = [
        Commit(session=7, seq=k, ref=k - 1,
               change=[M.insert([(1000 + k, k)])])
        for k in range(1, 6)
    ]
    # A foreign mark kind mid-stream (simulating a future wire form).
    commits[2] = Commit(
        session=7, seq=3, ref=2,
        change=[("mvout", [(1001, 1)])],
    )
    prefix, reason = em._device_prefix_ex(commits)
    assert prefix == 0  # stops before it (2 < DEVICE_MIN_BATCH)
    assert reason == "other_mark"
    # A MOVE commit in the same slot keeps the stream device-eligible:
    # moves ride the EM kernel now.
    commits[2] = Commit(
        session=7, seq=3, ref=2,
        change=M.normalize([
            M.move_out(0, [(1001, 1)]), M.skip(1), M.move_in(0, 1),
        ]),
    )
    assert em._device_prefix(commits) == 5
    commits[2] = Commit(
        session=7, seq=3, ref=2, change=[M.insert([(1003, 3)])]
    )
    assert em._device_prefix(commits) == 5


def test_compose_pool_overflow_flagged():
    """Composing changes whose merged live inserts exceed Pc must raise the
    overflow lane instead of silently truncating (ADVICE r2)."""
    small_pc = 4
    a = [M.insert([21, 22, 23])]
    b = [M.insert([11, 12, 13])]
    da, _ = TK.from_marks(a, LC, small_pc)
    db, _ = TK.from_marks(b, LC, small_pc)
    L = np.asarray([0], np.int32)
    comp, ovf = TK.batched_compose(
        TK.DenseChange(*[np.asarray(x)[None] for x in da]),
        TK.DenseChange(*[np.asarray(x)[None] for x in db]),
        L,
    )
    assert int(ovf[0]) == 1
    # A fitting compose of the same shape stays clean.
    da2, _ = TK.from_marks([M.insert([21, 22])], LC, small_pc)
    db2, _ = TK.from_marks([M.insert([11])], LC, small_pc)
    _, ovf2 = TK.batched_compose(
        TK.DenseChange(*[np.asarray(x)[None] for x in da2]),
        TK.DenseChange(*[np.asarray(x)[None] for x in db2]),
        L,
    )
    assert int(ovf2[0]) == 0


def test_batched_independence():
    """Different changesets in one batch don't interfere (vmap sanity) —
    move-bearing and move-free changesets mixed in one dispatch."""
    rng = np.random.default_rng(42)
    docs, changes = [], []
    for j in range(8):
        s = random_state(rng, 6)
        docs.append(s)
        gen = random_change_with_moves if j % 2 else random_change
        changes.append(gen(rng, s))
    ids = np.stack([TK.doc_to_dense(s, LC)[0] for s in docs])
    Ls = np.asarray([len(s) for s in docs], np.int32)
    dcs = [dense(c)[0] for c in changes]
    batch = TK.DenseChange(
        *[np.stack([np.asarray(getattr(d, f)) for d in dcs])
          for f in TK.DenseChange._fields]
    )
    out, out_L = TK.batched_apply(ids, Ls, batch)
    for i in range(8):
        assert TK.dense_to_doc(out[i], out_L[i]) == M.apply(docs[i], changes[i])


# ---------------------------------------------------------------------------
# Moves through the dense lanes (r7): the four algebra laws re-fuzzed on
# move-bearing inputs — the device mirror of test_tree_marks'
# test_move_laws_fuzz, plus directed capture/splice witnesses.


@pytest.mark.parametrize("seed", range(20))
def test_move_laws_fuzz_on_device(seed):
    """apply / invert-roundtrip / compose-vs-sequential / pairwise rebase
    convergence, all through the dense move lanes."""
    rng = np.random.default_rng(seed + 12000)
    s = random_state(rng)
    a = random_change_with_moves(rng, s)
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    da = tree_map_batch(dense(a)[0])
    out, out_L = TK.batched_apply(ids[None], Lb, da)
    want = M.apply(s, a)
    assert TK.dense_to_doc(out[0], out_L[0]) == want
    # invert round trip (the return move)
    inv = TK.batched_invert(ids[None], Lb, da)
    back, back_L = TK.batched_apply(out, out_L, inv)
    assert TK.dense_to_doc(back[0], back_L[0]) == s
    # compose == sequential apply
    b = random_change_with_moves(rng, want)
    db = tree_map_batch(dense(b)[0])
    ab, ovf = TK.batched_compose(da, db, Lb)
    assert int(ovf[0]) == 0
    o2, l2 = TK.batched_apply(ids[None], Lb, ab)
    assert TK.dense_to_doc(o2[0], l2[0]) == M.apply(want, b)
    # pairwise rebase convergence + host parity
    b2 = random_change_with_moves(rng, s)
    db2 = tree_map_batch(dense(b2)[0])
    b_on_a = TK.batched_rebase(db2, da, Lb, False)
    via_a, via_a_L = TK.batched_apply(out, out_L, b_on_a)
    sb, Lb_ = TK.batched_apply(ids[None], Lb, db2)
    a_on_b = TK.batched_rebase(da, db2, Lb, True)
    via_b, via_b_L = TK.batched_apply(sb, Lb_, a_on_b)
    got_a = TK.dense_to_doc(via_a[0], via_a_L[0])
    assert got_a == TK.dense_to_doc(via_b[0], via_b_L[0])
    assert got_a == M.apply(M.apply(s, a), M.rebase(b2, a))


@pytest.mark.parametrize("seed", range(10))
def test_compose_associative_with_moves_on_device(seed):
    rng = np.random.default_rng(seed + 50000)
    s = random_state(rng)
    a = random_change_with_moves(rng, s)
    s1 = M.apply(s, a)
    b = random_change_with_moves(rng, s1)
    s2 = M.apply(s1, b)
    c = random_change_with_moves(rng, s2)
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    da, db, dc = (tree_map_batch(dense(x)[0]) for x in (a, b, c))
    ab, _ = TK.batched_compose(da, db, Lb)
    left, _ = TK.batched_compose(ab, dc, Lb)
    La1 = TK.out_len(TK.DenseChange(*[x[0] for x in da]), np.int32(L))
    bc, _ = TK.batched_compose(db, dc, np.asarray([La1], np.int32))
    right, _ = TK.batched_compose(da, bc, Lb)
    o1, l1 = TK.batched_apply(ids[None], Lb, left)
    o2, l2 = TK.batched_apply(ids[None], Lb, right)
    want = M.apply(s, M.compose(M.compose(a, b), c))
    assert TK.dense_to_doc(o1[0], l1[0]) == want
    assert TK.dense_to_doc(o2[0], l2[0]) == want


def test_rebase_marks_follow_moved_content_on_device():
    """c deletes content that over moved: the delete follows the content
    to its destination (moveEffectTable capture, phase 1 of the kernel)."""
    s = [1, 2, 3, 4, 5]
    over = [M.skip(1), M.move_out(0, [2, 3]), M.skip(2), M.move_in(0, 2)]
    c = [M.skip(1), M.delete([2, 3])]
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    do, dc = tree_map_batch(dense(over)[0]), tree_map_batch(dense(c)[0])
    so, Lo = TK.batched_apply(ids[None], Lb, do)
    out, oL = TK.batched_apply(so, Lo, TK.batched_rebase(dc, do, Lb, False))
    assert TK.dense_to_doc(out[0], oL[0]) == [1, 4, 5]


def test_rebase_both_move_later_wins_on_device():
    """Both sides move the same unit: the later-sequenced move wins in
    either application order (the c_after both-move cancellation)."""
    s = [1, 2, 3]
    a = [M.move_in(0, 1), M.skip(2), M.move_out(0, [3])]  # 3 to front
    b = [M.skip(2), M.move_out(0, [3]), M.move_in(0, 1)]  # 3 stays-ish
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    da, db = tree_map_batch(dense(a)[0]), tree_map_batch(dense(b)[0])
    sa, La_ = TK.batched_apply(ids[None], Lb, da)
    via_a, vaL = TK.batched_apply(
        sa, La_, TK.batched_rebase(db, da, Lb, False)
    )
    sb, Lb_ = TK.batched_apply(ids[None], Lb, db)
    via_b, vbL = TK.batched_apply(
        sb, Lb_, TK.batched_rebase(da, db, Lb, True)
    )
    got = TK.dense_to_doc(via_a[0], vaL[0])
    assert got == TK.dense_to_doc(via_b[0], vbL[0])
    assert got == M.apply(M.apply(s, a), M.rebase(b, a))


def test_attach_stays_at_source_when_region_moves_on_device():
    """An insert positioned inside a region that over moved anchors at
    the source boundary (attaches do not follow moves — the splice's
    boundary map, not the capture table)."""
    s = [1, 2, 3, 4]
    over = [M.skip(1), M.move_out(0, [2, 3]), M.skip(1), M.move_in(0, 2)]
    c = [M.skip(2), M.insert([9])]  # between 2 and 3
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    do, dc = tree_map_batch(dense(over)[0]), tree_map_batch(dense(c)[0])
    so, Lo = TK.batched_apply(ids[None], Lb, do)
    out, oL = TK.batched_apply(so, Lo, TK.batched_rebase(dc, do, Lb, False))
    assert TK.dense_to_doc(out[0], oL[0]) == [1, 9, 4, 2, 3]


def test_move_invert_is_return_move_with_same_ids():
    """Inverting a move re-attaches the SAME ids at the source — the
    dense mirror of the host's return-move inversion."""
    s = [11, 22, 33, 44, 55]
    c = [M.skip(1), M.move_out(0, [22, 33]), M.skip(2), M.move_in(0, 2)]
    ids, L = TK.doc_to_dense(s, LC)
    Lb = np.asarray([L], np.int32)
    dc = tree_map_batch(dense(c)[0])
    out, out_L = TK.batched_apply(ids[None], Lb, dc)
    assert TK.dense_to_doc(out[0], out_L[0]) == [11, 44, 55, 22, 33]
    inv = TK.batched_invert(ids[None], Lb, dc)
    back, back_L = TK.batched_apply(out, out_L, inv)
    assert TK.dense_to_doc(back[0], back_L[0]) == [11, 22, 33, 44, 55]
