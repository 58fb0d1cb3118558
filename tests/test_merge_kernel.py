"""Directed unit tests for the merge kernel, cross-checked against the
pure-Python oracle (reference semantics per SURVEY.md Appendix A)."""

import numpy as np
import pytest

from fluidframework_tpu.ops import encode as E
from fluidframework_tpu.ops.merge_kernel import apply_ops, compact, jit_apply_ops
from fluidframework_tpu.ops.segment_state import (
    make_state,
    materialize,
    to_host,
)
from fluidframework_tpu.protocol.constants import (
    KIND_FREE,
    NO_CLIENT,
    RSEQ_NONE,
    UNASSIGNED_SEQ,
)
from fluidframework_tpu.testing.oracle import OracleDoc

CAP = 64


def run_kernel(ops, self_client=NO_CLIENT, cap=CAP):
    state = make_state(cap, self_client)
    return apply_ops(state, np.stack(ops).astype(np.int32))


def run_oracle(ops, self_client=NO_CLIENT):
    doc = OracleDoc(self_client)
    for op in ops:
        doc.apply(op)
    return doc


def kernel_struct(state):
    h = to_host(state)
    rows = []
    for i in range(int(h.count)):
        if int(h.kind[i]) == KIND_FREE:
            continue
        rseq = int(h.rseq[i])
        rows.append(
            (
                int(h.orig[i]),
                int(h.off[i]),
                int(h.length[i]),
                int(h.seq[i]),
                int(h.client[i]),
                None if rseq == RSEQ_NONE else rseq,
                int(h.aval[i]),
            )
        )
    return rows


def check_equiv(ops, payloads, self_client=NO_CLIENT):
    st = run_kernel(ops, self_client)
    doc = run_oracle(ops, self_client)
    assert kernel_struct(st) == doc.struct()
    assert materialize(st, payloads) == doc.text(payloads)
    assert int(to_host(st).err) == 0
    return st, doc


def test_insert_empty_and_append():
    pay = {1: "hello", 2: " world"}
    ops = [
        E.insert(0, 1, 5, seq=1, ref=0, client=0),
        E.insert(5, 2, 6, seq=2, ref=1, client=0),
    ]
    st, doc = check_equiv(ops, pay)
    assert materialize(st, pay) == "hello world"


def test_insert_middle_splits():
    pay = {1: "abcd", 2: "XY"}
    ops = [
        E.insert(0, 1, 4, seq=1, ref=0, client=0),
        E.insert(2, 2, 2, seq=2, ref=1, client=1),
    ]
    st, _ = check_equiv(ops, pay)
    assert materialize(st, pay) == "abXYcd"


def test_concurrent_inserts_later_seq_wins_position():
    # Two clients insert at position 0 concurrently (both ref=0): the
    # later-sequenced insert lands closer to the position (leftmost) —
    # reference breakTie ordering.
    pay = {1: "AA", 2: "BB"}
    ops = [
        E.insert(0, 1, 2, seq=1, ref=0, client=0),
        E.insert(0, 2, 2, seq=2, ref=0, client=1),
    ]
    st, _ = check_equiv(ops, pay)
    assert materialize(st, pay) == "BBAA"


def test_concurrent_insert_after_sees_own():
    # Client 0 inserts "AA" (seq 1), then concurrently client 0 inserts at
    # pos 2 (end of its text, ref=1) while client 1 inserts at 0 (ref=0).
    pay = {1: "AA", 2: "BB", 3: "CC"}
    ops = [
        E.insert(0, 1, 2, seq=1, ref=0, client=0),
        E.insert(0, 2, 2, seq=2, ref=0, client=1),  # sees only ""
        E.insert(2, 3, 2, seq=3, ref=1, client=0),  # sees "AA", appends
    ]
    st, _ = check_equiv(ops, pay)
    # Client 0's append at its pos 2 must land after "AA", not after "BBAA".
    assert materialize(st, pay) == "BBAACC"


def test_local_pending_insert_stays_left_of_remote():
    # A client with a pending local insert at pos 0 receives a remote
    # sequenced insert at pos 0: local pending wins (stays left).
    pay = {1: "LL", 2: "RR"}
    ops = [
        E.insert(0, 1, 2, seq=UNASSIGNED_SEQ, ref=0, client=5, lseq=1),
        E.insert(0, 2, 2, seq=1, ref=0, client=1),
    ]
    st, doc = check_equiv(ops, pay, self_client=5)
    assert materialize(st, pay) == "LLRR"
    # After the ack the states converge with a remote replica's view.
    st2 = apply_ops(st, np.stack([E.ack("insert", 1, 2)]).astype(np.int32))
    h = to_host(st2)
    assert int(h.seq[int(np.argmax(np.asarray(h.kind) != KIND_FREE))]) in (1, 2)


def test_remove_basic_and_tombstone():
    pay = {1: "abcdef"}
    ops = [
        E.insert(0, 1, 6, seq=1, ref=0, client=0),
        E.remove(1, 4, seq=2, ref=1, client=1),
    ]
    st, _ = check_equiv(ops, pay)
    assert materialize(st, pay) == "aef"


def test_remove_skips_concurrent_invisible_insert():
    # Client 1 removes [0,4) of "aaaa" at ref=1 while client 0 concurrently
    # inserted "ZZ" at pos 2 (seq 2, also ref=1). The remove (seq 3) must not
    # remove the unseen "ZZ".
    pay = {1: "aaaa", 2: "ZZ"}
    ops = [
        E.insert(0, 1, 4, seq=1, ref=0, client=0),
        E.insert(2, 2, 2, seq=2, ref=1, client=0),
        E.remove(0, 4, seq=3, ref=1, client=1),
    ]
    st, _ = check_equiv(ops, pay)
    assert materialize(st, pay) == "ZZ"


def test_overlapping_remove_keeps_earliest_seq():
    pay = {1: "abcd"}
    ops = [
        E.insert(0, 1, 4, seq=1, ref=0, client=0),
        E.remove(0, 4, seq=2, ref=1, client=1),
        E.remove(0, 4, seq=3, ref=1, client=2),  # concurrent double remove
    ]
    st, doc = check_equiv(ops, pay)
    h = to_host(st)
    live = [i for i in range(int(h.count)) if int(h.kind[i]) != KIND_FREE]
    assert all(int(h.rseq[i]) == 2 for i in live)  # earliest remover kept
    assert all(int(h.rbits[i]) == 0b110 for i in live)  # both recorded


def test_local_remove_beaten_by_remote():
    # Local client 5 removes [0,2) (pending); remote client 1's remove of the
    # same range arrives first: removedSeq adopts the remote seq.
    pay = {1: "ab"}
    ops = [
        E.insert(0, 1, 2, seq=1, ref=0, client=5, lseq=1),
        E.ack("insert", 1, 2),
        E.remove(0, 2, seq=UNASSIGNED_SEQ, ref=2, client=5, lseq=2),
        E.remove(0, 2, seq=3, ref=2, client=1),
    ]
    st = run_kernel(ops, self_client=5)
    h = to_host(st)
    assert int(h.rseq[np.argmax(np.asarray(h.kind) != KIND_FREE)]) == 3
    # Ack of the local remove must not override the earlier remote seq.
    st = apply_ops(st, np.stack([E.ack("remove", 2, 4)]).astype(np.int32))
    h = to_host(st)
    assert int(h.rseq[np.argmax(np.asarray(h.kind) != KIND_FREE)]) == 3


def test_annotate_lww():
    pay = {1: "abcd"}
    ops = [
        E.insert(0, 1, 4, seq=1, ref=0, client=0),
        E.annotate(0, 4, 7, seq=2, ref=1, client=0),
        E.annotate(1, 3, 9, seq=3, ref=1, client=1),
    ]
    st, doc = check_equiv(ops, pay)
    h = to_host(st)
    vals = [
        int(h.aval[i])
        for i in range(int(h.count))
        if int(h.kind[i]) != KIND_FREE
    ]
    assert vals == [7, 9, 7]


def test_compact_reclaims_and_merges():
    pay = {1: "abcdef", 2: "XY"}
    ops = [
        E.insert(0, 1, 6, seq=1, ref=0, client=0),
        E.insert(3, 2, 2, seq=2, ref=1, client=0),  # split abc|def
        E.remove(3, 5, seq=3, ref=2, client=0, msn=3),  # remove XY, msn -> 3
    ]
    st = run_kernel(ops)
    before = materialize(st, pay)
    st2 = compact(st)
    assert materialize(st2, pay) == before == "abcdef"
    h = to_host(st2)
    # Tombstone reclaimed (rseq 3 <= minSeq 3); split halves re-merged.
    assert int(h.count) == 1
    assert int(h.length[0]) == 6


def test_compact_keeps_window_tombstones():
    pay = {1: "abcd"}
    ops = [
        E.insert(0, 1, 4, seq=1, ref=0, client=0),
        E.remove(0, 2, seq=2, ref=1, client=1, msn=1),
    ]
    st = compact(run_kernel(ops))
    h = to_host(st)
    assert int(h.count) == 2  # tombstone above minSeq must survive
    assert materialize(st, pay) == "cd"


def test_jit_and_eager_agree():
    pay = {1: "hello", 2: "XY"}
    ops = np.stack(
        [
            E.insert(0, 1, 5, seq=1, ref=0, client=0),
            E.insert(2, 2, 2, seq=2, ref=1, client=1),
            E.remove(1, 4, seq=3, ref=2, client=0),
        ]
    ).astype(np.int32)
    s1 = apply_ops(make_state(CAP, NO_CLIENT), ops)
    s2 = jit_apply_ops(make_state(CAP, NO_CLIENT), ops)
    assert materialize(s1, pay) == materialize(s2, pay)


@pytest.mark.parametrize("seed", range(8))
def test_random_sequenced_stream_matches_oracle(seed):
    """Random fully-acked op streams (ref = seq-1) vs the oracle."""
    rng = np.random.default_rng(seed)
    payloads = {}
    ops = []
    doc = OracleDoc(NO_CLIENT)
    next_orig = 1
    alphabet = "abcdefghijklmnopqrstuvwxyz"
    for seq in range(1, 41):
        length = len(doc.text(payloads))
        kind = rng.integers(0, 3) if length > 0 else 0
        client = int(rng.integers(0, 6))
        if kind == 0:
            n = int(rng.integers(1, 6))
            payloads[next_orig] = "".join(
                rng.choice(list(alphabet), n)
            )
            op = E.insert(
                int(rng.integers(0, length + 1)),
                next_orig,
                n,
                seq=seq,
                ref=seq - 1,
                client=client,
            )
            next_orig += 1
        elif kind == 1:
            a = int(rng.integers(0, length))
            b = int(rng.integers(a + 1, length + 1))
            op = E.remove(a, b, seq=seq, ref=seq - 1, client=client)
        else:
            a = int(rng.integers(0, length))
            b = int(rng.integers(a + 1, length + 1))
            op = E.annotate(a, b, int(rng.integers(1, 100)), seq=seq, ref=seq - 1, client=client)
        ops.append(op)
        doc.apply(op)

    st = run_kernel(ops, cap=256)
    assert kernel_struct(st) == doc.struct()
    assert materialize(st, payloads) == doc.text(payloads)


def _bit(h, row: int, slot: int) -> int:
    """Bit of writer ``slot`` in row ``row``'s removers set."""
    from fluidframework_tpu.ops.segment_state import (
        RBITS_PER_LANE, rbits_of,
    )

    lane, bit = divmod(slot, RBITS_PER_LANE)
    return (int(rbits_of(h)[lane][row]) >> bit) & 1


@pytest.mark.parametrize("top_slot", [92, 123])
def test_wide_writer_slots_overlap_remove(top_slot):
    """Writer slots land across every removers lane (rbits .. rbits4)
    and behave identically: overlapping removes record every remover,
    and the remover's own perspective hides the row. The cases: the top
    slot of the three lanes the cap stood at until PR 36 (93), and the
    top slot of the fourth (MAX_WRITERS = 124)."""
    from fluidframework_tpu.ops.segment_state import RBITS_LANES
    from fluidframework_tpu.protocol.constants import MAX_WRITERS

    assert MAX_WRITERS == 124 == 31 * len(RBITS_LANES)
    payloads = {1: "abcdef"}
    rows = [
        E.insert(0, 1, 6, seq=1, ref=0, client=40),
        E.remove(1, 3, seq=2, ref=1, client=33),  # mid-lane remover
        E.remove(1, 3, seq=3, ref=1, client=2),  # lo-lane overlap
        E.remove(1, 3, seq=4, ref=1, client=70),  # hi-lane overlap
        E.remove(1, 3, seq=5, ref=1, client=100),  # fourth-lane overlap
        E.remove(3, 5, seq=6, ref=1, client=top_slot),  # top slot
    ]
    ops = np.stack(rows).astype(np.int32)
    st = jit_apply_ops(make_state(32, NO_CLIENT), ops)
    h = to_host(st)
    assert int(h.err) == 0
    assert materialize(st, payloads) == "af"
    live = [i for i in range(int(h.count)) if int(h.kind[i]) != 0]
    # The overlapped rows carry every remover across the lanes.
    overlapped = [i for i in live if int(h.rseq[i]) == 2 and _bit(h, i, 2)]
    assert overlapped and all(
        _bit(h, i, 33) and _bit(h, i, 70) and _bit(h, i, 100)
        and not _bit(h, i, top_slot)
        for i in overlapped
    )
    top = [i for i in live if int(h.rseq[i]) == 6]
    assert top and all(_bit(h, i, top_slot) for i in top)


@pytest.mark.parametrize("slot,flagged", [(93, False), (123, False),
                                          (124, True)])
def test_wide_slot_client_error_flag(slot, flagged):
    """The first slot beyond the mask is flagged; slot 93, which was the
    first one beyond it until PR 36, and the last one are not."""
    rows = [E.insert(0, 1, 2, seq=1, ref=0, client=slot)]
    st = jit_apply_ops(make_state(8, NO_CLIENT), np.stack(rows).astype(np.int32))
    from fluidframework_tpu.protocol.constants import ERR_CLIENT

    assert bool(int(to_host(st).err) & ERR_CLIENT) == flagged
