"""The merge-sequence kernel: pure op application over segment tables.

TPU-native re-execution of the reference merge-tree hot path
(``packages/dds/merge-tree/src/mergeTree.ts`` — ``insertingWalk:1740``,
``breakTie:1719``, ``markRangeRemoved:1955``, ``annotateRange:1895``,
``nodeLength:916``, ``ackPendingSegment:1283``; see SURVEY.md Appendix A):

- Position resolution is a masked prefix sum over the segment table (replacing
  the B-tree descent + ``PartialSequenceLengths`` per-(refSeq, client) views —
  the visibility predicate is evaluated directly per row, vectorized).
- Insert/remove/annotate are masked gathers/scatters over int32 lanes.
- One document applies its sequenced ops in order via ``lax.scan``; documents
  batch with ``vmap``; chips shard the document axis with ``jax.sharding``.
- ``compact`` is the zamboni equivalent (``zamboni.ts:19``): reclaims
  tombstones below the collab window and re-merges split siblings.

Semantics notes (bit-exact intent vs the reference, verified by the oracle
cross-check + convergence fuzz tests):

- Visibility from perspective ``(refSeq, client)`` [``nodeLength``]: rows with
  an acked ``removedSeq`` that is either ``<= refSeq`` or attached to an
  invisible insert are *skipped entirely* (no tie-break participation);
  invisible concurrent inserts contribute length 0 but do participate;
  ``removedClientIds`` membership is an int32 bitmask over client slots.
- Tie-break [``breakTie``]: at a zero-remaining position over a zero-length
  row, the insert goes before it iff ``norm(newSeq) > norm(rowSeq)`` with
  local sentinels normalized above every real seq.
- Range ops walk only rows with positive visible length [``nodeMap`` skips
  len 0/undefined], after boundary splits [``ensureIntervalBoundary``].
- Remove overlap [``markRangeRemoved:1975-1990``]: the earliest acked remover
  keeps ``removedSeq``; a pending local remove beaten by a remote one adopts
  the remote seq; all removers accumulate in the bitmask.
- Annotate is single-lane LWW with local-pending-wins (the sequencer assigns
  pending local ops a later seq than any already-delivered remote op, so
  "local pending wins until ack" equals last-writer-wins at final seqs).
  Multi-key PropertySet merge stays host-side (interned ``aval`` values).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from fluidframework_tpu.ops.segment_state import (
    RBITS_LANES,
    SEGMENT_LANES,
    SegmentState,
    rbits_of,
    removed_by_slot,
    writer_bits,
)
from fluidframework_tpu.protocol.constants import (
    ERR_CAPACITY,
    ERR_CLIENT,
    ERR_RANGE,
    MAX_WRITERS,
    F_ARG,
    F_CLIENT,
    F_LEN,
    F_LSEQ,
    F_MSN,
    F_POS1,
    F_POS2,
    F_REF,
    F_SEQ,
    F_TYPE,
    KIND_FREE,
    KIND_TEXT,
    NORM_EXISTING_LOCAL,
    NORM_NEW_LOCAL,
    OP_ACK_ANNOTATE,
    OP_ACK_INSERT,
    OP_ACK_REMOVE,
    OP_ANNOTATE,
    OP_INSERT,
    OP_NOOP,
    OP_REMOVE,
    RSEQ_NONE,
    UNASSIGNED_SEQ,
)

_I32 = jnp.int32


def _iota(state: SegmentState) -> jnp.ndarray:
    return lax.iota(_I32, state.kind.shape[-1])


def perspective(state: SegmentState, ref_seq, client, is_local):
    """Visible length of every row from ``(refSeq, client)``.

    Returns ``(participate, vis)``: rows with ``participate=False`` are
    skipped entirely (the reference's ``undefined`` length); others contribute
    ``vis`` (possibly 0) and take part in tie-breaking.

    Implements the reference's *new* length calculations
    (``mergeTree.ts:935-964``, the ``mergeTreeUseNewLengthCalculations``
    path): a removed segment is skipped only once ``removedSeq <= minSeq``
    (zamboni-eligible, may not exist on other replicas); any other tombstone
    contributes length 0 and still participates in insert tie-breaking by its
    insert seq. The legacy path (skip on any acked remove ≤ refSeq) is
    *divergent* for a concurrent insert next to a segment that was inserted
    and removed entirely after the op's refSeq — the convergence fuzz in
    ``tests/test_fuzz_convergence.py`` reproduces that divergence if the
    legacy rule is used.
    """
    live = state.kind != KIND_FREE
    removed = state.rseq != RSEQ_NONE
    r_acked = removed & (state.rseq != UNASSIGNED_SEQ)

    # Zamboni-eligible tombstones are skipped from every perspective.
    skip = r_acked & (state.rseq <= state.min_seq)

    # Remote perspective: normalize local sentinels above any real seq —
    # a pending local remove never hides a row from a remote op's view,
    # and a pending local insert is invisible unless client-matched.
    rseq_eff = jnp.where(state.rseq == UNASSIGNED_SEQ, RSEQ_NONE, state.rseq)
    removed_by_client = removed_by_slot(rbits_of(state), client)
    hidden = removed & ((rseq_eff <= ref_seq) | removed_by_client)
    seq_eff = jnp.where(
        state.seq == UNASSIGNED_SEQ, NORM_EXISTING_LOCAL, state.seq
    )
    ins_vis = (state.client == client) | (seq_eff <= ref_seq)
    vis_remote = jnp.where(~hidden & ins_vis, state.length, 0)

    # Local perspective (reference localNetLength): sees all segments; any
    # removal (acked or pending) hides.
    vis_local = jnp.where(removed, 0, state.length)

    vis = jnp.where(is_local, vis_local, vis_remote)
    participate = live & ~skip
    vis = jnp.where(participate, vis, 0)
    return participate, vis


def _excl_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.cumsum(x) - x


def _first_true(mask: jnp.ndarray):
    has = jnp.any(mask)
    idx = jnp.argmax(mask).astype(_I32)
    return has, idx


def _gather_lanes(state: SegmentState, take: jnp.ndarray) -> SegmentState:
    """Reorder all segment lanes by index vector ``take`` (clamped)."""
    take = jnp.clip(take, 0, state.kind.shape[-1] - 1)
    return state._replace(**{k: getattr(state, k)[take] for k in SEGMENT_LANES})


def _lane_where(state: SegmentState, mask: jnp.ndarray, **updates) -> SegmentState:
    return state._replace(
        **{k: jnp.where(mask, v, getattr(state, k)) for k, v in updates.items()}
    )


def _bookkeep(state: SegmentState, op: jnp.ndarray) -> SegmentState:
    """Advance cur_seq / collab-window floor from a sequenced op's stamps.

    Also flags client slots outside the removers-bitmask range (the sequencer
    must keep slots < MAX_WRITERS; aliasing bits would diverge replicas).
    """
    return state._replace(
        cur_seq=jnp.maximum(state.cur_seq, op[F_SEQ]),
        min_seq=jnp.maximum(state.min_seq, op[F_MSN]),
        err=state.err | jnp.where(op[F_CLIENT] >= MAX_WRITERS, ERR_CLIENT, 0),
    )


# ---------------------------------------------------------------------------
# Insert (reference insertingWalk + breakTie, mergeTree.ts:1740/1719)
# ---------------------------------------------------------------------------


def insert_place_mask(state: SegmentState, op, part, vis, rem):
    """Rows the insert may land before (insertingWalk + breakTie,
    mergeTree.ts:1740/1719). Shared with the sharded-document owner
    resolution (parallel/sharded_doc.py) — the tie-break rule must never
    de-synchronize between ownership and the owner's actual insert."""
    op_norm = jnp.where(op[F_SEQ] == UNASSIGNED_SEQ, NORM_NEW_LOCAL, op[F_SEQ])
    seg_norm = jnp.where(
        state.seq == UNASSIGNED_SEQ, NORM_EXISTING_LOCAL, state.seq
    )
    return part & (
        ((vis > 0) & (rem >= 0) & (rem < vis))
        | ((vis == 0) & (rem == 0) & (op_norm > seg_norm))
    )


def _apply_insert(state: SegmentState, op: jnp.ndarray) -> SegmentState:
    cap = state.kind.shape[-1]
    is_local = op[F_CLIENT] == state.self_client
    part, vis = perspective(state, op[F_REF], op[F_CLIENT], is_local)
    prefix = _excl_cumsum(vis)
    rem = op[F_POS1] - prefix
    place = insert_place_mask(state, op, part, vis, rem)
    has, idx = _first_true(place)
    total = jnp.sum(vis)
    idx = jnp.where(has, idx, state.count)
    split = jnp.where(has, rem[jnp.clip(idx, 0, cap - 1)], 0)
    range_err = ~has & (op[F_POS1] > total)

    # Shift by 1 (insert-before/append) or 2 (mid-segment split).
    sh = jnp.where(split > 0, 2, 1).astype(_I32)
    cap_err = state.count + sh > cap
    err = state.err | jnp.where(cap_err, ERR_CAPACITY, 0) | jnp.where(range_err, ERR_RANGE, 0)

    j = _iota(state)
    take = jnp.where(j >= idx + sh, j - sh, j)
    out = _gather_lanes(state, take)

    at_left = (j == idx) & (split > 0)  # truncated original before the insert
    at_new = j == idx + (sh - 1)
    at_right = (j == idx + 2) & (split > 0)
    out = _lane_where(out, at_left, length=jnp.broadcast_to(split, (cap,)))
    # The inserted row.
    z = jnp.zeros((cap,), _I32)
    out = _lane_where(
        out,
        at_new,
        kind=z + KIND_TEXT,
        orig=z + op[F_ARG],
        off=z,
        length=z + op[F_LEN],
        seq=z + op[F_SEQ],
        client=z + op[F_CLIENT],
        lseq=z + jnp.where(op[F_SEQ] == UNASSIGNED_SEQ, op[F_LSEQ], 0),
        rseq=z + RSEQ_NONE,
        rlseq=z,
        **{k: z for k in RBITS_LANES},
        aseq=z,
        alseq=z,
        aval=z,
    )
    # Right half of a split keeps the original stamps at shifted offset.
    out = _lane_where(
        out,
        at_right,
        off=out.off + split,
        length=out.length - split,
    )
    out = out._replace(count=state.count + sh, err=err)
    # Capacity overflow: drop the op entirely (sticky error flag).
    out = jax.tree_util.tree_map(
        lambda new, old: jnp.where(cap_err, old, new), out, state
    )
    return _bookkeep(out._replace(err=err), op)


# ---------------------------------------------------------------------------
# Boundary split (reference ensureIntervalBoundary, mergeTree.ts:1706)
# ---------------------------------------------------------------------------


def _split_at(state: SegmentState, pos, ref_seq, client, is_local) -> SegmentState:
    cap = state.kind.shape[-1]
    part, vis = perspective(state, ref_seq, client, is_local)
    prefix = _excl_cumsum(vis)
    rem = pos - prefix
    hit = part & (vis > 0) & (rem > 0) & (rem < vis)
    has, idx = _first_true(hit)
    split = jnp.where(has, rem[jnp.clip(idx, 0, cap - 1)], 0)

    cap_err = state.count + 1 > cap
    do = has & ~cap_err
    err = state.err | jnp.where(has & cap_err, ERR_CAPACITY, 0)

    j = _iota(state)
    take = jnp.where(j >= idx + 1, j - 1, j)
    out = _gather_lanes(state, take)
    out = _lane_where(out, j == idx, length=jnp.zeros((cap,), _I32) + split)
    out = _lane_where(
        out, j == idx + 1, off=out.off + split, length=out.length - split
    )
    out = out._replace(count=state.count + 1)
    out = jax.tree_util.tree_map(lambda new, old: jnp.where(do, new, old), out, state)
    return out._replace(err=err)


def _covered(state: SegmentState, start, end, ref_seq, client, is_local):
    """Rows fully inside [start, end) with positive visible length — the rows
    a range op marks after boundary splits (reference nodeMap skip rules).

    Returns ``(covered_mask, total_visible_length)`` so callers can flag
    out-of-range requests.
    """
    part, vis = perspective(state, ref_seq, client, is_local)
    prefix = _excl_cumsum(vis)
    cov = part & (vis > 0) & (prefix >= start) & (prefix + vis <= end)
    return cov, jnp.sum(vis)


# ---------------------------------------------------------------------------
# Remove (reference markRangeRemoved, mergeTree.ts:1955)
# ---------------------------------------------------------------------------


def _apply_remove(state: SegmentState, op: jnp.ndarray) -> SegmentState:
    is_local = op[F_CLIENT] == state.self_client
    state = _split_at(state, op[F_POS1], op[F_REF], op[F_CLIENT], is_local)
    state = _split_at(state, op[F_POS2], op[F_REF], op[F_CLIENT], is_local)
    cov, total = _covered(
        state, op[F_POS1], op[F_POS2], op[F_REF], op[F_CLIENT], is_local
    )
    state = state._replace(
        err=state.err | jnp.where(op[F_POS2] > total, ERR_RANGE, 0)
    )

    local_op = op[F_SEQ] == UNASSIGNED_SEQ
    bits = writer_bits(op[F_CLIENT])
    not_removed = state.rseq == RSEQ_NONE
    was_local = state.rseq == UNASSIGNED_SEQ

    new_rseq = jnp.where(not_removed | was_local, op[F_SEQ], state.rseq)
    new_rlseq = jnp.where(not_removed & local_op, op[F_LSEQ], state.rlseq)
    state = _lane_where(
        state,
        cov,
        rseq=new_rseq,
        rlseq=new_rlseq,
        **{
            k: lane | bit
            for k, lane, bit in zip(RBITS_LANES, rbits_of(state), bits)
        },
    )
    return _bookkeep(state, op)


# ---------------------------------------------------------------------------
# Annotate (reference annotateRange, mergeTree.ts:1895; single-lane LWW)
# ---------------------------------------------------------------------------


def _apply_annotate(state: SegmentState, op: jnp.ndarray) -> SegmentState:
    is_local = op[F_CLIENT] == state.self_client
    state = _split_at(state, op[F_POS1], op[F_REF], op[F_CLIENT], is_local)
    state = _split_at(state, op[F_POS2], op[F_REF], op[F_CLIENT], is_local)
    cov, total = _covered(
        state, op[F_POS1], op[F_POS2], op[F_REF], op[F_CLIENT], is_local
    )
    state = state._replace(
        err=state.err | jnp.where(op[F_POS2] > total, ERR_RANGE, 0)
    )

    local_op = op[F_SEQ] == UNASSIGNED_SEQ
    pending = state.alseq != 0
    apply = cov & (local_op | ~pending)
    state = _lane_where(
        state,
        apply,
        aval=jnp.broadcast_to(op[F_ARG], state.aval.shape),
        aseq=jnp.broadcast_to(op[F_SEQ], state.aseq.shape),
        alseq=jnp.where(local_op, op[F_LSEQ], 0) + jnp.zeros_like(state.alseq),
    )
    return _bookkeep(state, op)


# ---------------------------------------------------------------------------
# Acks of the local client's own sequenced ops (reference ackPendingSegment,
# mergeTree.ts:1283: stamp the pending group with the server-assigned seq)
# ---------------------------------------------------------------------------


def _apply_ack_insert(state: SegmentState, op: jnp.ndarray) -> SegmentState:
    live = state.kind != KIND_FREE
    m = live & (state.seq == UNASSIGNED_SEQ) & (state.lseq == op[F_LSEQ])
    state = _lane_where(
        state,
        m,
        seq=jnp.broadcast_to(op[F_SEQ], state.seq.shape),
        lseq=jnp.zeros_like(state.lseq),
    )
    return _bookkeep(state, op)


def _apply_ack_remove(state: SegmentState, op: jnp.ndarray) -> SegmentState:
    live = state.kind != KIND_FREE
    m = live & (state.rlseq == op[F_LSEQ])
    # Overlapping remote remove already stamped an earlier seq: keep it
    # (reference segment.ack returns false for overlapping removes).
    new_rseq = jnp.where(state.rseq == UNASSIGNED_SEQ, op[F_SEQ], state.rseq)
    state = _lane_where(
        state, m, rseq=new_rseq, rlseq=jnp.zeros_like(state.rlseq)
    )
    return _bookkeep(state, op)


def _apply_ack_annotate(state: SegmentState, op: jnp.ndarray) -> SegmentState:
    live = state.kind != KIND_FREE
    m = live & (state.alseq == op[F_LSEQ])
    state = _lane_where(
        state,
        m,
        aseq=jnp.broadcast_to(op[F_SEQ], state.aseq.shape),
        alseq=jnp.zeros_like(state.alseq),
    )
    return _bookkeep(state, op)


def _apply_noop(state: SegmentState, op: jnp.ndarray) -> SegmentState:
    return _bookkeep(state, op)


_BRANCHES = (
    _apply_noop,  # OP_NOOP
    _apply_insert,  # OP_INSERT
    _apply_remove,  # OP_REMOVE
    _apply_annotate,  # OP_ANNOTATE
    _apply_ack_insert,  # OP_ACK_INSERT
    _apply_ack_remove,  # OP_ACK_REMOVE
    _apply_ack_annotate,  # OP_ACK_ANNOTATE
)


def apply_op(state: SegmentState, op: jnp.ndarray) -> SegmentState:
    """Apply one op row (int32[OP_WIDTH]) to one document."""
    ty = jnp.clip(op[F_TYPE], 0, len(_BRANCHES) - 1)
    return lax.switch(ty, _BRANCHES, state, op)


def apply_ops(state: SegmentState, ops: jnp.ndarray) -> SegmentState:
    """Apply ops[K, OP_WIDTH] in order (the sequenced stream) to one doc."""

    def body(s, op):
        return apply_op(s, op), None

    out, _ = lax.scan(body, state, ops)
    return out


# vmap over a [D, ...] stacked state and [D, K, OP_WIDTH] op batches.
batched_apply_ops = jax.vmap(apply_ops)

jit_apply_ops = jax.jit(apply_ops, donate_argnums=(0,))
jit_batched_apply_ops = jax.jit(batched_apply_ops, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Compaction — the zamboni equivalent (reference zamboni.ts:19, packParent:63)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,))
def compact(state: SegmentState) -> SegmentState:
    """Reclaim tombstones below the collab window, squeeze out holes, and
    re-merge adjacent split siblings. Safe to run at any time; deterministic
    given the state, so replicas stay convergent.

    Unlike the reference's incremental ≤2-scours-per-op policy, compaction is
    a whole-table vectorized pass the host schedules when the table fills.
    """
    cap = state.kind.shape[-1]
    live = state.kind != KIND_FREE
    pending = (state.lseq != 0) | (state.rlseq != 0) | (state.alseq != 0)
    reclaim = (
        live
        & ~pending
        & (state.rseq != RSEQ_NONE)
        & (state.rseq != UNASSIGNED_SEQ)
        & (state.rseq <= state.min_seq)
    )
    keep = live & ~reclaim

    pos = jnp.cumsum(keep) - 1
    scatter_to = jnp.where(keep, pos, cap)  # cap drops

    def squeeze(lane, fill):
        out = jnp.full((cap,), fill, _I32)
        return out.at[scatter_to].set(lane, mode="drop")

    fills = {"kind": KIND_FREE, "rseq": RSEQ_NONE}
    sq = state._replace(
        **{
            k: squeeze(getattr(state, k), fills.get(k, 0))
            for k in SEGMENT_LANES
        }
    )
    n = jnp.sum(keep).astype(_I32)

    # Merge runs of adjacent rows that are splits of one acked, unremoved,
    # identically-annotated insert (conservative subset of packParent).
    valid = _iota(sq) < n
    prev = jax.tree_util.tree_map(
        lambda x: jnp.roll(x, 1) if x.ndim else x, sq
    )
    mergeable = (
        valid
        & (_iota(sq) > 0)
        & (sq.kind == KIND_TEXT)
        & (prev.kind == KIND_TEXT)
        & (sq.orig == prev.orig)
        & (sq.off == prev.off + prev.length)
        & (sq.seq == prev.seq)
        & (sq.client == prev.client)
        & (sq.seq != UNASSIGNED_SEQ)
        & (sq.rseq == RSEQ_NONE)
        & (prev.rseq == RSEQ_NONE)
        & (sq.aseq == prev.aseq)
        & (sq.aval == prev.aval)
        & (sq.alseq == 0)
        & (prev.alseq == 0)
        & (sq.lseq == 0)
        & (prev.lseq == 0)
    )
    head = valid & ~mergeable
    run_id = jnp.where(valid, jnp.cumsum(head) - 1, cap - 1)
    run_len = jax.ops.segment_sum(
        jnp.where(valid, sq.length, 0), run_id, num_segments=cap
    ).astype(_I32)

    hpos = jnp.cumsum(head) - 1
    h_to = jnp.where(head, hpos, cap)

    def squeeze_heads(lane, fill):
        out = jnp.full((cap,), fill, _I32)
        return out.at[h_to].set(lane, mode="drop")

    out = sq._replace(
        **{k: squeeze_heads(getattr(sq, k), fills.get(k, 0)) for k in SEGMENT_LANES}
    )
    n_heads = jnp.sum(head).astype(_I32)
    merged_len = jnp.full((cap,), 0, _I32).at[h_to].set(
        run_len[run_id], mode="drop"
    )
    out = out._replace(
        length=jnp.where(_iota(out) < n_heads, merged_len, 0),
        count=n_heads,
    )
    return out


batched_compact = jax.jit(jax.vmap(compact), donate_argnums=(0,))
