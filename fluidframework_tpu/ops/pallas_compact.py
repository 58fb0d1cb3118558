"""Pallas TPU compaction kernel — the zamboni equivalent, scatter-free.

The XLA :func:`merge_kernel.compact` squeezes with a general scatter,
which TPUs execute serially. This kernel squeezes with the apply kernel's
own primitives instead: the row ``j`` that belongs at column ``dest[j]``
walks left by ``j - dest[j]`` in log2(capacity) static shift-and-select
steps (:func:`_squeeze`). No gather, no scatter, no matmul, nothing of
size capacity^2 — so it runs at every capacity tier with the apply
kernel's block rule, and the v5e compiler takes it in about a second at
the base tiers (the unrolled steps make compile time grow with capacity;
see ``fleet._PALLAS_COMPACT_MAX_CAP``).

Semantics are identical to the XLA compact (pinned by parity tests):

1. reclaim tombstones with ``removedSeq <= minSeq`` and no pending local
   stamps (zamboni rule, ``zamboni.ts:19``), squeeze live rows down;
2. re-merge adjacent rows that are splits of one acked, unremoved,
   identically-annotated insert (conservative ``packParent``), via a second
   head-squeeze whose merged lengths come from prefix-sum differences
   (head t's run length = next head's prefix-length - its own).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from fluidframework_tpu.ops.pallas_kernel import (
    N_LANES,
    N_SCALARS,
    OP_WIDTH,
    SC_COUNT,
    SC_MIN_SEQ,
    _apply_values,
    _excl_cumsum,
    _on_tpu,
    _shift_left,
    _shift_right,
    block_params,
    doc_block,
    pack_state,
    unpack_state,
)
from fluidframework_tpu.ops.segment_state import SEGMENT_LANES, SegmentState
from fluidframework_tpu.protocol.constants import (
    KIND_FREE,
    KIND_TEXT,
    RSEQ_NONE,
    UNASSIGNED_SEQ,
)

_I32 = jnp.int32

L_KIND = SEGMENT_LANES.index("kind")
L_ORIG = SEGMENT_LANES.index("orig")
L_OFF = SEGMENT_LANES.index("off")
L_LEN = SEGMENT_LANES.index("length")
L_SEQ = SEGMENT_LANES.index("seq")
L_CLIENT = SEGMENT_LANES.index("client")
L_LSEQ = SEGMENT_LANES.index("lseq")
L_RSEQ = SEGMENT_LANES.index("rseq")
L_RLSEQ = SEGMENT_LANES.index("rlseq")
L_ASEQ = SEGMENT_LANES.index("aseq")
L_ALSEQ = SEGMENT_LANES.index("alseq")
L_AVAL = SEGMENT_LANES.index("aval")

_FILLS = {L_KIND: KIND_FREE, L_RSEQ: RSEQ_NONE}


def _squeeze(keep, dest, lanes):
    """Order-preserving squeeze: row j with ``keep[j]`` lands at column
    ``dest[j]`` (its exclusive keep-count), every lane alike. Each kept
    row moves left by ``j - dest[j]``, one binary digit of that distance
    per step, lowest first: two kept rows i < j differ in distance by at
    most j - i - 1, so no step ever lands two rows on one column, and a
    step is a static shift and a select per lane — the primitives of the
    apply kernel, at any capacity. Columns at and past the keep-count
    hold leftovers; callers mask them."""
    s = keep.shape[1]
    col = jax.lax.broadcasted_iota(_I32, keep.shape, 1)
    dist = jnp.where(keep, col - dest, 0)
    valid = keep
    d = 1
    while d < s:
        move = valid & ((dist & d) != 0)
        arrive = _shift_left(move.astype(_I32), d) != 0
        lanes = [jnp.where(arrive, _shift_left(x, d), x) for x in lanes]
        dist = jnp.where(arrive, _shift_left(dist, d), dist)
        valid = arrive | (valid & ~move)
        d *= 2
    return lanes


def compact_values(lanes, min_seq):
    """The compaction body on VALUES: returns (out_lanes, n_heads) so the
    standalone kernel and the fused apply+compact kernel share it."""
    b, s = lanes[0].shape
    col = jax.lax.broadcasted_iota(_I32, (b, s), 1)

    kind, rseq = lanes[L_KIND], lanes[L_RSEQ]
    live = kind != KIND_FREE
    pending = (lanes[L_LSEQ] != 0) | (lanes[L_RLSEQ] != 0) | (lanes[L_ALSEQ] != 0)
    reclaim = (
        live
        & ~pending
        & (rseq != RSEQ_NONE)
        & (rseq != UNASSIGNED_SEQ)
        & (rseq <= min_seq)
    )
    keep = live & ~reclaim
    dest = _excl_cumsum(keep.astype(_I32))
    n = jnp.sum(keep.astype(_I32), axis=1, keepdims=True)

    sq = _squeeze(keep, dest, lanes)
    valid = col < n
    sq_lanes = [
        jnp.where(valid, sq[i], _FILLS.get(i, 0)) for i in range(N_LANES)
    ]

    # -- sibling re-merge (packParent subset) --------------------------------
    prev = [_shift_right(x, 1) for x in sq_lanes]
    mergeable = (
        valid
        & (col > 0)
        & (sq_lanes[L_KIND] == KIND_TEXT)
        & (prev[L_KIND] == KIND_TEXT)
        & (sq_lanes[L_ORIG] == prev[L_ORIG])
        & (sq_lanes[L_OFF] == prev[L_OFF] + prev[L_LEN])
        & (sq_lanes[L_SEQ] == prev[L_SEQ])
        & (sq_lanes[L_CLIENT] == prev[L_CLIENT])
        & (sq_lanes[L_SEQ] != UNASSIGNED_SEQ)
        & (sq_lanes[L_RSEQ] == RSEQ_NONE)
        & (prev[L_RSEQ] == RSEQ_NONE)
        & (sq_lanes[L_ASEQ] == prev[L_ASEQ])
        & (sq_lanes[L_AVAL] == prev[L_AVAL])
        & (sq_lanes[L_ALSEQ] == 0)
        & (prev[L_ALSEQ] == 0)
        & (sq_lanes[L_LSEQ] == 0)
        & (prev[L_LSEQ] == 0)
    )
    head = valid & ~mergeable
    n_heads = jnp.sum(head.astype(_I32), axis=1, keepdims=True)
    dest_h = _excl_cumsum(head.astype(_I32))

    vlen = jnp.where(valid, sq_lanes[L_LEN], 0)
    total = jnp.sum(vlen, axis=1, keepdims=True)
    plen = _excl_cumsum(vlen)

    hq = _squeeze(head, dest_h, sq_lanes + [plen])
    valid_h = col < n_heads
    out_lanes = [
        jnp.where(valid_h, hq[i], _FILLS.get(i, 0)) for i in range(N_LANES)
    ]
    # Merged length of head t = (next head's prefix length, or total) - own.
    pl_sq = jnp.where(valid_h, hq[N_LANES], 0)
    pl_next = jnp.concatenate([pl_sq[:, 1:], jnp.zeros((b, 1), _I32)], axis=1)
    nxt = jnp.where(col + 1 < n_heads, pl_next, total)
    out_lanes[L_LEN] = jnp.where(valid_h, nxt - pl_sq, 0)
    return out_lanes, n_heads


def _kernel(tables_ref, scalars_ref, otables_ref, oscalars_ref):
    b = tables_ref.shape[1]
    lanes = [tables_ref[i] for i in range(N_LANES)]
    min_seq = scalars_ref[:, SC_MIN_SEQ : SC_MIN_SEQ + 1]
    out_lanes, n_heads = compact_values(lanes, min_seq)
    for i in range(N_LANES):
        otables_ref[i] = out_lanes[i]
    sc_col = jax.lax.broadcasted_iota(_I32, (b, N_SCALARS), 1)
    oscalars_ref[...] = jnp.where(sc_col == SC_COUNT, n_heads, scalars_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_docs", "interpret"), donate_argnums=(0, 1)
)
def compact_packed(tables, scalars, *, block_docs=8, interpret=False):
    n_docs, cap = tables.shape[1], tables.shape[2]
    blk = doc_block(block_docs, n_docs, cap)
    out = pl.pallas_call(
        _kernel,
        grid=(n_docs // blk,),
        in_specs=[
            pl.BlockSpec((N_LANES, blk, cap), lambda i: (0, i, 0)),
            pl.BlockSpec((blk, N_SCALARS), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((N_LANES, blk, cap), lambda i: (0, i, 0)),
            pl.BlockSpec((blk, N_SCALARS), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(tables.shape, _I32),
            jax.ShapeDtypeStruct(scalars.shape, _I32),
        ],
        input_output_aliases={0: 0, 1: 1},
        compiler_params=block_params(blk, cap),
        interpret=interpret,
    )(tables, scalars)
    return out[0], out[1]


def pallas_batched_compact(
    state: SegmentState, *, block_docs: int = 8, interpret=None
) -> SegmentState:
    """Drop-in equivalent of ``merge_kernel.batched_compact``."""
    if interpret is None:
        interpret = not _on_tpu()
    tables, scalars = pack_state(state)
    tables, scalars = compact_packed(
        tables, scalars, block_docs=block_docs, interpret=interpret
    )
    return unpack_state(tables, scalars)


def _fused_kernel(ops_ref, tables_ref, scalars_ref, otables_ref, oscalars_ref):
    """Apply the op batch AND compact in ONE Pallas dispatch (VERDICT r1
    #10: the service step previously cost two device calls; fusing halves
    dispatches and keeps the intermediate table in VMEM)."""
    lanes, count, min_seq, cur_seq, self_client, err = _apply_values(
        ops_ref, tables_ref, scalars_ref
    )
    out_lanes, n_heads = compact_values(lanes, min_seq)
    for i in range(N_LANES):
        otables_ref[i] = out_lanes[i]
    b = count.shape[0]
    zpad = jnp.zeros((b, N_SCALARS - 5), _I32)
    oscalars_ref[:, :] = jnp.concatenate(
        [n_heads, min_seq, cur_seq, self_client, err, zpad], axis=1
    )


@functools.partial(
    jax.jit, static_argnames=("block_docs", "interpret"), donate_argnums=(0, 1)
)
def apply_compact_packed(tables, scalars, ops, *, block_docs=8, interpret=False):
    """Fused service step: ops [D, K, OP_WIDTH] applied and the tables
    compacted, one dispatch. Bit-identical to apply_ops_packed followed by
    compact_packed (parity-tested)."""
    n_docs, cap = tables.shape[1], tables.shape[2]
    k = ops.shape[1]
    blk = doc_block(block_docs, n_docs, cap)
    ops_t = jnp.transpose(ops.astype(_I32), (1, 0, 2))  # [K, D, W]
    out = pl.pallas_call(
        _fused_kernel,
        grid=(n_docs // blk,),
        in_specs=[
            pl.BlockSpec((k, blk, OP_WIDTH), lambda i: (0, i, 0)),
            pl.BlockSpec((N_LANES, blk, cap), lambda i: (0, i, 0)),
            pl.BlockSpec((blk, N_SCALARS), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((N_LANES, blk, cap), lambda i: (0, i, 0)),
            pl.BlockSpec((blk, N_SCALARS), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(tables.shape, _I32),
            jax.ShapeDtypeStruct(scalars.shape, _I32),
        ],
        input_output_aliases={1: 0, 2: 1},
        compiler_params=block_params(blk, cap),
        interpret=interpret,
    )(ops_t, tables, scalars)
    return out[0], out[1]
