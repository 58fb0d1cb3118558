"""Pallas TPU kernel for batched merge-op application.

Why this exists: the XLA formulation in :mod:`merge_kernel` streams every
per-segment lane through HBM once per sequenced op (a ``lax.scan`` step) and
``vmap`` turns its per-op ``lax.switch`` into execute-all-7-branches (its
rate on the chip is not measured on current code). The hot loop is
memory-latency-bound, not compute-bound: the fix is to keep each
document's segment table resident in VMEM for the whole op batch and apply
ops as branch-free vector arithmetic. That is exactly what this kernel does:

- Grid over blocks of documents; each grid step DMAs its block's lanes
  (13 int32 lanes x [block, capacity]) into VMEM once, applies all K ops with
  a ``fori_loop``, and writes the block back once. HBM traffic per op batch
  is O(state), not O(state * K).
- One *unified* op pipeline instead of 7 switch branches: every op type is
  expressed as (optional) boundary splits + (optional) new-row placement +
  masked lane updates, gated by per-document type masks. Insert, remove and
  annotate share the same perspective/prefix-sum/first-hit machinery
  (reference ``mergeTree.ts`` ``insertingWalk:1740``/``breakTie:1719``/
  ``markRangeRemoved:1955``/``annotateRange:1895``; SURVEY.md Appendix A).
- Row shifts (B-tree node inserts in the reference) are static shift-by-one
  selects, prefix sums are Hillis-Steele log-step shifts — no gathers or
  scatters anywhere, which TPUs execute serially.

Semantics are bit-identical to :func:`merge_kernel.batched_apply_ops` for
well-formed op streams (``pos2 > pos1`` on range ops, as produced by
``ops.encode``); the parity fuzz in ``tests/test_pallas_kernel.py`` pins
kernel-vs-kernel and kernel-vs-oracle equivalence, including capacity
overflow and out-of-range behavior.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fluidframework_tpu.ops.segment_state import (
    RBITS_LANES,
    SEGMENT_LANES,
    SegmentState,
    removed_by_slot,
    writer_bits,
)
from fluidframework_tpu.protocol.constants import (
    ERR_CAPACITY,
    ERR_CLIENT,
    ERR_RANGE,
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
    MAX_WRITERS,
    NORM_EXISTING_LOCAL,
    NORM_NEW_LOCAL,
    OP_ACK_ANNOTATE,
    OP_ACK_INSERT,
    OP_ACK_REMOVE,
    OP_ANNOTATE,
    OP_INSERT,
    OP_REMOVE,
    OP_WIDTH,
    RSEQ_NONE,
    UNASSIGNED_SEQ,
)

_I32 = jnp.int32
N_LANES = len(SEGMENT_LANES)
# The body names the plain lanes and walks the removers lanes as a list.
_PLAIN = tuple(k for k in SEGMENT_LANES if k not in RBITS_LANES)
_I_PLAIN = tuple(SEGMENT_LANES.index(k) for k in _PLAIN)
_I_RBITS = tuple(SEGMENT_LANES.index(k) for k in RBITS_LANES)
_I_OFF, _I_LEN = SEGMENT_LANES.index("off"), SEGMENT_LANES.index("length")


def _named(lanes):
    """(the plain lanes in ``_PLAIN`` order, the removers lanes)."""
    return [lanes[i] for i in _I_PLAIN], [lanes[i] for i in _I_RBITS]


def _packed(plain, rb):
    """Inverse of :func:`_named`: the lanes in SEGMENT_LANES order."""
    lanes = [None] * N_LANES
    for i, x in zip(_I_PLAIN + _I_RBITS, list(plain) + list(rb)):
        lanes[i] = x
    return lanes
# Scalar pack layout (lane dim of the [D, N_SCALARS] array).
SC_COUNT, SC_MIN_SEQ, SC_CUR_SEQ, SC_SELF, SC_ERR = range(5)
N_SCALARS = 8  # padded for sublane friendliness


def _shift_right(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """Shift columns right by static d along the last axis, zero-fill."""
    b, s = x.shape
    return jnp.concatenate([jnp.zeros((b, d), x.dtype), x[:, : s - d]], axis=1)


def _shift_left(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """Shift columns left by static d along the last axis, zero-fill."""
    b, s = x.shape
    return jnp.concatenate([x[:, d:], jnp.zeros((b, d), x.dtype)], axis=1)


def _excl_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix sum along lanes (Hillis-Steele log-step shifts)."""
    s = x.shape[1]
    y = x
    d = 1
    while d < s:
        y = y + _shift_right(y, d)
        d *= 2
    return y - x


def _apply_values(ops_ref, tables_ref, scalars_ref):
    """The op-application body on VALUES: returns (lanes, count, min_seq,
    cur_seq, self_client, err) so the standalone kernel and the fused
    apply+compact kernel (pallas_compact.apply_compact_packed) share it."""
    k_total = ops_ref.shape[0]
    b, s = tables_ref.shape[1], tables_ref.shape[2]
    col = jax.lax.broadcasted_iota(_I32, (b, s), 1)

    def first_true(mask):
        """(has, idx) of the first true column per document row."""
        idx = jnp.min(jnp.where(mask, col, s), axis=1, keepdims=True)
        return idx < s, idx

    def value_at(val, idx):
        """val[:, idx] per document row, as [b, 1] (one-hot reduction)."""
        return jnp.sum(jnp.where(col == idx, val, 0), axis=1, keepdims=True)

    def shift1(lanes, do, q, strict):
        """Rows at col > q (or >= q when not strict) take their left
        neighbour's value — the vectorized B-tree row shift."""
        edge = jnp.where(strict, q, q - 1)
        return [jnp.where(do & (col > edge), _shift_right(x, 1), x) for x in lanes]

    def step(k, carry):
        lanes, count, min_seq, cur_seq, self_client, err = carry
        (kind, orig, off, length, seq, client, lseq, rseq, rlseq, aseq,
         alseq, aval), rb = _named(lanes)

        op = jnp.reshape(ops_ref[pl.ds(k, 1), :, :], (b, OP_WIDTH))

        def f(i):
            return op[:, i : i + 1]

        ty = f(F_TYPE)
        pos1, pos2 = f(F_POS1), f(F_POS2)
        seqn, refn, clientn = f(F_SEQ), f(F_REF), f(F_CLIENT)
        lseqn, arg, ilen, msn = f(F_LSEQ), f(F_ARG), f(F_LEN), f(F_MSN)

        is_ins = ty == OP_INSERT
        is_rem = ty == OP_REMOVE
        is_ann = ty == OP_ANNOTATE
        is_range = is_rem | is_ann
        local_op = seqn == UNASSIGNED_SEQ
        is_local = clientn == self_client

        # -- perspective (merge_kernel.perspective, mergeTree.ts:916-1004) --
        def perspective(kind_, seq_, client_, length_, rseq_, rb_):
            live = kind_ != KIND_FREE
            removed = rseq_ != RSEQ_NONE
            r_acked = removed & (rseq_ != UNASSIGNED_SEQ)
            skip = r_acked & (rseq_ <= min_seq)
            rseq_eff = jnp.where(rseq_ == UNASSIGNED_SEQ, RSEQ_NONE, rseq_)
            removed_by_client = removed_by_slot(rb_, clientn)
            hidden = removed & ((rseq_eff <= refn) | removed_by_client)
            seq_eff = jnp.where(seq_ == UNASSIGNED_SEQ, NORM_EXISTING_LOCAL, seq_)
            ins_vis = (client_ == clientn) | (seq_eff <= refn)
            vis_remote = jnp.where(~hidden & ins_vis, length_, 0)
            vis_local = jnp.where(removed, 0, length_)
            vis = jnp.where(is_local, vis_local, vis_remote)
            part = live & ~skip
            return part, jnp.where(part, vis, 0)

        part, vis = perspective(kind, seq, client, length, rseq, rb)
        prefix = _excl_cumsum(vis)
        total = jnp.sum(vis, axis=1, keepdims=True)
        rem1 = pos1 - prefix
        rem2 = pos2 - prefix

        # Strictly-inside hits = boundary splits needed (ensureIntervalBoundary).
        strict1 = part & (vis > 0) & (rem1 > 0) & (rem1 < vis)
        strict2 = part & (vis > 0) & (rem2 > 0) & (rem2 < vis)
        has1, idx1 = first_true(strict1)
        has2, idx2 = first_true(strict2)
        split1 = value_at(rem1, idx1)
        split2 = value_at(rem2, idx2)

        # Insert placement with tie-break (insertingWalk + breakTie).
        op_norm = jnp.where(local_op, NORM_NEW_LOCAL, seqn)
        seg_norm = jnp.where(seq == UNASSIGNED_SEQ, NORM_EXISTING_LOCAL, seq)
        place = part & (
            ((vis > 0) & (rem1 >= 0) & (rem1 < vis))
            | ((vis == 0) & (rem1 == 0) & (op_norm > seg_norm))
        )
        hasp, idxp = first_true(place)
        idxp = jnp.where(hasp, idxp, count)

        # -- capacity / do flags (sequential checks, as the XLA kernel) ----
        sh = jnp.where(has1, 2, 1)
        cap_err_i = is_ins & (count + sh > s)
        do_ins = is_ins & ~cap_err_i
        do_a_rng = is_range & has1 & (count + 1 <= s)
        cap_a = is_range & has1 & (count + 1 > s)
        count_a = count + jnp.where(do_a_rng, 1, 0)
        do_b_rng = is_range & has2 & (count_a + 1 <= s)
        cap_b = is_range & has2 & (count_a + 1 > s)

        err = (
            err
            | jnp.where(cap_err_i | cap_a | cap_b, ERR_CAPACITY, 0)
            | jnp.where(is_ins & ~hasp & (pos1 > total), ERR_RANGE, 0)
            | jnp.where(is_range & (pos2 > total), ERR_RANGE, 0)
            | jnp.where(clientn >= MAX_WRITERS, ERR_CLIENT, 0)
        )

        lanes = _packed(
            (kind, orig, off, length, seq, client, lseq, rseq, rlseq, aseq,
             alseq, aval), rb,
        )
        I_OFF, I_LEN = _I_OFF, _I_LEN

        # -- split A at pos1 (insert mid-segment or range start) -----------
        do_a = do_a_rng | (do_ins & has1)
        lanes = shift1(lanes, do_a, idx1, strict=True)
        m_q = do_a & (col == idx1)
        m_q1 = do_a & (col == idx1 + 1)
        lanes[I_LEN] = jnp.where(m_q, split1, lanes[I_LEN])
        lanes[I_OFF] = jnp.where(m_q1, lanes[I_OFF] + split1, lanes[I_OFF])
        lanes[I_LEN] = jnp.where(m_q1, lanes[I_LEN] - split1, lanes[I_LEN])

        # -- split B at pos2 (range ops; index/length in post-A space) -----
        same_row = do_a_rng & (idx1 == idx2)
        q_b = idx2 + jnp.where(do_a_rng, 1, 0)
        l_b = jnp.where(same_row, split2 - split1, split2)
        lanes = shift1(lanes, do_b_rng, q_b, strict=True)
        m_q = do_b_rng & (col == q_b)
        m_q1 = do_b_rng & (col == q_b + 1)
        lanes[I_LEN] = jnp.where(m_q, l_b, lanes[I_LEN])
        lanes[I_OFF] = jnp.where(m_q1, lanes[I_OFF] + l_b, lanes[I_OFF])
        lanes[I_LEN] = jnp.where(m_q1, lanes[I_LEN] - l_b, lanes[I_LEN])

        # -- insert the new row (between split halves, or at placement) ----
        q_i = jnp.where(has1, idx1 + 1, idxp)
        lanes = shift1(lanes, do_ins, q_i, strict=False)
        m_new = do_ins & (col == q_i)
        new_row = {  # every lane not named here starts at zero
            "kind": jnp.full((b, s), KIND_TEXT, _I32),
            "orig": jnp.broadcast_to(arg, (b, s)),
            "length": jnp.broadcast_to(ilen, (b, s)),
            "seq": jnp.broadcast_to(seqn, (b, s)),
            "client": jnp.broadcast_to(clientn, (b, s)),
            "lseq": jnp.broadcast_to(jnp.where(local_op, lseqn, 0), (b, s)),
            "rseq": jnp.full((b, s), RSEQ_NONE, _I32),
        }
        zero_row = jnp.zeros((b, s), _I32)
        lanes = [
            jnp.where(m_new, new_row.get(k, zero_row), x)
            for k, x in zip(SEGMENT_LANES, lanes)
        ]

        count = jnp.where(
            is_range,
            count_a + jnp.where(do_b_rng, 1, 0),
            jnp.where(do_ins, count + sh, count),
        )

        (kind, orig, off, length, seq, client, lseq, rseq, rlseq, aseq,
         alseq, aval), rb = _named(lanes)

        # -- covered rows (post-split perspective; _covered/nodeMap) -------
        part2, vis2 = perspective(kind, seq, client, length, rseq, rb)
        prefix2 = _excl_cumsum(vis2)
        cov = (
            part2
            & (vis2 > 0)
            & (prefix2 >= pos1)
            & (prefix2 + vis2 <= pos2)
        )

        # -- remove marks (markRangeRemoved:1975-1990) ---------------------
        m_rem = cov & is_rem
        not_removed = rseq == RSEQ_NONE
        was_local = rseq == UNASSIGNED_SEQ
        rseq = jnp.where(
            m_rem & (not_removed | was_local), jnp.broadcast_to(seqn, (b, s)), rseq
        )
        rlseq = jnp.where(
            m_rem & not_removed & local_op, jnp.broadcast_to(lseqn, (b, s)), rlseq
        )
        rb = [
            jnp.where(m_rem, x | bit, x)
            for x, bit in zip(rb, writer_bits(clientn))
        ]

        # -- annotate marks (annotateRange; single-lane LWW) ---------------
        pending = alseq != 0
        m_ann = cov & is_ann & (local_op | ~pending)
        aval = jnp.where(m_ann, jnp.broadcast_to(arg, (b, s)), aval)
        aseq = jnp.where(m_ann, jnp.broadcast_to(seqn, (b, s)), aseq)
        alseq = jnp.where(
            m_ann, jnp.broadcast_to(jnp.where(local_op, lseqn, 0), (b, s)), alseq
        )

        # -- acks of own ops (ackPendingSegment, mergeTree.ts:1283) --------
        live = kind != KIND_FREE
        m_aci = (ty == OP_ACK_INSERT) & live & (seq == UNASSIGNED_SEQ) & (
            lseq == lseqn
        )
        seq = jnp.where(m_aci, jnp.broadcast_to(seqn, (b, s)), seq)
        lseq = jnp.where(m_aci, 0, lseq)

        m_acr = (ty == OP_ACK_REMOVE) & live & (rlseq == lseqn)
        rseq = jnp.where(
            m_acr & (rseq == UNASSIGNED_SEQ), jnp.broadcast_to(seqn, (b, s)), rseq
        )
        rlseq = jnp.where(m_acr, 0, rlseq)

        m_aca = (ty == OP_ACK_ANNOTATE) & live & (alseq == lseqn)
        aseq = jnp.where(m_aca, jnp.broadcast_to(seqn, (b, s)), aseq)
        alseq = jnp.where(m_aca, 0, alseq)

        # -- bookkeeping (collab window floor / current seq) ---------------
        cur_seq = jnp.maximum(cur_seq, seqn)
        min_seq = jnp.maximum(min_seq, msn)

        lanes = _packed(
            (kind, orig, off, length, seq, client, lseq, rseq, rlseq, aseq,
             alseq, aval), rb,
        )
        return lanes, count, min_seq, cur_seq, self_client, err

    lanes0 = [tables_ref[i] for i in range(N_LANES)]
    count0 = scalars_ref[:, SC_COUNT : SC_COUNT + 1]
    min_seq0 = scalars_ref[:, SC_MIN_SEQ : SC_MIN_SEQ + 1]
    cur_seq0 = scalars_ref[:, SC_CUR_SEQ : SC_CUR_SEQ + 1]
    self0 = scalars_ref[:, SC_SELF : SC_SELF + 1]
    err0 = scalars_ref[:, SC_ERR : SC_ERR + 1]

    return jax.lax.fori_loop(
        0, k_total, step, (lanes0, count0, min_seq0, cur_seq0, self0, err0)
    )


def _kernel(ops_ref, tables_ref, scalars_ref, otables_ref, oscalars_ref):
    lanes, count, min_seq, cur_seq, self_client, err = _apply_values(
        ops_ref, tables_ref, scalars_ref
    )
    for i in range(N_LANES):
        otables_ref[i] = lanes[i]
    zpad = jnp.zeros((count.shape[0], N_SCALARS - 5), _I32)
    oscalars_ref[:, :] = jnp.concatenate(
        [count, min_seq, cur_seq, self_client, err, zpad], axis=1
    )


def pack_state(state: SegmentState):
    """SegmentState -> (tables [N_LANES, D, S], scalars [D, N_SCALARS])."""
    tables = jnp.stack([getattr(state, k) for k in SEGMENT_LANES], axis=0)
    scalars = jnp.stack(
        [state.count, state.min_seq, state.cur_seq, state.self_client, state.err]
        + [jnp.zeros_like(state.count)] * (N_SCALARS - 5),
        axis=-1,
    ).astype(_I32)
    return tables, scalars


def unpack_state(tables, scalars) -> SegmentState:
    return SegmentState(
        **{k: tables[i] for i, k in enumerate(SEGMENT_LANES)},
        count=scalars[..., SC_COUNT],
        min_seq=scalars[..., SC_MIN_SEQ],
        cur_seq=scalars[..., SC_CUR_SEQ],
        self_client=scalars[..., SC_SELF],
        err=scalars[..., SC_ERR],
    )


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# What the v5e compiler charges a kernel of this family: its scoped VMEM
# stack (in/out blocks double-buffered, the loop-carried lanes, the body's
# temporaries) came to 346-411 bytes per (doc, row) cell of the block at
# 15 lanes and 459 at 16: it goes with the lanes, so the grant does.
# A block of 2^15 cells sits inside Mosaic's default 16 MB; bigger tiers
# cannot go under 8 docs (the sublane tile), so they ask for more VMEM,
# up to what the chip's 128 MiB leaves the compiler. Eight docs of 65,536
# rows need 156 MB and do not fit: the fleet's top tier is 32,768.
_BLOCK_CELLS = 1 << 15
_VMEM_BYTES_PER_CELL = 30 * N_LANES
_VMEM_CEILING = 120 << 20


def doc_block(block_docs: int, n_docs: int, cap: int) -> int:
    """Docs per grid step for a ``[N_LANES, n_docs, cap]`` table: the
    largest multiple of 8 (Mosaic's sublane tile) that divides ``n_docs``,
    is at most ``block_docs`` and keeps ``blk * cap`` within the default
    VMEM budget — but never under 8. A doc dim no such block divides is
    one whole-dim block (the other shape Mosaic accepts)."""
    top = max(8, min(block_docs, _BLOCK_CELLS // cap))
    return max(
        (b for b in range(8, min(top, n_docs) + 1, 8) if n_docs % b == 0),
        default=n_docs,
    )


def block_params(blk: int, cap: int) -> pltpu.CompilerParams:
    """The scoped-VMEM grant that goes with :func:`doc_block`'s choice."""
    cells = max(blk, 8) * cap
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(
            _VMEM_CEILING, max(32 << 20, cells * _VMEM_BYTES_PER_CELL)
        )
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_docs", "interpret"),
    donate_argnums=(0, 1),
)
def apply_ops_packed(tables, scalars, ops, *, block_docs=64, interpret=False):
    """Apply ops [D, K, OP_WIDTH] to a packed state. ``block_docs`` is an
    upper bound: the block that runs is :func:`doc_block`'s."""
    n_docs = tables.shape[1]
    cap = tables.shape[2]
    k = ops.shape[1]
    blk = doc_block(block_docs, n_docs, cap)
    ops_t = jnp.transpose(ops.astype(_I32), (1, 0, 2))  # [K, D, W]
    grid = (n_docs // blk,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
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


def pallas_batched_apply_ops(
    state: SegmentState, ops, *, block_docs: int = 64, interpret=None
) -> SegmentState:
    """Drop-in equivalent of ``merge_kernel.batched_apply_ops`` running the
    VMEM-resident Pallas kernel. ``interpret=None`` auto-selects interpreter
    mode off-TPU (CPU tests)."""
    if interpret is None:
        interpret = not _on_tpu()
    tables, scalars = pack_state(state)
    tables, scalars = apply_ops_packed(
        tables, scalars, ops, block_docs=block_docs, interpret=interpret
    )
    return unpack_state(tables, scalars)
