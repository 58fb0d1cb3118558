"""Device kernel for SharedTree sequence-field changesets.

Reference: ``packages/dds/tree/src/feature-libraries/sequence-field/
{rebase,compose,invert}.ts`` co-iterate two run-length mark lists via a
MarkQueue that splits marks to equal lengths (SURVEY.md Appendix B.3). The
host mirror is ``tree/marks.py``. Here the same algebra is lowered to a
**dense fixed-shape IR** where the co-iteration becomes prefix sums and
scatters — the TPU-native form (no data-dependent control flow; every op is
O(capacity) vector work, `vmap`-able across documents and `jit`-compiled).

Dense IR for a changeset over an input document of length ``L`` (padded to
static capacity ``Lc``, attach pool capacity ``Pc``):

- ``del_mask[Lc]``  — 1 where input slot i is deleted;
- ``ins_cnt[Lc+1]`` — how many ATTACH atoms (inserts and move-ins) land at
  boundary b (before input slot b; boundary L = append);
- ``ins_ids[Pc]``   — inserted item ids for plain-insert atoms (0 for
  move-in atoms), concatenated in boundary order;
- ``mov_id[Lc]``    — move id (>0) where input slot i is MOVED OUT
  (0 = not moved) — the reference's MoveOut, ``format.ts:14-220``;
- ``mov_off[Lc]``   — slot i's offset within its move's unit stream;
- ``pool_mid[Pc]``  — move id of attach-pool atom k when it is a MOVE-IN
  (0 = plain insert atom);
- ``pool_off[Pc]``  — the move-in atom's offset in its move's stream.

Move streams are POSITIONLESS identity, exactly as in the host IR: within
one changeset every ``(mid, off)`` pair is detached exactly once (mov
lanes) and attached exactly once (pool lanes), and ``apply`` reunites
them by tag — a **two-phase** device form: phase 1 resolves each move
tag to its source slot / destination position with a comparison-matrix
"effect table" (the dense moveEffectTable, held in VMEM as a one-hot
matmul operand), phase 2 splices via the standard prefix-sum scatter.

Values ride as int32 ids; deletions AND move-outs are positional (values
are implicit from the document), unlike the host IR whose ``del``/``mout``
marks carry values — ``invert`` therefore takes the document ids. The
runs-within-a-boundary order of the attach pool IS the output order, which
lets ``rebase`` keep the pool compact-in-order (the boundary mapping is
monotone; atoms only ever DROP, when their move died under a concurrent
delete or lost a both-move conflict).

Tie policy matches ``marks.py``: rebasing the LATER-sequenced change puts
its attaches before the earlier change's at the same boundary
(``c_after=False``); ``c_after=True`` mirrors. Capture/splice matches the
reference's move-effect resolution (``sequence-field/moveEffectTable.ts``):
marks FOLLOW content that a concurrent change moved, deletion beats
movement in either order, and the later-sequenced move wins both-move
conflicts. Attaches anchor to their SOURCE position (they slide to the
collapse boundary, they do not follow the move).

Mark coverage is the FULL sequence-field vocabulary {skip, del, ins,
mout, min}: the r4 contract that excluded moves from the device is
retired — ``from_marks`` lowers ``mout``/``min`` into the lanes above and
every algebra law is fuzz-pinned against the host on move-bearing inputs
(``test_tree_kernel.py``). ``revive`` stays value-carrying delete
inversion (``invert`` re-inserts the SAME ids, pinned by
``test_revive_restores_identical_ids``); unknown mark kinds are still
rejected loudly.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class DenseChange(NamedTuple):
    """One changeset in dense IR (arrays may carry a leading batch dim)."""

    del_mask: jnp.ndarray  # int32[Lc]
    ins_cnt: jnp.ndarray  # int32[Lc+1]
    ins_ids: jnp.ndarray  # int32[Pc]
    mov_id: jnp.ndarray  # int32[Lc] move id of a moved-out slot (0 = none)
    mov_off: jnp.ndarray  # int32[Lc] offset in the move's unit stream
    pool_mid: jnp.ndarray  # int32[Pc] move id of a move-in atom (0 = ins)
    pool_off: jnp.ndarray  # int32[Pc] stream offset of the move-in atom


def empty_change(Lc: int, Pc: int) -> DenseChange:
    return DenseChange(
        jnp.zeros(Lc, jnp.int32),
        jnp.zeros(Lc + 1, jnp.int32),
        jnp.zeros(Pc, jnp.int32),
        jnp.zeros(Lc, jnp.int32),
        jnp.zeros(Lc, jnp.int32),
        jnp.zeros(Pc, jnp.int32),
        jnp.zeros(Pc, jnp.int32),
    )


def _detach_mask(c: DenseChange) -> jnp.ndarray:
    """1 where the slot leaves its position (delete OR move-out)."""
    return jnp.maximum(c.del_mask, (c.mov_id > 0).astype(jnp.int32))


def out_len(c: DenseChange, L: jnp.ndarray) -> jnp.ndarray:
    """Length of c's output document."""
    Lc = c.del_mask.shape[-1]
    valid = jnp.arange(Lc) < L
    bvalid = jnp.arange(Lc + 1) <= L
    return (
        L
        - jnp.sum(_detach_mask(c) * valid)
        + jnp.sum(c.ins_cnt * bvalid)
    )


# -- scatter/search primitives as MXU matmuls --------------------------------
#
# jnp scatters (`.at[].add/set`) serialize on TPU (~ms per call at these
# shapes — measured, not guessed); a one-hot matmul does the same dense
# permutation as MXU work in microseconds. Out-of-range positions simply match no output
# column — scatter-drop semantics for free (mask by driving pos to -1).

_HIGHEST = jax.lax.Precision.HIGHEST


def _onehot_f32(pos: jnp.ndarray, out_size: int) -> jnp.ndarray:
    return (pos[:, None] == jnp.arange(out_size)[None, :]).astype(jnp.float32)


def _scatter_add(pos: jnp.ndarray, vals: jnp.ndarray, out_size: int):
    """out[p] = sum of vals where pos == p. Exact for |vals| sums < 2^24."""
    oh = _onehot_f32(pos, out_size)
    out = jax.lax.dot_general(
        vals.astype(jnp.float32), oh, (((0,), (0,)), ((), ())),
        precision=_HIGHEST,
    )
    return out.astype(jnp.int32)


def _scatter_ids(pos: jnp.ndarray, ids: jnp.ndarray, out_size: int):
    """out[p] = ids[i] where pos[i] == p (single writer per slot). 15-bit
    hi/lo split keeps int32 ids exact through the f32 MXU path."""
    oh = _onehot_f32(pos, out_size)
    hi = jax.lax.dot_general(
        (ids >> 15).astype(jnp.float32), oh, (((0,), (0,)), ((), ())),
        precision=_HIGHEST,
    )
    lo = jax.lax.dot_general(
        (ids & 0x7FFF).astype(jnp.float32), oh, (((0,), (0,)), ((), ())),
        precision=_HIGHEST,
    )
    return hi.astype(jnp.int32) * 32768 + lo.astype(jnp.int32)


def _count_leq(sorted_vals: jnp.ndarray, queries: jnp.ndarray):
    """searchsorted(sorted_vals, queries, side='right') as a comparison
    matrix reduction (binary-search gathers serialize on TPU)."""
    return jnp.sum(
        (sorted_vals[None, :] <= queries[:, None]).astype(jnp.int32), axis=1
    )


def _tag_match(mid_a, off_a, mid_b, off_b) -> jnp.ndarray:
    """match[i, j] = 1.0 where move tags (mid_a[i], off_a[i]) ==
    (mid_b[j], off_b[j]) and the tag is real (mid > 0). At most one match
    per row/column for well-formed changesets — the dense move-effect
    table, phase 1 of every move-aware op."""
    return (
        (mid_a[:, None] == mid_b[None, :])
        & (off_a[:, None] == off_b[None, :])
        & (mid_a[:, None] > 0)
    ).astype(jnp.float32)


def _matmul_take_ids(match: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """out[i] = ids[j] where match[i, j] == 1 (single match per row; 0 for
    matchless rows). 15-bit split keeps int32 ids exact through f32."""
    hi = jax.lax.dot_general(
        match, (ids >> 15).astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=_HIGHEST,
    )
    lo = jax.lax.dot_general(
        match, (ids & 0x7FFF).astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=_HIGHEST,
    )
    return hi.astype(jnp.int32) * 32768 + lo.astype(jnp.int32)


def _matmul_take_small(match: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """out[i] = vals[j] where match[i, j] == 1 — for values < 2^24 (exact
    in one f32 pass: positions, counts, flags)."""
    out = jax.lax.dot_general(
        match, vals.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=_HIGHEST,
    )
    return out.astype(jnp.int32)


def _prefix(c: DenseChange, L: jnp.ndarray):
    """Shared prefix sums. Returns (valid, keep, surv_pos, Dex_b, bcum,
    icnt) where ``surv_pos[i]`` is slot i's position in c's output,
    ``Dex_b[b]`` counts detached slots (deletes + move-outs) before
    boundary b, and ``bcum[b]`` counts attach atoms at boundaries <= b."""
    Lc = c.del_mask.shape[-1]
    idx = jnp.arange(Lc)
    valid = idx < L
    dmask = _detach_mask(c) * valid
    keep = valid & (dmask == 0)
    Dex_b = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(dmask).astype(jnp.int32)]
    )  # [Lc+1]: detaches in [0, b)
    icnt = c.ins_cnt * (jnp.arange(Lc + 1) <= L)
    bcum = jnp.cumsum(icnt).astype(jnp.int32)  # [Lc+1]: attaches at [0..b]
    surv_pos = idx - Dex_b[:Lc] + bcum[:Lc]
    return valid, keep, surv_pos, Dex_b, bcum, icnt


def _pool_boundaries(icnt: jnp.ndarray, Pc: int):
    """Boundary b(k) of each attach-pool atom k, plus validity mask and the
    position of k's run start in the pool (exclusive cumulative)."""
    bcum = jnp.cumsum(icnt).astype(jnp.int32)
    k = jnp.arange(Pc)
    total = bcum[-1]
    kvalid = k < total
    b_of_k = _count_leq(bcum, k)
    bcum_at = jnp.take(bcum, jnp.clip(b_of_k, 0, icnt.shape[-1] - 1))
    icnt_at = jnp.take(icnt, jnp.clip(b_of_k, 0, icnt.shape[-1] - 1))
    run_start = bcum_at - icnt_at  # pool index where b's run began
    return b_of_k, kvalid, run_start, total


def _pool_positions(c: DenseChange, L, Dex_b, icnt):
    """Output position of every attach-pool atom: survivors before its
    boundary plus every pool atom preceding it (the pool is globally
    output-ordered)."""
    Pc = c.ins_ids.shape[-1]
    b_of_k, kvalid, _run_start, total = _pool_boundaries(icnt, Pc)
    pos = (b_of_k - jnp.take(Dex_b, b_of_k)) + jnp.arange(Pc)
    return b_of_k, kvalid, pos, total


def apply_change(
    doc_ids: jnp.ndarray, L: jnp.ndarray, c: DenseChange
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply a changeset; returns (new_ids[Lc], new_L). The output must fit
    the same capacity (caller invariant)."""
    Lc = doc_ids.shape[-1]
    valid, keep, surv_pos, Dex_b, bcum, icnt = _prefix(c, L)
    out = _scatter_ids(jnp.where(keep, surv_pos, -1), doc_ids, Lc)
    b_of_k, kvalid, ins_pos, total = _pool_positions(c, L, Dex_b, icnt)
    # Phase 1 (splice table): each move-in atom pulls the document value
    # its tag detached; plain insert atoms carry their own id.
    src = _tag_match(c.pool_mid, c.pool_off, c.mov_id, c.mov_off)
    src = src * valid[None, :].astype(jnp.float32)
    vals = jnp.where(c.pool_mid > 0, _matmul_take_ids(src, doc_ids), c.ins_ids)
    # Phase 2: splice through the standard prefix-sum scatter.
    out = out + _scatter_ids(jnp.where(kvalid, ins_pos, -1), vals, Lc)
    new_L = (L - Dex_b[-1]) + total
    return out, new_L


def rebase_change(
    c: DenseChange, over: DenseChange, L: jnp.ndarray, c_after: bool = False
) -> DenseChange:
    """Rebase ``c`` over concurrent ``over`` (both read the same input of
    length L); result reads over's output.

    Phase 1 resolves capture into per-tag effect tables: where every input
    slot LANDS in over's output (kept -> survivor position; over-moved ->
    over's matching move-in position — marks follow moved content;
    over-deleted -> nowhere), and which of c's move tags DIE (their unit
    deleted by over — deletion beats movement) or CANCEL (both sides moved
    the unit and over is later-sequenced, ``c_after=True``). Phase 2
    splices: detach lanes scatter to their landing positions, attach atoms
    map through the monotone boundary map (attaches anchor to their source
    gap — they slide, they do not follow moves) with dead/cancelled move-in
    atoms compacted out of the pool."""
    Lc = c.del_mask.shape[-1]
    Pc = c.ins_ids.shape[-1]
    ovalid, okeep, of_pos, oDex_b, obcum, oicnt = _prefix(over, L)
    _ob_of_k, o_kvalid, o_ins_pos, _ototal = _pool_positions(
        over, L, oDex_b, oicnt
    )
    cvalid, _ckeep, _csurv, _cDex_b, _cbcum, cicnt = _prefix(c, L)

    # Phase 1a: landing position of every input slot in over's output.
    over_del = ovalid & (over.del_mask > 0)
    over_mov = ovalid & (over.mov_id > 0)
    dest_tbl = _tag_match(
        over.mov_id, over.mov_off, over.pool_mid, over.pool_off
    ) * o_kvalid[None, :].astype(jnp.float32)
    o_dest = _matmul_take_small(dest_tbl, o_ins_pos)  # [Lc]
    tpos = jnp.where(
        okeep, of_pos, jnp.where(over_mov, o_dest, -1)
    )

    # Phase 1b: fate of c's move tags under over.
    c_mov = cvalid & (c.mov_id > 0)
    dead_slot = (c_mov & over_del).astype(jnp.int32)
    cancel_slot = (
        c_mov & over_mov & jnp.bool_(c_after)
    ).astype(jnp.int32)
    tag_tbl = _tag_match(c.pool_mid, c.pool_off, c.mov_id, c.mov_off)
    atom_dead = _matmul_take_small(tag_tbl, dead_slot) > 0
    atom_cancel = _matmul_take_small(tag_tbl, cancel_slot) > 0

    # Phase 2a: detach lanes follow their content. c's delete of a slot
    # over also deleted vanishes; a cancelled move leaves the unit where
    # over put it (over's move won).
    live_del = (c.del_mask * cvalid) * (tpos >= 0)
    del_out = _scatter_add(jnp.where(live_del > 0, tpos, -1), live_del, Lc)
    live_mov = c_mov & (tpos >= 0) & (cancel_slot == 0)
    mov_id_out = _scatter_ids(jnp.where(live_mov, tpos, -1), c.mov_id, Lc)
    mov_off_out = _scatter_ids(jnp.where(live_mov, tpos, -1), c.mov_off, Lc)

    # Phase 2b: boundaries b -> over-output boundary. c-before-over tie
    # (default) excludes over's own attaches at b; c_after includes them.
    b = jnp.arange(Lc + 1)
    incl = obcum
    excl = obcum - oicnt
    b_map = b - oDex_b + (incl if c_after else excl)
    cb_of_k, c_kvalid, _crs, _ctotal = _pool_boundaries(cicnt, Pc)
    atom_b = jnp.take(b_map, jnp.clip(cb_of_k, 0, Lc))
    atom_live = c_kvalid & ~atom_dead & ~atom_cancel
    newpos = jnp.cumsum(atom_live.astype(jnp.int32)) - 1
    tgt = jnp.where(atom_live, newpos, -1)
    ins_out = _scatter_add(
        jnp.where(atom_live, atom_b, -1),
        jnp.ones(Pc, jnp.int32),
        Lc + 1,
    )
    return DenseChange(
        del_out,
        ins_out,
        _scatter_ids(tgt, c.ins_ids, Pc),
        mov_id_out,
        mov_off_out,
        _scatter_ids(tgt, c.pool_mid, Pc),
        _scatter_ids(tgt, c.pool_off, Pc),
    )


def invert_change(
    doc_ids: jnp.ndarray, L: jnp.ndarray, c: DenseChange
) -> DenseChange:
    """Inverse changeset over c's output (values for revives come from the
    document, hence ``doc_ids``). Deletes invert to value-carrying
    re-inserts (Revive); moves invert to the RETURN move — same tag, with
    detach and attach sides swapped."""
    Lc = doc_ids.shape[-1]
    Pc = c.ins_ids.shape[-1]
    valid, keep, surv_pos, Dex_b, bcum, icnt = _prefix(c, L)
    b_of_k, kvalid, ins_pos, total = _pool_positions(c, L, Dex_b, icnt)
    # Detach everything c attached: insert atoms invert to deletes,
    # move-in atoms invert to the return move-out (same tag).
    is_min = kvalid & (c.pool_mid > 0)
    is_ins = kvalid & (c.pool_mid == 0)
    inv_del = _scatter_add(
        jnp.where(is_ins, ins_pos, -1), jnp.ones(Pc, jnp.int32), Lc
    )
    min_pos = jnp.where(is_min, ins_pos, -1)
    inv_mov_id = _scatter_ids(min_pos, c.pool_mid, Lc)
    inv_mov_off = _scatter_ids(min_pos, c.pool_off, Lc)
    # Re-attach everything c detached, at its original spot among
    # survivors (surv_pos evaluated as if the slot had survived): deletes
    # revive the document ids, move-outs become the return move-in.
    detached = valid & (_detach_mask(c) != 0)
    inv_ins = _scatter_add(
        jnp.where(detached, surv_pos, -1),
        jnp.ones(Lc, jnp.int32),
        Lc + 1,
    )
    # Pool: detached slots in input order (surv_pos is monotone there).
    dpos = jnp.cumsum(detached.astype(jnp.int32)) - 1
    was_del = detached & (c.del_mask != 0)
    was_mov = detached & (c.mov_id > 0)
    inv_ids = _scatter_ids(jnp.where(was_del, dpos, -1), doc_ids, Pc)
    inv_pmid = _scatter_ids(jnp.where(was_mov, dpos, -1), c.mov_id, Pc)
    inv_poff = _scatter_ids(jnp.where(was_mov, dpos, -1), c.mov_off, Pc)
    return DenseChange(
        inv_del, inv_ins, inv_ids, inv_mov_id, inv_mov_off, inv_pmid,
        inv_poff,
    )


def compose_change(
    a: DenseChange, b: DenseChange, L: jnp.ndarray
) -> Tuple[DenseChange, jnp.ndarray]:
    """Changeset equivalent to applying ``a`` then ``b`` (b reads a's
    output O1; the result reads a's input and writes b's output O2).

    Phase 1 resolves every input unit's FATE through both changesets with
    the move-effect tables: its O1 position (following a's moves), then
    its O2 position (following b's — dead if either side deleted it,
    "deletion wins over movement" in either order). Units that survive but
    land anywhere other than in-place become composed moves with FRESH
    singleton tags (tag identity is changeset-local, like the host
    engine's fresh mids; only the apply-result is contractual). Phase 2
    builds the attach pool by one sort over O2 positions — units-in-motion,
    surviving a-inserts and b-inserts interleaved — and anchors each atom
    at the gap after the last in-place unit preceding it (the host
    engine's cur_gap rule, computable as a comparison-matrix max because
    in-place units are monotone in both frames).

    Returns ``(change, overflow)``: ``overflow`` is 1 when the live attach
    pool exceeds ``Pc`` and the result truncated (the ERR_CAPACITY analog —
    callers must treat the composed change as invalid when set)."""
    Lc = a.del_mask.shape[-1]
    Pc = a.ins_ids.shape[-1]
    idx = jnp.arange(Lc)
    avalid, akeep, af_pos, aDex_b, abcum, aicnt = _prefix(a, L)
    La = (L - aDex_b[-1]) + abcum[-1]
    ab_of_k, a_kvalid, a_pos, _atotal = _pool_positions(a, L, aDex_b, aicnt)

    # Phase 1: O1 position of every input unit (a's capture table)...
    a_mov = avalid & (a.mov_id > 0)
    a_dest_tbl = _tag_match(
        a.mov_id, a.mov_off, a.pool_mid, a.pool_off
    ) * a_kvalid[None, :].astype(jnp.float32)
    a_dest = _matmul_take_small(a_dest_tbl, a_pos)
    p1 = jnp.where(akeep, af_pos, jnp.where(a_mov, a_dest, -1))

    # ...then the O2 position of every O1 position (b's capture table).
    bvalid, bkeep, bf_pos, bDex_b, _bbcum, bicnt = _prefix(b, La)
    _bb_of_m, b_kvalid, b_pos, _btotal = _pool_positions(b, La, bDex_b, bicnt)
    b_mov_q = bvalid & (b.mov_id > 0)
    b_dest_tbl = _tag_match(
        b.mov_id, b.mov_off, b.pool_mid, b.pool_off
    ) * b_kvalid[None, :].astype(jnp.float32)
    b_dest = _matmul_take_small(b_dest_tbl, b_pos)
    o2_of_q = jnp.where(bkeep, bf_pos, jnp.where(b_mov_q, b_dest, -1))

    # Gather b's verdict at each unit's O1 position (one-hot matmuls; the
    # +2 bias keeps the -1 "b deleted it" verdict distinct from the 0 a
    # matchless row produces).
    p1_oh = _onehot_f32(jnp.where(p1 >= 0, p1, -1), Lc)
    q2 = jnp.where(
        p1 >= 0, _matmul_take_small(p1_oh, o2_of_q + 2) - 2, -1
    )
    b_skip_at_p1 = _matmul_take_small(p1_oh, bkeep.astype(jnp.int32)) > 0

    alive = avalid & (q2 >= 0)
    inplace = alive & akeep & b_skip_at_p1
    moved = alive & ~inplace
    # Every dead unit — a-deleted, or moved by either side and then
    # b-deleted at its landing spot — composes to a plain delete at its
    # input slot ("deletion wins over movement" in either order).
    del_out = jnp.where(avalid & ~alive, 1, 0).astype(jnp.int32)

    # a's insert atoms: where did the inserted value land in O2 (if at
    # all)? Move-in atoms are EXCLUDED — their content is an input unit,
    # already tracked by the unit fate above.
    a_is_ins = a_kvalid & (a.pool_mid == 0)
    a_pos_oh = _onehot_f32(jnp.where(a_is_ins, a_pos, -1), Lc)
    a_atom_o2 = jnp.where(
        a_is_ins, _matmul_take_small(a_pos_oh, o2_of_q + 2) - 2, -1
    )
    # b's insert atoms land at their own pool positions; b's move-in atoms
    # are likewise covered by unit fates / a-insert relocation.
    b_is_ins = b_kvalid & (b.pool_mid == 0)

    # Phase 2: one sort over O2 positions merges the three atom sources.
    BIG = Lc + 2 * Pc + 2
    cand_pos = jnp.concatenate(
        [
            jnp.where(moved, q2, BIG),
            jnp.where(a_is_ins & (a_atom_o2 >= 0), a_atom_o2, BIG),
            jnp.where(b_is_ins, b_pos, BIG),
        ]
    )
    cand_val = jnp.concatenate([jnp.zeros(Lc, jnp.int32), a.ins_ids,
                                b.ins_ids])
    cand_unit = jnp.concatenate(
        [idx, jnp.full(Pc, -1, jnp.int32), jnp.full(Pc, -1, jnp.int32)]
    )
    order = jnp.argsort(cand_pos, stable=True)
    sorted_pos = jnp.take(cand_pos, order)
    sorted_val = jnp.take(cand_val, order)
    sorted_unit = jnp.take(cand_unit, order)
    n_live = jnp.sum((sorted_pos < BIG).astype(jnp.int32))
    overflow = (n_live > Pc).astype(jnp.int32)
    kpool = jnp.arange(Pc)
    pool_live = kpool < n_live
    pool_pos = jnp.where(pool_live, sorted_pos[:Pc], BIG)
    pool_unit = jnp.where(pool_live, sorted_unit[:Pc], -1)
    is_unit_atom = pool_unit >= 0
    # Fresh singleton tags for composed moves: tag = pool index + 1.
    pool_mid_out = jnp.where(is_unit_atom, kpool + 1, 0).astype(jnp.int32)
    pool_ids_out = jnp.where(
        is_unit_atom | ~pool_live, 0, sorted_val[:Pc]
    ).astype(jnp.int32)
    mov_id_out = _scatter_ids(
        jnp.where(is_unit_atom, pool_unit, -1), kpool + 1, Lc
    )
    # Anchor rule: each atom attaches at the gap AFTER the last in-place
    # unit preceding it in O2 (comparison-matrix max; in-place units are
    # monotone so max == last-seen).
    bnd = jnp.max(
        jnp.where(
            inplace[None, :] & (q2[None, :] < pool_pos[:, None]),
            (idx + 1)[None, :],
            0,
        ),
        axis=1,
    )
    ins_cnt_out = _scatter_add(
        jnp.where(pool_live, bnd, -1), jnp.ones(Pc, jnp.int32), Lc + 1
    )
    zero_off = jnp.zeros(Pc, jnp.int32)
    return (
        DenseChange(
            del_out,
            ins_cnt_out,
            pool_ids_out,
            mov_id_out,
            jnp.zeros(Lc, jnp.int32),
            pool_mid_out,
            zero_off,
        ),
        overflow,
    )


# -- host <-> dense conversion (test/bench plumbing, not the hot path) ------


def from_marks(marks, Lc: int, Pc: int) -> Tuple[DenseChange, int]:
    """Lower a tree/marks.py changeset (values must be int ids) to dense.
    Returns (change, input_len). Arrays are HOST numpy — batch conversion
    must not pay one host→device transfer per changeset; callers device_put
    the stacked batch once. ``mout``/``min`` lower to the move lanes
    (host mids are 0-based; dense tags are 1-based, 0 = no move); the
    lifting back to marks is ``tree/marks.lift_dense``."""
    del_mask = np.zeros(Lc, np.int32)
    ins_cnt = np.zeros(Lc + 1, np.int32)
    ins_ids = np.zeros(Pc, np.int32)
    mov_id = np.zeros(Lc, np.int32)
    mov_off = np.zeros(Lc, np.int32)
    pool_mid = np.zeros(Pc, np.int32)
    pool_off = np.zeros(Pc, np.int32)
    i = 0
    p = 0
    for t, v in marks:
        if t == "skip":
            i += v
        elif t == "del":
            del_mask[i : i + len(v)] = 1
            i += len(v)
        elif t == "ins":
            ins_cnt[i] += len(v)
            ins_ids[p : p + len(v)] = v
            p += len(v)
        elif t == "mout":
            mid, start, vals = v
            mov_id[i : i + len(vals)] = mid + 1
            mov_off[i : i + len(vals)] = np.arange(
                start, start + len(vals), dtype=np.int32
            )
            i += len(vals)
        elif t == "min":
            mid, start, n = v
            ins_cnt[i] += n
            pool_mid[p : p + n] = mid + 1
            pool_off[p : p + n] = np.arange(start, start + n, dtype=np.int32)
            p += n
        else:
            from fluidframework_tpu.tree.marks import _check_kind

            _check_kind(t)  # unknown kinds raise their own error first
            raise AssertionError("unreachable: _check_kind covers the IR")
    return (
        DenseChange(
            del_mask, ins_cnt, ins_ids, mov_id, mov_off, pool_mid, pool_off
        ),
        i,
    )


def doc_to_dense(doc, Lc: int) -> Tuple[jnp.ndarray, int]:
    ids = np.zeros(Lc, np.int32)
    ids[: len(doc)] = doc
    return jnp.asarray(ids), len(doc)


def dense_to_doc(ids: jnp.ndarray, L) -> list:
    return [int(x) for x in np.asarray(ids)[: int(L)]]


# -- batched/jitted entry points --------------------------------------------

batched_apply = jax.jit(jax.vmap(apply_change))
batched_rebase = jax.jit(
    jax.vmap(rebase_change, in_axes=(0, 0, 0, None)), static_argnums=(3,)
)
batched_invert = jax.jit(jax.vmap(invert_change))
batched_compose = jax.jit(jax.vmap(compose_change))
