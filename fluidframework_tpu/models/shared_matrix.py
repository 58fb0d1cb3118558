"""SharedMatrix — 2-D grid with collaborative row/col insertion and LWW cells.

Reference: ``packages/dds/matrix`` (``matrix.ts:80``): row and column order
are two merge-tree clients used as **permutation vectors**
(``permutationvector.ts:151``), cells are a sparse store keyed by stable
row/col *handles* so concurrent reorder and cell writes commute.

TPU design: both permutation vectors are :class:`SegmentState` tables driven
by the same merge kernel as SharedString (a row-insert of ``count`` rows is
one segment of length ``count``; each position's stable handle is
``(orig, offset)``), and the cell store is host-side LWW with
pending-local-wins — the reference's conflict policy.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from fluidframework_tpu.ops import encode as E
from fluidframework_tpu.ops.merge_kernel import compact, jit_apply_ops
from fluidframework_tpu.ops.segment_state import (
    capacity_of,
    grow,
    lanes_summary,
    make_interactive_state,
    to_host,
)
from fluidframework_tpu.protocol.constants import (
    KIND_FREE,
    RSEQ_NONE,
    UNASSIGNED_SEQ,
)
from fluidframework_tpu.protocol.types import SequencedDocumentMessage
from fluidframework_tpu.runtime.shared_object import SharedObject

# Axis-run identity: conn_no * stride + per-connection counter (slots
# recycle; the connection ordinal never does).
_MINT_STRIDE = 1 << 14

#: The wire kinds of a matrix channel: the four axis ops and the cell write.
MATRIX_KINDS = ("insrow", "inscol", "remrow", "remcol", "cell")


def axis_row_from_wire(
    c: dict, *, seq: int, ref: int, client: int, msn: int
) -> np.ndarray:
    """A sequenced axis op (``insrow``/``inscol``/``remrow``/``remcol``)
    as one kernel row: what every remote replica applies, and what the
    service's device lambda enqueues for the axis's fleet slot."""
    common = dict(seq=seq, ref=ref, client=client, msn=msn)
    if c["k"].startswith("ins"):
        return E.insert(c["pos"], c["orig"], c["count"], **common)
    return E.remove(c["start"], c["end"], **common)


def axis_handles(h, msn: int = 0) -> Tuple[list, set]:
    """One axis state (host lanes) as its live handles in axis order,
    ``(orig, offset)`` per position, and the handles it still HOLDS: the
    live ones and those of a removal the minimum sequence number has not
    passed (an op in flight may still address them). ``msn`` is a minimum
    sequence number the caller knows beside the state's own, which only
    this axis's ops advance."""
    n = int(h.count)
    kind, rseq = np.asarray(h.kind)[:n], np.asarray(h.rseq)[:n]
    orig, off = np.asarray(h.orig)[:n], np.asarray(h.off)[:n]
    length, min_seq = np.asarray(h.length)[:n], max(int(h.min_seq), msn)
    live, held = [], set()
    for i in np.flatnonzero(kind != KIND_FREE).tolist():
        o, f = int(orig[i]), int(off[i])
        run = [(o, f + j) for j in range(int(length[i]))]
        r = int(rseq[i])
        if r == RSEQ_NONE:
            live.extend(run)
        elif r != UNASSIGNED_SEQ and r <= min_seq:
            continue
        held.update(run)
    return live, held


def cell_key_text(rh: tuple, ch: tuple) -> str:
    return f"{rh[0]}:{rh[1]}:{ch[0]}:{ch[1]}"


class _PermutationVector:
    """One axis's order: a kernel-backed sequence of handle runs."""

    def __init__(self, capacity: int, self_client: int):
        self.state = make_interactive_state(capacity, self_client)
        self.pulls = 0  # device→host pulls of the state for its handles
        self._handles: Optional[list] = None

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, value) -> None:
        # Whoever replaces the state (an op, a restamp, a load) ends the
        # cached handles' life.
        self._state = value
        self._handles = None

    def apply(self, row: np.ndarray) -> None:
        self.state = jit_apply_ops(self.state, row[None, :].astype(np.int32))
        cap = capacity_of(self.state)
        if int(self.state.count) > cap - 8:
            self.state = compact(self.state)
            if int(self.state.count) > cap - 8:
                self.state = grow(self.state, cap * 2)

    def handles(self) -> list:
        """Live handles in axis order: (orig, offset) per position. Pulled
        to the host once per change of the axis, not once per call."""
        if self._handles is None:
            self.pulls += 1
            self._handles = axis_handles(to_host(self.state))[0]
        return self._handles


class SharedMatrix(SharedObject):
    def __init__(self, channel_id: str, capacity: int = 128):
        super().__init__(channel_id)
        self._capacity = capacity
        self._rows: Optional[_PermutationVector] = None
        self._cols: Optional[_PermutationVector] = None
        self._cells: Dict[Tuple[tuple, tuple], Any] = {}
        self._cell_pending: Dict[Tuple[tuple, tuple], int] = {}
        self._lseq = 0
        self._mint = 0  # per-connection axis-run id counter
        self._rebase_view: Optional[dict] = None  # axes as of a resubmit batch

    def on_reconnect(self, new_client_id: int) -> None:
        """Adopt the new client slot on both axis kernels (see
        ``segment_state.adopt_client_slot`` for the restamp rationale)."""
        from fluidframework_tpu.ops.segment_state import adopt_client_slot

        self._mint = 0
        for vec in (self._rows, self._cols):
            vec.state = adopt_client_slot(vec.state, new_client_id)

    def adopt_stashed_slot(self, old_client_id: int) -> None:
        import jax.numpy as jnp

        for vec in (self._rows, self._cols):
            vec.state = vec.state._replace(
                self_client=jnp.int32(old_client_id)
            )

    def attach(self, runtime) -> None:
        super().attach(runtime)
        self._rows = _PermutationVector(self._capacity, self.client_id)
        self._cols = _PermutationVector(self._capacity, self.client_id)

    # -- reads ----------------------------------------------------------------

    @property
    def row_count(self) -> int:
        return len(self._rows.handles())

    @property
    def col_count(self) -> int:
        return len(self._cols.handles())

    def get_cell(self, row: int, col: int, default: Any = None) -> Any:
        rh = self._rows.handles()[row]
        ch = self._cols.handles()[col]
        return self._cells.get((rh, ch), default)

    def to_list(self, default: Any = None) -> list:
        rows = self._rows.handles()
        cols = self._cols.handles()
        return [
            [self._cells.get((r, c), default) for c in cols] for r in rows
        ]

    # -- local edits ----------------------------------------------------------

    def _vector_op(self, axis: str, contents: dict, row: np.ndarray, kind: str):
        vec = self._rows if axis == "row" else self._cols
        vec.apply(row)
        self.submit_local_message(
            contents, {"kind": kind, "axis": axis, "lseq": self._lseq}
        )

    def insert_rows(self, pos: int, count: int) -> None:
        self._insert_axis("row", pos, count)

    def insert_cols(self, pos: int, count: int) -> None:
        self._insert_axis("col", pos, count)

    def _mint_orig(self) -> int:
        self._mint += 1
        assert self._mint < _MINT_STRIDE
        return self.conn_no * _MINT_STRIDE + self._mint

    def _insert_axis(self, axis: str, pos: int, count: int) -> None:
        assert 0 < count < _MINT_STRIDE
        self._lseq += 1
        orig = self._mint_orig()
        row = E.insert(
            pos, orig, count, seq=UNASSIGNED_SEQ,
            client=self.client_id, lseq=self._lseq,
        )
        self._vector_op(
            axis,
            {"k": f"ins{axis}", "pos": pos, "count": count, "orig": orig},
            row,
            "insert",
        )

    def remove_rows(self, pos: int, count: int) -> None:
        self._remove_axis("row", pos, count)

    def remove_cols(self, pos: int, count: int) -> None:
        self._remove_axis("col", pos, count)

    def _remove_axis(self, axis: str, pos: int, count: int) -> None:
        self._lseq += 1
        row = E.remove(
            pos, pos + count, seq=UNASSIGNED_SEQ,
            client=self.client_id, lseq=self._lseq,
        )
        self._vector_op(
            axis,
            {"k": f"rem{axis}", "start": pos, "end": pos + count},
            row,
            "remove",
        )

    def set_cell(self, row: int, col: int, value: Any) -> None:
        rh = self._rows.handles()[row]
        ch = self._cols.handles()[col]
        key = (rh, ch)
        self._cells[key] = value
        self._cell_pending[key] = self._cell_pending.get(key, 0) + 1
        self.submit_local_message(
            {"k": "cell", "row": list(rh), "col": list(ch), "val": value},
            {"kind": "cell"},
        )

    # -- sequenced stream -----------------------------------------------------

    def process_core(
        self, msg: SequencedDocumentMessage, local: bool, local_metadata: Optional[Any]
    ) -> None:
        c = msg.contents
        if c["k"] == "cell":
            key = (tuple(c["row"]), tuple(c["col"]))
            if local:
                left = self._cell_pending.get(key, 0) - 1
                if left <= 0:
                    self._cell_pending.pop(key, None)
                else:
                    self._cell_pending[key] = left
                return
            if self._cell_pending.get(key, 0) > 0:
                return  # pending local write wins until acked
            self._cells[key] = c["val"]
            return

        vec = self._rows if c["k"].endswith("row") else self._cols
        if local:
            row = E.ack(
                local_metadata["kind"],
                local_metadata["lseq"],
                msg.sequence_number,
                msn=msg.minimum_sequence_number,
            )
        else:
            row = axis_row_from_wire(
                c,
                seq=msg.sequence_number,
                ref=msg.reference_sequence_number,
                client=msg.client_id,
                msn=msg.minimum_sequence_number,
            )
        vec.apply(row)

    # -- reconnect rebase (SharedString.resubmit_core on an axis) -------------

    def begin_resubmit(self) -> None:
        # Every regeneration of one batch reads the reconnect-time axes.
        self._rebase_view = {
            "row": to_host(self._rows.state), "col": to_host(self._cols.state),
        }

    def end_resubmit(self) -> None:
        self._rebase_view = None

    def resubmit_core(self, contents: Any, local_metadata: Any) -> None:
        """Regenerate a pending op against the caught-up state. A cell
        write is keyed by handles and goes out again as it is; an axis op
        is positions at a refSeq that is gone, so it is re-created from
        the rows that carry its local sequence number."""
        from fluidframework_tpu.ops.segment_state import restamp_rows
        from fluidframework_tpu.runtime.rebase import (
            regen_insert,
            regen_remove,
        )

        kind = local_metadata["kind"]
        if kind == "cell":
            self.submit_local_message(contents, local_metadata)
            return
        axis, L = local_metadata["axis"], local_metadata["lseq"]
        vec = self._rows if axis == "row" else self._cols
        view = self._rebase_view
        h = view[axis] if view else to_host(vec.state)
        if kind == "remove":
            for run in regen_remove(h, L):
                self._lseq += 1
                vec.state = restamp_rows(vec.state, "rlseq", run.rows, self._lseq)
                self.submit_local_message(
                    {"k": f"rem{axis}", "start": run.pos,
                     "end": run.pos + run.span},
                    {"kind": "remove", "axis": axis, "lseq": self._lseq},
                )
            return
        runs = regen_insert(h, L)
        # Nothing parts a pending insert's rows: a remote insert cannot see
        # them and lands beside them, and this client's own later inserts
        # regenerate under their own local sequence numbers. So the op goes
        # out again as ONE insert under its run id, and the handles (and
        # the cells written under them) stand.
        assert len(runs) <= 1, "a pending axis insert regenerated in parts"
        for run in runs:
            self._lseq += 1
            vec.state = restamp_rows(vec.state, "lseq", run.rows, self._lseq)
            self.submit_local_message(
                {"k": f"ins{axis}", "pos": run.pos, "count": run.span,
                 "orig": contents["orig"]},
                {"kind": "insert", "axis": axis, "lseq": self._lseq},
            )

    # -- summary / load -------------------------------------------------------

    def summarize_core(self) -> dict:
        def dump(vec):
            return lanes_summary(to_host(vec.state))

        rows = set(self._rows.handles())
        cols = set(self._cols.handles())
        cells = {}
        for (rh, chd), v in self._cells.items():
            if rh in rows and chd in cols:  # GC unreachable cells
                cells[cell_key_text(rh, chd)] = v
        return {"rows": dump(self._rows), "cols": dump(self._cols), "cells": cells}

    def load_core(self, summary: dict) -> None:
        import jax.numpy as jnp

        def restore(d):
            vec = _PermutationVector(
                max(self._capacity, d["count"] + 16), self.client_id
            )
            h = to_host(vec.state)
            updates = {}
            for k, vals in d["lanes"].items():
                lane = np.asarray(getattr(h, k)).copy()
                lane[: d["count"]] = vals
                updates[k] = jnp.asarray(lane)
            vec.state = vec.state._replace(
                **updates,
                count=jnp.int32(d["count"]),
                min_seq=jnp.int32(d["min_seq"]),
                cur_seq=jnp.int32(d["cur_seq"]),
            )
            return vec

        self._rows = restore(summary["rows"])
        self._cols = restore(summary["cols"])
        # A stashed-state snapshot may carry pending rows (unacked lseq
        # stamps): future local ops must not collide with them.
        self._lseq = max(
            [0]
            + [
                int(v)
                for d in (summary["rows"], summary["cols"])
                for lane in ("lseq", "rlseq", "alseq")
                for v in d["lanes"].get(lane, [])
            ]
        )
        self._cells = {}
        for key, v in summary["cells"].items():
            a, b, c, d = (int(x) for x in key.split(":"))
            self._cells[((a, b), (c, d))] = v
