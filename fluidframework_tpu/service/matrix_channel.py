"""The service's side of a SharedMatrix channel.

Reference: ``packages/dds/matrix`` (``matrix.ts:80``): row and column
order are two merge-tree clients used as permutation vectors
(``permutationvector.ts:151``), cells a sparse store keyed by stable
row/col handles, last writer wins. The service's replica follows the
client's (``models/shared_matrix.py``): both axes are kernel states, here
two slots of the device backend's fleet stepped by the boxcar that
carries text rows, and the cells live on the host beside them, as a
string channel's payloads do (the device holds structure).

The sequenced log's order is the only order the service sees, so a cell's
value is its last sequenced write. A read is one cut: the axes are
gathered on the device at one sequence number and come to the host while
the serving loop goes on sequencing, so the store lends the gather the
cells AS OF that number (``lend``: the dict is shared until the next write
copies it, so a read that no write races costs nothing) and the reply is
joined from that loan, never from the live store.

A cell whose row or column is gone is unreachable for ever. The host does
not know which handles a positional remove hit, but every gather brings
the axes to the host anyway: a cell of the loan whose handle the axis no
longer holds (removed at or under the minimum sequence number, or compacted
away) is dropped from the live store then: at a grid read, a summary, a
hibernation. The minimum sequence number is the highest the channel's ops
have carried, cell writes too (``msn``; an axis state's own moves with
that axis's ops alone), and a table that takes removals and is never
gathered is gathered by the backend itself (``removals``,
``DeviceFleetBackend.tables_due``), so the store is bounded by what is
reachable plus the last few removals' cells.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Tuple

from fluidframework_tpu.models.shared_matrix import axis_handles, cell_key_text
from fluidframework_tpu.ops.segment_state import lanes_summary

CellKey = Tuple[tuple, tuple]  # (row handle, column handle)
_GONE = object()


class MatrixRead(NamedTuple):
    """One table as one cut: both axis states and the cells, all as of
    the sequence number ``seq``."""

    rows: Any  # SegmentState of the row axis
    cols: Any
    cells: Dict[CellKey, Any]
    seq: int
    msn: int  # the minimum sequence number as of ``seq``


class MatrixChannel:
    """One matrix channel's host state: the keys of its two axis slots,
    the cells, the highest sequence number taken in (an axis op enqueued
    or a cell written) with the minimum sequence number it carried, and
    the row and column removals taken in since the last gather."""

    __slots__ = ("axes", "cells", "seq", "msn", "removals", "_lent")

    def __init__(self, doc_id: str, address: str):
        self.axes = ((doc_id, f"{address}#rows"), (doc_id, f"{address}#cols"))
        self.cells: Dict[CellKey, Any] = {}
        self.seq = self.msn = self.removals = 0
        self._lent = False

    def lend(self) -> Tuple[Dict[CellKey, Any], int, int]:
        """The cells as of ``seq`` (never to change under the borrower),
        ``seq`` and ``msn``: what a gather of the axes takes along."""
        self._lent = True
        self.removals = 0
        return self.cells, self.seq, self.msn

    def _own(self) -> Dict[CellKey, Any]:
        if self._lent:
            self.cells, self._lent = dict(self.cells), False
        return self.cells

    def write(self, key: CellKey, value: Any) -> bool:
        """Last sequenced writer wins. True when the key is new."""
        cells = self._own()
        new = key not in cells
        cells[key] = value
        return new

    def drop(self, keys: List[CellKey]) -> int:
        cells = self._own()
        return sum(cells.pop(k, _GONE) is not _GONE for k in keys)


def _axes(read: MatrixRead) -> Tuple[list, list, List[CellKey]]:
    """(live row handles, live column handles, unreachable cells) of one
    cut: the cells whose row or column its axis no longer holds."""
    rows, rows_held = axis_handles(read.rows, read.msn)
    cols, cols_held = axis_handles(read.cols, read.msn)
    gone = [
        k for k in read.cells
        if k[0] not in rows_held or k[1] not in cols_held
    ]
    return rows, cols, gone


def join(read: MatrixRead) -> Tuple[list, List[CellKey]]:
    """(grid, unreachable cells) of one cut: rows in axis order, each a
    list of cell values, None where unset."""
    rows, cols, gone = _axes(read)
    get = read.cells.get
    return [[get((r, c)) for c in cols] for r in rows], gone


def summary(read: MatrixRead) -> Tuple[dict, List[CellKey]]:
    """(summary, unreachable cells) of one cut, the summary in the
    client's ``summarize_core`` shape, so that a fresh ``SharedMatrix``
    loads from it."""
    rows, cols, gone = _axes(read)
    rows, cols = set(rows), set(cols)
    return {
        "rows": lanes_summary(read.rows),
        "cols": lanes_summary(read.cols),
        "cells": {
            cell_key_text(rh, ch): v for (rh, ch), v in read.cells.items()
            if rh in rows and ch in cols
        },
    }, gone


def unreachable(read: MatrixRead) -> List[CellKey]:
    """The unreachable cells of one cut alone (a gather no reader asked
    for: no grid is joined)."""
    return _axes(read)[2]
