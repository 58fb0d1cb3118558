"""Durable-log replay of one SharedMatrix channel by the plain reference.

A shared table is two permutation vectors and a store of cells
(FluidFramework ``packages/dds/matrix``: ``matrix.ts:80``,
``permutationvector.ts:151``): row order and column order are each a merge
sequence whose positions carry stable handles ``(orig, offset)``, and a cell
is keyed by a pair of handles, last sequenced writer wins. Two
:class:`OracleDoc` (whose ``Seg`` carries ``orig``, ``off`` and ``length``)
and a dict are the whole reference. Nothing here imports the program.

``replay`` takes the sequenced messages of one document as ``LogOp`` tuples
whose ``contents`` is the matrix channel's wire op (``insrow``/``inscol``
with ``pos``, ``count``, ``orig``; ``remrow``/``remcol`` with ``start``,
``end``; ``cell`` with ``row``, ``col`` handles and ``val``) or None, checks
that the log is a gapless total order, and returns the grid: rows in axis
order, each a list of cell values, None where unset. ``withhold`` and
``every`` are ``replay.py``'s: the same replay with one op left out (the
control), and the grid after each message for a reader who may have been
served any prefix. With ``every`` the result is a :class:`History`, which
can also give the grid no prefix has: the axes of one prefix under the
cells of a later one (``skew``), what a read that is not one cut serves.
"""

from __future__ import annotations

from typing import Iterable, List, Optional

from benchmark.reference.oracle import (
    F_ARG, F_CLIENT, F_LEN, F_MSN, F_POS1, F_POS2, F_REF, F_SEQ, F_TYPE,
    NO_CLIENT, OP_INSERT, OP_REMOVE, OP_WIDTH, OracleDoc,
)
from benchmark.reference.replay import LogFault, LogOp

AXIS_KINDS = ("insrow", "inscol", "remrow", "remcol")


def lower(op: LogOp) -> list:
    """An axis op as the op row a remote replica applies."""
    c = op.contents
    row = [0] * OP_WIDTH
    row[F_SEQ], row[F_REF], row[F_CLIENT], row[F_MSN] = (
        op.seq, op.ref, op.client, op.msn,
    )
    if c["k"].startswith("ins"):
        row[F_TYPE], row[F_POS1] = OP_INSERT, c["pos"]
        row[F_ARG], row[F_LEN] = c["orig"], c["count"]
    else:
        row[F_TYPE], row[F_POS1], row[F_POS2] = OP_REMOVE, c["start"], c["end"]
    return row


def handles(axis: OracleDoc) -> list:
    """Live handles in axis order."""
    return [
        (s.orig, s.off + j)
        for s in axis.segs if s.removed_seq is None
        for j in range(s.length)
    ]


def join(rows: list, cols: list, cells: dict) -> list:
    return [[cells.get((r, c)) for c in cols] for r in rows]


class History:
    """The table after each message of a log: ``grids[n]`` is the replay
    of the first ``n`` messages, ``grids[0]`` the empty start."""

    def __init__(self):
        self.grids: List[list] = [[]]
        self._axes = [([], [])]  # (row handles, column handles) after each
        self.writes: list = []  # (message number, cell key, value)
        self.kinds: List[Optional[str]] = [None]  # kind of message n, 1-based

    def _push(self, kind, rows, cols, grid) -> None:
        self.kinds.append(kind)
        self._axes.append((rows, cols))
        self.grids.append(grid)

    def handles_at(self, n: int) -> tuple:
        """(row handles, column handles), live and in axis order."""
        return self._axes[n]

    def cells_at(self, n: int) -> dict:
        return {key: val for at, key, val in self.writes if at <= n}

    def skew(self, axes_at: int, cells_at: int) -> list:
        """The axes as of ``axes_at`` messages joined with the cells as of
        ``cells_at``: one cut only when the two are equal."""
        return join(*self.handles_at(axes_at), self.cells_at(cells_at))


def replay(
    ops: Iterable[LogOp], head: int, withhold: Optional[int] = None,
    first: int = 1, every: bool = False,
) -> tuple:
    """(grid, acked pairs, channel ops applied) of a log that must run
    ``first``..``head`` without a gap. ``withhold`` names the ordinal
    (among the channel's ops) of one op to leave out. With ``every`` the
    first element is a :class:`History`."""
    axes = {"row": OracleDoc(NO_CLIENT), "col": OracleDoc(NO_CLIENT)}
    cells: dict = {}
    acked, want, applied = set(), first, 0
    hist = History() if every else None
    rows: list = []
    cols: list = []
    grid: list = []
    for op in ops:
        if op.seq != want:
            raise LogFault(f"durable log gap: wanted seq {want}, got {op.seq}")
        want += 1
        c = op.contents
        kind = c.get("k") if isinstance(c, dict) else None
        if kind in AXIS_KINDS or kind == "cell":
            acked.add((op.client, op.csn))
            if applied != withhold:
                if kind == "cell":
                    key = (tuple(c["row"]), tuple(c["col"]))
                    cells[key] = c["val"]
                    if hist is not None:
                        hist.writes.append((len(hist.grids), key, c["val"]))
                        if key[0] in rows and key[1] in cols:
                            i, j = rows.index(key[0]), cols.index(key[1])
                            grid = list(grid)
                            grid[i] = list(grid[i])
                            grid[i][j] = c["val"]
                else:
                    axis = axes[kind[3:]]
                    axis.apply(lower(op))
                    if hist is not None:
                        rows, cols = handles(axes["row"]), handles(axes["col"])
                        grid = join(rows, cols, cells)
            applied += 1
            if applied % 64 == 0:
                axes["row"].reclaim()
                axes["col"].reclaim()
        else:
            kind = None
        if hist is not None:
            hist._push(kind, rows, cols, grid)
    if want != head + 1:
        raise LogFault(f"durable log ends at {want - 1}, head is {head}")
    if hist is not None:
        return hist, acked, applied
    return join(handles(axes["row"]), handles(axes["col"]), cells), acked, applied
