"""Durable-log replay by the plain reference.

``replay`` takes the sequenced operations of one channel as plain tuples
(the harness reads them out of the program's durable log; nothing here
imports the program), checks that the log is a gapless total order
1..head, lowers each SharedString wire op to an op row the way a client
does, applies it to :class:`OracleDoc`, and returns the text together
with the acknowledged ``(client, clientSequenceNumber)`` pairs it saw.

``withhold`` is the control of the comparison: the same replay with one
sequenced op left out. The state is integers, so there is no lower
precision to fall to; the analog is a log that lost one op, and a
comparison that is worth anything has to notice it.
"""

from __future__ import annotations

from typing import Iterable, NamedTuple, Optional

from benchmark.reference.oracle import (
    F_ARG, F_CLIENT, F_LEN, F_MSN, F_POS1, F_POS2, F_REF, F_SEQ, F_TYPE,
    NO_CLIENT, OP_ANNOTATE, OP_INSERT, OP_REMOVE, OP_WIDTH, OracleDoc,
)


class LogOp(NamedTuple):
    """One sequenced message of a document's durable log."""

    seq: int
    ref: int
    client: int
    csn: int
    msn: int
    contents: Optional[dict]  # the channel's wire op; None for system messages


class LogFault(Exception):
    """The durable log broke a guarantee the configuration states."""


def lower(op: LogOp, payloads: dict) -> Optional[list]:
    """A SharedString wire op as one op row; inserts record their text."""
    c = op.contents
    if c is None:
        return None
    row = [0] * OP_WIDTH
    row[F_SEQ], row[F_REF], row[F_CLIENT], row[F_MSN] = (
        op.seq, op.ref, op.client, op.msn,
    )
    kind = c.get("k")
    if kind == "ins":
        payloads[c["orig"]] = c["text"]
        row[F_TYPE], row[F_POS1] = OP_INSERT, c["pos"]
        row[F_ARG], row[F_LEN] = c["orig"], len(c["text"])
    elif kind == "rem":
        row[F_TYPE], row[F_POS1], row[F_POS2] = OP_REMOVE, c["start"], c["end"]
    elif kind == "ann":
        row[F_TYPE], row[F_POS1], row[F_POS2] = (
            OP_ANNOTATE, c["start"], c["end"],
        )
        row[F_ARG] = c["val"]
    else:
        return None
    return row


def replay(
    ops: Iterable[LogOp], head: int, withhold: Optional[int] = None,
    first: int = 1, every: bool = False,
) -> tuple:
    """(text, acked pairs, channel ops applied) of a log that must run
    ``first``..``head`` without a gap. ``withhold`` names the ordinal
    (among the channel's ops) of one op to leave out: the control. With
    ``every`` the text is a list, the text after each message of the log:
    ``[n]`` is the replay of ``first``..``first + n - 1`` and ``[0]`` the
    empty start, for a reader who may have been served any prefix."""
    oracle, payloads = OracleDoc(NO_CLIENT), {}
    acked, want, applied = set(), first, 0
    texts = [""]
    for op in ops:
        if op.seq != want:
            raise LogFault(f"durable log gap: wanted seq {want}, got {op.seq}")
        want += 1
        row = lower(op, payloads)
        if row is not None:
            acked.add((op.client, op.csn))
            if applied != withhold:
                oracle.apply(row)
            applied += 1
            if applied % 64 == 0:
                oracle.reclaim()
        if every:
            texts.append(oracle.text(payloads) if row is not None else texts[-1])
    if want != head + 1:
        raise LogFault(f"durable log ends at {want - 1}, head is {head}")
    return (texts if every else oracle.text(payloads)), acked, applied
