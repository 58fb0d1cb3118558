"""Struct-of-arrays document state for the merge-sequence kernel.

TPU-native replacement for the reference merge-tree's pointer-based B-tree
(``packages/dds/merge-tree/src/mergeTreeNodes.ts``): one document is a dense
int32 table of segment rows in document order (holes allowed, reclaimed by
:func:`fluidframework_tpu.ops.merge_kernel.compact`). Every per-segment stamp
of the reference — ``seq``, ``clientId``, ``localSeq``, ``removedSeq``,
``removedClientIds``, ``localRemovedSeq`` (``mergeTreeNodes.ts:126-175``) —
becomes an int32 lane, so op application is masked elementwise math + prefix
sums instead of tree traversal, and ``vmap`` batches documents.

Content addressing: segment text lives host-side, keyed by ``orig`` (an id the
inserting client allocates) — a row covers ``payload[orig][off : off+length]``.
Splits are pure array ops (adjust ``off``/``length``); the device never sees
text bytes, only structure.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from fluidframework_tpu.protocol.constants import (
    KIND_FREE,
    MAX_WRITERS,
    RSEQ_NONE,
)


class SegmentState(NamedTuple):
    """One document's merge state (or a [D, ...] batch when stacked/vmapped).

    Array lanes have shape ``[S]`` (segment capacity); scalars are 0-d int32.
    """

    # --- per-segment lanes [S] ---
    kind: jnp.ndarray  # KIND_FREE / KIND_TEXT / KIND_MARKER
    orig: jnp.ndarray  # host content id
    off: jnp.ndarray  # offset into the orig payload
    length: jnp.ndarray  # segment length (chars)
    seq: jnp.ndarray  # insert seq (UNASSIGNED_SEQ while local)
    client: jnp.ndarray  # inserting client slot
    lseq: jnp.ndarray  # local seq of pending insert (0 = none)
    rseq: jnp.ndarray  # removedSeq (RSEQ_NONE = not removed, UNASSIGNED_SEQ = local)
    rlseq: jnp.ndarray  # local seq of pending remove (0 = none)
    rbits: jnp.ndarray  # bitmask of removing client slots 0-30 (removedClientIds)
    rbits2: jnp.ndarray  # bitmask of removing client slots 31-61
    rbits3: jnp.ndarray  # bitmask of removing client slots 62-92
    aseq: jnp.ndarray  # seq of last annotate (0 = never)
    alseq: jnp.ndarray  # local seq of pending annotate (0 = none)
    aval: jnp.ndarray  # interned annotate value
    rbits4: jnp.ndarray  # bitmask of removing client slots 93-123
    # --- per-document scalars ---
    count: jnp.ndarray  # high-water mark of used rows
    min_seq: jnp.ndarray  # collab-window minimum sequence number
    cur_seq: jnp.ndarray  # last applied sequence number
    self_client: jnp.ndarray  # local client slot (NO_CLIENT on the server)
    err: jnp.ndarray  # ERR_* flag bits (sticky)


SEGMENT_LANES = (
    "kind",
    "orig",
    "off",
    "length",
    "seq",
    "client",
    "lseq",
    "rseq",
    "rlseq",
    "rbits",
    "rbits2",
    "rbits3",
    "aseq",
    "alseq",
    "aval",
    "rbits4",
)

# The removers set (reference ``removedClientIds``): one bit per writer
# slot, RBITS_PER_LANE usable bits to an int32 lane (the sign bit stays
# out of the shift arithmetic), lane i holding slots 31*i .. 31*i+30.
# Everything that reads or writes the set walks this tuple; a wider set is
# one more name here (appended to the END of SEGMENT_LANES: every packed
# index derives from that order), one more field above, and the constant.
RBITS_LANES = ("rbits", "rbits2", "rbits3", "rbits4")
RBITS_PER_LANE = 31
assert MAX_WRITERS == RBITS_PER_LANE * len(RBITS_LANES), MAX_WRITERS


def rbits_of(state) -> tuple:
    """The removers lanes of a state (or of a host copy), in slot order."""
    return tuple(getattr(state, k) for k in RBITS_LANES)


def interactive_device():
    """Device for per-op interactive applies: the host CPU backend.

    A single client editing one document applies one small op at a time —
    latency-bound, not throughput-bound — so the XLA:CPU backend is the
    right executor (an accelerator round-trip per keystroke costs more
    than the op). The
    service-scale paths (``make_batched_state`` + ``batched_apply_ops``,
    ``parallel.mesh.DocShard``) keep the default device: there the work is
    thousands of documents per dispatch and belongs on the TPU mesh.
    """
    import jax

    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError:  # pragma: no cover - cpu backend always exists
        return jax.devices()[0]


def make_interactive_state(
    capacity: int, self_client: int, min_seq: int = 0
) -> SegmentState:
    """``make_state`` committed to the interactive (CPU) device: every
    subsequent jit on it executes host-side, keeping single-op DDS latency
    off the accelerator round-trip path."""
    import jax

    return jax.device_put(
        make_state(capacity, self_client, min_seq), interactive_device()
    )


def make_state(capacity: int, self_client: int, min_seq: int = 0) -> SegmentState:
    """Fresh empty document state with room for ``capacity`` segment rows."""
    def z():
        # Distinct buffers per lane: donation rejects aliased arguments.
        return jnp.zeros((capacity,), jnp.int32)

    return SegmentState(
        kind=jnp.full((capacity,), KIND_FREE, jnp.int32),
        orig=z(),
        off=z(),
        length=z(),
        seq=z(),
        client=z(),
        lseq=z(),
        rseq=jnp.full((capacity,), RSEQ_NONE, jnp.int32),
        rlseq=z(),
        rbits=z(),
        rbits2=z(),
        rbits3=z(),
        aseq=z(),
        alseq=z(),
        aval=z(),
        rbits4=z(),
        count=jnp.int32(0),
        min_seq=jnp.int32(min_seq),
        cur_seq=jnp.int32(0),
        self_client=jnp.int32(self_client),
        err=jnp.int32(0),
    )


def make_batched_state(n_docs: int, capacity: int, self_client: int) -> SegmentState:
    """[D, S] batch of empty documents (the vmap/pjit operand)."""
    one = make_state(capacity, self_client)
    return SegmentState(*[jnp.broadcast_to(x, (n_docs,) + x.shape).copy() for x in one])


def capacity_of(state: SegmentState) -> int:
    return state.kind.shape[-1]


def grow(state: SegmentState, new_capacity: int) -> SegmentState:
    """Reallocate a (single-doc) state with a larger segment table."""
    cap = capacity_of(state)
    assert new_capacity > cap, "grow() requires a larger capacity"
    pad = new_capacity - cap
    fills = {"kind": KIND_FREE, "rseq": RSEQ_NONE}
    return state._replace(
        **{
            k: jnp.concatenate(
                [
                    getattr(state, k),
                    jnp.full((pad,), fills.get(k, 0), jnp.int32),
                ]
            )
            for k in SEGMENT_LANES
        }
    )


def removed_by_slot(rbits, client):
    """Whether the writer slot appears in the removers bitmask ``rbits``
    (the lanes of :data:`RBITS_LANES`, in order). Pure jnp
    (broadcastable) — shared by the XLA and Pallas perspectives."""
    # Arithmetic lane select (masked blends + one shift): Mosaic fails to
    # lower a broadcasting select over the shifted lanes.
    client = jnp.asarray(client, jnp.int32)
    lane = jnp.clip(client // RBITS_PER_LANE, 0, len(rbits) - 1)
    bits = sum(
        lane_bits * (lane == i).astype(jnp.int32)
        for i, lane_bits in enumerate(rbits)
    )
    shift = jnp.clip(client - RBITS_PER_LANE * lane, 0, RBITS_PER_LANE - 1)
    # Out-of-range slots (negative sentinels, >= MAX_WRITERS) must read
    # as not-removed rather than aliasing the clipped lane's bits — the
    # sequencer nacks writer MAX_WRITERS+, but this guard keeps the read
    # honest for any caller.
    in_range = (client >= 0) & (client < MAX_WRITERS)
    return (((bits >> shift) & 1) == 1) & in_range


def removed_by_slot_host(rbits, client: int) -> bool:
    """Host-int twin of removed_by_slot for per-row Python loops (a jnp
    call per row would cost a device dispatch each): ``rbits`` is one
    row's lane values as ints. Same slot layout — keep the two in this
    module so the mapping has one home."""
    if client < 0 or client >= MAX_WRITERS:
        return False
    lane, bit = divmod(client, RBITS_PER_LANE)
    return bool((int(rbits[lane]) >> bit) & 1)


def writer_bits(slot) -> tuple:
    """One single-bit mask per removers lane for a writer slot: the lane
    that holds the slot carries its bit, the others 0."""
    s = jnp.asarray(slot, jnp.int32)
    n = RBITS_PER_LANE
    return tuple(
        jnp.where(
            (s >= n * i) & (s < n * (i + 1)),
            jnp.int32(1) << jnp.clip(s - n * i, 0, n - 1), 0,
        ).astype(jnp.int32)
        for i in range(len(RBITS_LANES))
    )


def adopt_client_slot(state: SegmentState, new_client_id: int) -> SegmentState:
    """Adopt a new connection's client slot after reconnect.

    Pending rows restamp from the old slot to the new one: client slots
    recycle, and rows that exist only on this replica (unacked local
    inserts / removes) would otherwise satisfy the kernel's own-insert fast
    path (``client == clientn``) or the removers bitmask for the slot's
    NEXT holder — making remote ops resolve positions differently here
    than on every other replica. Shared by every kernel-backed DDS."""
    import jax.numpy as jnp

    from fluidframework_tpu.protocol.constants import UNASSIGNED_SEQ

    pending_ins = state.seq == UNASSIGNED_SEQ
    pending_rem = state.rlseq > 0
    old = writer_bits(state.self_client)
    new = writer_bits(jnp.int32(new_client_id))
    return state._replace(
        client=jnp.where(pending_ins, new_client_id, state.client),
        **{
            k: jnp.where(pending_rem, (lane & ~o) | n, lane)
            for k, lane, o, n in zip(RBITS_LANES, rbits_of(state), old, new)
        },
        self_client=jnp.int32(new_client_id),
    )


def restamp_rows(state: SegmentState, lane: str, rows, value: int) -> SegmentState:
    """Host-side per-row lane restamp (resubmit bookkeeping)."""
    import jax.numpy as jnp

    arr = np.asarray(getattr(state, lane)).copy()
    arr[rows] = value
    return state._replace(**{lane: jnp.asarray(arr)})


def to_host(state: SegmentState) -> "SegmentState":
    """Pull a (single-doc) state to host numpy for materialization/tests."""
    return SegmentState(*[np.asarray(x) for x in state])


def lanes_summary(h: SegmentState) -> dict:
    """One state's rows in use as the summary format's lane lists (what
    ``summarize_core`` of a kernel-backed channel saves and ``load_core``
    restores). ``h`` holds host lanes."""
    n = int(h.count)
    return {
        "lanes": {
            lane: np.asarray(getattr(h, lane))[:n].tolist()
            for lane in SEGMENT_LANES
        },
        "count": n,
        "min_seq": int(h.min_seq),
        "cur_seq": int(h.cur_seq),
    }


def materialize(state: SegmentState, payloads: dict) -> str:
    """Join live, locally-visible rows into the document text.

    Local perspective (reference ``localNetLength`` mergeTree.ts:613): any
    removal — acked or pending — hides the segment.
    """
    h = to_host(state)
    parts = []
    for i in range(int(h.count)):
        if int(h.kind[i]) == KIND_FREE:
            continue
        if int(h.rseq[i]) != RSEQ_NONE:
            continue
        o, f, n = int(h.orig[i]), int(h.off[i]), int(h.length[i])
        parts.append(payloads[o][f : f + n])
    return "".join(parts)
