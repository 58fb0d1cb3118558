"""SharedMatrix on the served path: both axes are fleet slots, the cells a
host store read as of the gather's sequence number.

What is held here, on seeded streams at a small size, exactly: the served
grid == every client's ``to_list()`` == the plain reference's replay of the
durable log (``benchmark/reference/matrix_replay.py``, which imports
nothing of the program); the kernel rows the service lowers are the rows a
client builds for the same sequenced message; a read that cell writes and
an axis op race is still one cut of the log; ``crash_device`` and
hibernation carry the cells; a summary read loads into a fresh
``SharedMatrix``; a cell dropped under a removed row never comes back.
"""

import asyncio
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import matrix_replay  # noqa: E402
from benchmark.reference.replay import LogOp  # noqa: E402
from fluidframework_tpu.drivers.network_driver import (  # noqa: E402
    NetworkFluidService,
)
from fluidframework_tpu.models.shared_matrix import (  # noqa: E402
    SharedMatrix,
    axis_row_from_wire,
)
from fluidframework_tpu.ops.merge_kernel import jit_apply_ops  # noqa: E402
from fluidframework_tpu.ops.segment_state import (  # noqa: E402
    SEGMENT_LANES,
    make_state,
    to_host,
)
from fluidframework_tpu.protocol.constants import (  # noqa: E402
    ERR_CAPACITY,
    NO_CLIENT,
)
from fluidframework_tpu.protocol.types import MessageType  # noqa: E402
from fluidframework_tpu.runtime.container import ContainerRuntime  # noqa: E402
from fluidframework_tpu.service import device_backend  # noqa: E402
from fluidframework_tpu.service.lambdas import stored_message  # noqa: E402
from fluidframework_tpu.service.matrix_channel import MatrixChannel  # noqa: E402
from fluidframework_tpu.service.network_server import (  # noqa: E402
    FluidNetworkServer,
)
from fluidframework_tpu.service.pipeline import PipelineFluidService  # noqa: E402
from fluidframework_tpu.telemetry import profiler  # noqa: E402

DOC, CH = "table", "m"


def clients(svc, n, doc=DOC):
    return [
        ContainerRuntime(svc, doc, channels=(SharedMatrix(CH),))
        for _ in range(n)
    ]


def drain(rts):
    busy = True
    while busy:
        busy = any(rt.process_incoming() for rt in rts if rt.connected)


def read_log(svc, doc=DOC):
    """(head, [LogOp]) of the durable log, the matrix channel's wire ops
    as contents."""
    head = svc.doc_head(doc)
    ops = []
    for _lo, _hi, obj in svc.log_entries(doc, 1, head):
        msgs = obj.messages() if hasattr(obj, "messages") else [
            stored_message(obj)
        ]
        for m in msgs:
            env = m.contents
            mine = (
                m.type == MessageType.OPERATION and isinstance(env, dict)
                and env.get("address") == CH
            )
            ops.append(LogOp(
                seq=m.sequence_number, ref=m.reference_sequence_number,
                client=m.client_id, csn=m.client_sequence_number,
                msn=m.minimum_sequence_number,
                contents=env["contents"] if mine else None,
            ))
    return head, ops


def edit(rng, m, tick):
    """One user action on a table, by the benchmark's weights (cells 20,
    each structural op 1), steering both axes into a small band."""
    rows, cols = m.row_count, m.col_count
    act = int(rng.integers(0, 24))
    if rows < 2 or (act == 20 and rows < 10):
        m.insert_rows(int(rng.integers(0, rows + 1)), int(rng.integers(1, 3)))
    elif cols < 2 or (act == 21 and cols < 6):
        m.insert_cols(int(rng.integers(0, cols + 1)), 1)
    elif act == 22 and rows > 2:
        at = int(rng.integers(0, rows - 1))
        m.remove_rows(at, int(rng.integers(1, min(2, rows - at) + 1)))
    elif act == 23 and cols > 2:
        m.remove_cols(int(rng.integers(0, cols)), 1)
    else:
        m.set_cell(
            int(rng.integers(0, rows)), int(rng.integers(0, cols)), tick
        )


def farm(seed, writers, steps, reconnects):
    rng = np.random.default_rng([seed, writers])
    svc = PipelineFluidService()
    rts = clients(svc, writers)
    ms = [rt.get_channel(CH) for rt in rts]
    ms[0].insert_rows(0, 4)
    ms[0].insert_cols(0, 3)
    drain(rts)
    for tick in range(steps):
        i = int(rng.integers(0, writers))
        rt, m = rts[i], ms[i]
        act = int(rng.integers(0, 10))
        if act < 5:
            edit(rng, m, tick)
        elif act < 7 and rt.connected:
            rt.flush()
        elif act < 9 and rt.connected:
            # A few of what has arrived: the others' refSeqs stay stale.
            rt.process_incoming(int(rng.integers(1, 4)))
        elif reconnects and rt.connected and sum(r.connected for r in rts) > 1:
            rt.disconnect()
        elif reconnects and not rt.connected:
            rt.reconnect()
    for rt in rts:
        if not rt.connected:
            rt.reconnect()
    drain(rts)
    return svc, rts, ms


@pytest.mark.parametrize(
    "seed,writers,reconnects",
    [(1, 2, False), (2, 3, False), (3, 4, False), (4, 8, False),
     (5, 3, True), (6, 4, True)],
)
def test_served_grid_is_every_clients_grid_and_the_logs_replay(
    seed, writers, reconnects
):
    svc, rts, ms = farm(seed, writers, 220, reconnects)
    served = svc.device_grid(DOC, CH)
    grids = [m.to_list() for m in ms]
    assert all(g == grids[0] for g in grids), "clients diverged"
    assert served == grids[0]
    head, log = read_log(svc)
    want, acked, applied = matrix_replay.replay(log, head)
    assert served == want
    assert not any(rt.pending for rt in rts)
    stats = svc.stats()
    assert stats["matrix_axis_ops"] + stats["matrix_cell_ops"] == applied
    assert stats["matrix_axis_ops"] > 0 and stats["matrix_cell_ops"] > 0
    assert svc.device.stats()["docs_with_errors"] == 0
    # Both axes are fleet slots of the one fleet the text channels use.
    rows, cols = svc.device._matrix[(DOC, CH)].axes
    assert {rows, cols} <= set(svc.device._index)
    assert svc.device.channels() == [(DOC, CH)]


def test_lowered_axis_rows_are_the_clients_rows_bit_for_bit():
    """The rows the service buffers for the axis slots are the rows a
    remote client builds for the same sequenced messages, and the fleet's
    lanes equal ``jit_apply_ops`` over those rows."""
    svc, rts, ms = farm(7, 3, 160, False)
    head, log = read_log(svc)
    built = {"row": [], "col": []}
    for op in log:
        c = op.contents
        if c is not None and c["k"] != "cell":
            built[c["k"][3:]].append(axis_row_from_wire(
                c, seq=op.seq, ref=op.ref, client=op.client, msn=op.msn
            ))
    # What the service lowers: a backend that never compacts (so that its
    # lanes can be held to a plain run of the kernel) is fed the log, and
    # every row it buffers is recorded.
    dev = device_backend.DeviceFleetBackend(compact_every=10**9)
    seen = {"row": [], "col": []}
    inner = dev.enqueue

    def record(doc_id, address, row):
        seen["row" if address.endswith("#rows") else "col"].append(row.copy())
        inner(doc_id, address, row)

    dev.enqueue = record
    for op in log:
        if op.contents is not None:
            dev.enqueue_matrix(
                DOC, CH, op.contents,
                seq=op.seq, ref=op.ref, client=op.client, msn=op.msn,
            )
    dev.flush()
    for axis in ("row", "col"):
        assert len(seen[axis]) == len(built[axis]) > 0
        assert np.array_equal(np.stack(seen[axis]), np.stack(built[axis]))
    read = dev.doc_states([(DOC, CH)])[(DOC, CH)]
    assert dev.grid_from_state((DOC, CH), read) == ms[0].to_list()
    for axis, got in (("row", read.rows), ("col", read.cols)):
        ref = make_state(128, NO_CLIENT)
        for row in built[axis]:  # one row a call, as a client applies them
            ref = jit_apply_ops(ref, row[None, :].astype(np.int32))
        ref = to_host(ref)
        n = int(ref.count)
        assert int(got.count) == n > 0
        for lane in SEGMENT_LANES:
            assert np.array_equal(
                np.asarray(getattr(got, lane))[:n],
                np.asarray(getattr(ref, lane))[:n],
            ), (axis, lane)
        assert int(got.min_seq) == int(ref.min_seq)
        assert int(got.cur_seq) == int(ref.cur_seq)


def test_crash_device_rebuilds_axes_and_cells():
    svc, rts, ms = farm(8, 4, 200, False)
    before = svc.device_grid(DOC, CH)
    live = svc.stats()["matrix_cells_live"]
    assert live > 0
    svc.crash_device()
    assert svc.stats()["matrix_cells_live"] == 0
    svc.pump()
    assert svc.device_grid(DOC, CH) == before == ms[0].to_list()
    assert svc.stats()["matrix_cells_live"] >= live


def test_summary_read_loads_into_a_fresh_client():
    svc, rts, ms = farm(9, 3, 200, False)
    summary = json.loads(json.dumps(svc.device_summary(DOC, CH)))
    assert set(summary) == {"rows", "cols", "cells"}
    fresh = ContainerRuntime(
        PipelineFluidService(), "other", channels=(SharedMatrix(CH),)
    ).get_channel(CH)
    fresh.load_core(summary)
    assert fresh.to_list() == ms[0].to_list() == svc.device_grid(DOC, CH)
    # The client's own summary holds the same reachable cells.
    assert summary["cells"] == ms[0].summarize_core()["cells"]


def test_cells_of_a_removed_row_are_dropped_and_never_resurface():
    svc = PipelineFluidService()
    a, b = clients(svc, 2)
    ma, mb = a.get_channel(CH), b.get_channel(CH)
    ma.insert_rows(0, 3)
    ma.insert_cols(0, 2)
    drain([a, b])
    for r in range(3):
        for c in range(2):
            ma.set_cell(r, c, f"{r}.{c}")
    drain([a, b])
    assert svc.stats()["matrix_cells_live"] == 6
    # b removes the middle row while a, not having seen it, writes into it.
    mb.remove_rows(1, 1)
    b.flush()
    ma.set_cell(1, 0, "late")
    a.flush()
    drain([a, b])
    want = [["0.0", "0.1"], ["2.0", "2.1"]]
    assert svc.device_grid(DOC, CH) == ma.to_list() == mb.to_list() == want
    # Until the MSN passes the removal the store keeps the row's cells;
    # then the next gather drops them.
    for _ in range(3):
        ma.set_cell(0, 0, "0.0")
        mb.insert_rows(2, 1)
        mb.remove_rows(2, 1)
        drain([a, b])
    assert svc.device_grid(DOC, CH) == want
    stats = svc.stats()
    assert stats["matrix_cells_dropped"] == 2
    assert stats["matrix_cells_live"] == 4
    store = svc.device._matrix[(DOC, CH)].cells
    assert len(store) == 4 and "late" not in store.values()
    # Nothing brings them back: not further edits, not a replay.
    ma.insert_rows(1, 1)
    drain([a, b])
    assert svc.device_grid(DOC, CH) == [
        ["0.0", "0.1"], [None, None], ["2.0", "2.1"],
    ]
    svc.crash_device()
    svc.pump()
    assert svc.device_grid(DOC, CH) == ma.to_list()
    assert svc.device_grid(DOC, CH) == ma.to_list()  # the second drops
    assert "late" not in svc.device._matrix[(DOC, CH)].cells.values()
    head, log = read_log(svc)
    assert matrix_replay.replay(log, head)[0] == ma.to_list()


def churn_rows(ma, mb, settle, sweep=lambda k: None):
    """``TABLE_SWEEP_REMOVALS`` times: ``ma`` appends a row and fills its
    two cells, ``mb`` removes it. Nobody reads the table."""
    n = device_backend.TABLE_SWEEP_REMOVALS
    for k in range(n):
        ma.insert_rows(2, 1)
        ma.set_cell(2, 0, f"dead{k}")
        ma.set_cell(2, 1, f"dead{k}")
        settle()
        mb.remove_rows(2, 1)
        settle()
        sweep(k)
    return n


def test_a_table_written_and_never_read_drops_its_removed_rows_cells():
    """No grid read, no axis op after the last removal: the backend asks
    for a gather of its own once the table took TABLE_SWEEP_REMOVALS
    removals, and the MSN that CELL writes carry lets the last removal
    pass (an axis state's own ``min_seq`` moves with axis ops alone)."""
    svc = PipelineFluidService()
    a, b = clients(svc, 2)
    ma, mb = a.get_channel(CH), b.get_channel(CH)
    ma.insert_rows(0, 2)
    ma.insert_cols(0, 2)
    drain([a, b])
    ma.set_cell(0, 0, "kept")
    drain([a, b])
    swept = []
    n = churn_rows(
        ma, mb, lambda: drain([a, b]),
        lambda k: swept.append(svc.table_sweep()),
    )
    # Due at the last removal and not before; the gather ends it.
    assert swept == [0] * (n - 1) + [1]
    assert svc.device.tables_due() == [] and svc.table_sweep() == 0
    stats = svc.stats()
    # Every removal the MSN had passed at the gather: all but the last.
    assert stats["matrix_cells_dropped"] == 2 * (n - 1)
    assert stats["matrix_cells_live"] == 3
    assert stats["matrix_reads"] == 0
    # Cell writes alone carry the MSN past the last removal; the next
    # gather, here a summary's, drops that row's cells too.
    for k in range(2):
        ma.set_cell(0, 1, f"a{k}")
        mb.set_cell(1, 0, f"b{k}")
        drain([a, b])
    summary = svc.device_summary(DOC, CH)
    assert sorted(summary["cells"].values()) == ["a1", "b1", "kept"]
    stats = svc.stats()
    assert stats["matrix_cells_dropped"] == 2 * n
    assert stats["matrix_cells_live"] == 3 == len(
        svc.device._matrix[(DOC, CH)].cells
    )
    assert stats["matrix_reads"] == 0
    assert ma.to_list() == mb.to_list() == [["kept", "a1"], ["b1", None]]
    head, log = read_log(svc)
    assert matrix_replay.replay(log, head)[0] == ma.to_list()


def test_hibernation_and_wake_carry_the_cells():
    svc = PipelineFluidService()
    a, b = clients(svc, 2)
    ma = a.get_channel(CH)
    ma.insert_rows(0, 2)
    ma.insert_cols(0, 2)
    drain([a, b])
    ma.set_cell(0, 1, "kept")
    drain([a, b])
    want = ma.to_list()
    a.disconnect()
    b.disconnect()
    svc.pump()
    svc.flush_device()
    rm = svc.device.residency
    for _ in range(40):
        if svc.hibernate_sweep(max_docs=8):
            break
    assert rm.is_cold(DOC)
    durable = svc.read_tier.latest.latest_summary(DOC)
    assert durable["channels"][CH]["cells"], "the durable summary has the cells"
    # Served from the cold record without a wake, then woken by a write.
    assert svc.device.grid(DOC, CH) == want
    a.reconnect()
    ma.set_cell(1, 0, "after")
    ma.insert_rows(2, 1)
    drain([a])
    assert not rm.is_cold(DOC)
    assert svc.device_grid(DOC, CH) == ma.to_list()
    assert ma.to_list()[0][1] == "kept" and ma.to_list()[1][0] == "after"


def test_an_axis_error_nacks_the_document():
    """An axis that outgrows the largest tier trips its slot's err lane;
    the table's document is nacked as a text channel's is."""
    from fluidframework_tpu.service.device_backend import DeviceFleetBackend

    dev = DeviceFleetBackend(capacity=16, max_capacity=16)
    seq = 0
    for k in range(40):
        seq += 1
        dev.enqueue_matrix(
            DOC, CH, {"k": "insrow", "pos": 0, "count": 1, "orig": 100 + k},
            seq=seq, ref=seq - 1, client=0, msn=0,
        )
    dev.flush()
    dev.collect_now()
    assert dev.take_errors() == [(DOC, CH)]
    idx = dev._index[dev._matrix[(DOC, CH)].axes[0]]
    assert int(dev._doc_state(idx).err) & ERR_CAPACITY


# -- the read that is one cut ---------------------------------------------------


class Served:
    """A started network server over a pipeline service, three websocket
    writers of one table, and a hook that holds a read's device→host
    transfer open in the executor."""

    def __init__(self):
        self.svc = PipelineFluidService()
        self.srv = FluidNetworkServer(self.svc)
        self.srv.start()
        net = NetworkFluidService("127.0.0.1", self.srv.port)
        self.rts = [
            ContainerRuntime(net, DOC, channels=(SharedMatrix(CH),))
            for _ in range(3)
        ]
        self.ms = [rt.get_channel(CH) for rt in self.rts]
        self.entered, self.release = threading.Event(), threading.Event()
        self.hold = False
        inner = FluidNetworkServer._read_transfer

        def held(dev, dev_vec):
            if self.hold:
                self.hold = False
                self.entered.set()
                assert self.release.wait(30)
            return inner(dev, dev_vec)

        self.srv._read_transfer = held

    def settle(self, want_rows=None):
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            for rt in self.rts:
                rt.process_incoming()
            if not any(rt.pending for rt in self.rts) and len(
                {rt.ref_seq for rt in self.rts}
            ) == 1 and (
                want_rows is None
                or all(m.row_count == want_rows for m in self.ms)
            ):
                return
            time.sleep(0.01)
        raise AssertionError("the writers did not settle")

    def get(self, view=None):
        q = f"?view={view}" if view else ""
        url = f"http://127.0.0.1:{self.srv.port}/documents/{DOC}/channels/{CH}{q}"
        with urllib.request.urlopen(url, timeout=30) as r:
            return json.loads(r.read())

    def raced_read(self):
        """A grid read whose transfer is held open while a row is removed
        and a cell of another row is overwritten; the reply."""
        box = []
        self.hold = True
        t = threading.Thread(target=lambda: box.append(self.get()["grid"]))
        t.start()
        assert self.entered.wait(30)
        self.ms[0].remove_rows(0, 1)
        self.rts[0].flush()
        self.settle(want_rows=2)
        self.ms[1].set_cell(0, 0, "raced")
        self.rts[1].flush()
        self.settle()
        self.release.set()
        t.join(30)
        return box[0]

    def history(self):
        fut = asyncio.run_coroutine_threadsafe(
            self._log(), self.srv._loop
        )
        head, log = fut.result(30)
        return matrix_replay.replay(log, head, every=True)[0]

    async def _log(self):
        return read_log(self.svc)

    def close(self):
        for rt in self.rts:
            rt.disconnect()
        self.srv.stop()


@pytest.fixture
def served():
    s = Served()
    s.ms[0].insert_rows(0, 3)
    s.ms[0].insert_cols(0, 2)
    s.rts[0].flush()
    s.settle(want_rows=3)
    for r in range(3):
        s.ms[r].set_cell(r, 0, f"r{r}")
        s.rts[r].flush()
    s.settle()
    yield s
    s.close()


def test_a_read_raced_by_an_axis_op_and_a_cell_write_is_one_cut(served):
    before = served.get()["grid"]
    assert before == [["r0", None], ["r1", None], ["r2", None]]
    reads0 = served.svc.stats()["matrix_reads"]
    reply = served.raced_read()
    hist = served.history()
    # The cut the gather took: every op acknowledged before the read was
    # asked for, nothing of what was sequenced under the open transfer.
    assert reply == before
    assert reply in hist.grids
    after = served.get()["grid"]
    assert after == [["raced", None], ["r2", None]] == hist.grids[-1]
    assert served.svc.stats()["matrix_reads"] == reads0 + 2
    assert profiler.totals()["matrix_read"][0] >= 2
    assert profiler.totals()["matrix_stage"][0] >= 7
    # The summary view through the same entry loads into a fresh client.
    fresh = ContainerRuntime(
        PipelineFluidService(), "other", channels=(SharedMatrix(CH),)
    ).get_channel(CH)
    fresh.load_core(served.get(view="summary"))
    assert fresh.to_list() == after


def test_the_raced_read_fails_on_a_store_read_live(served, monkeypatch):
    """The same race against a store that hands the gather its LIVE cells
    (no cut): the reply joins the old axes with the newer cells, which is
    no prefix of the log. This is what the comparison has to catch."""
    monkeypatch.setattr(
        MatrixChannel, "lend", lambda self: (self.cells, self.seq, self.msn)
    )
    reply = served.raced_read()
    hist = served.history()
    assert reply == [["r0", None], ["raced", None], ["r2", None]]
    assert reply not in hist.grids
    # And the reference can make that grid itself: the control.
    n = len(hist.grids) - 1
    assert reply == hist.skew(n - 2, n)


def test_the_ticker_sweeps_a_table_nobody_reads(served):
    """Over the wire, with no GET at all: the deadline ticker gathers the
    table once it is due and the removed rows' cells leave the store."""
    svc = served.svc
    dropped0 = svc.stats()["matrix_cells_dropped"]
    n = churn_rows(served.ms[0], served.ms[1], lambda: (
        served.rts[0].flush(), served.rts[1].flush(), served.settle(),
    ))
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and (
        svc.stats()["matrix_cells_dropped"] == dropped0
    ):
        served.settle()
        time.sleep(0.02)
    stats = svc.stats()
    # The rows whose removal the MSN had passed at the gather (the third
    # writer sends nothing, so the MSN trails by a few).
    assert 0 < stats["matrix_cells_dropped"] - dropped0 <= 2 * n
    assert stats["matrix_cells_dropped"] + stats["matrix_cells_live"] == (
        3 + 2 * n
    )
    assert stats["matrix_reads"] == 0
    assert svc.device.tables_due() == []
    assert served.get()["grid"] == served.ms[2].to_list()
