"""SharedMatrix tests: permutation-vector merge + cell LWW (SURVEY §2.2)."""

import numpy as np
import pytest

from fluidframework_tpu.models.shared_matrix import SharedMatrix
from fluidframework_tpu.runtime.container import ContainerRuntime
from fluidframework_tpu.service.local_server import LocalFluidService


def pair(n=2):
    svc = LocalFluidService()
    return [
        ContainerRuntime(svc, "doc", channels=(SharedMatrix("m"),))
        for _ in range(n)
    ]


def drain(rts):
    busy = True
    while busy:
        busy = any(rt.process_incoming() for rt in rts)


def test_basic_grid_and_cells():
    a, b = pair()
    ma, mb = a.get_channel("m"), b.get_channel("m")
    ma.insert_rows(0, 2)
    ma.insert_cols(0, 3)
    drain([a, b])
    ma.set_cell(0, 0, "x")
    mb.set_cell(1, 2, "y")
    drain([a, b])
    assert ma.to_list() == mb.to_list() == [["x", None, None], [None, None, "y"]]


def test_concurrent_row_insert_converges():
    a, b = pair()
    ma, mb = a.get_channel("m"), b.get_channel("m")
    ma.insert_rows(0, 1)
    ma.insert_cols(0, 1)
    drain([a, b])
    ma.set_cell(0, 0, "base")
    drain([a, b])

    ma.insert_rows(0, 1)  # concurrent inserts at row 0
    mb.insert_rows(0, 1)
    drain([a, b])
    assert ma.row_count == mb.row_count == 3
    assert ma.to_list() == mb.to_list()
    # The original row's cell follows its handle through the reorder.
    rows = ma.to_list()
    assert ["base"] in rows


def test_cells_survive_row_reorder():
    a, b = pair()
    ma, mb = a.get_channel("m"), b.get_channel("m")
    ma.insert_rows(0, 3)
    ma.insert_cols(0, 1)
    drain([a, b])
    for i in range(3):
        ma.set_cell(i, 0, f"r{i}")
    drain([a, b])
    # b inserts rows in the middle while a writes a cell below them.
    mb.insert_rows(1, 2)
    ma.set_cell(2, 0, "updated")
    a.flush()
    b.flush()
    drain([a, b])
    la, lb = ma.to_list(), mb.to_list()
    assert la == lb
    flat = [r[0] for r in la]
    assert flat == ["r0", None, None, "r1", "updated"]


def test_remove_rows_and_cell_gc():
    a, b = pair()
    ma, mb = a.get_channel("m"), b.get_channel("m")
    ma.insert_rows(0, 3)
    ma.insert_cols(0, 2)
    drain([a, b])
    ma.set_cell(1, 0, "gone")
    ma.set_cell(2, 1, "kept")
    drain([a, b])
    mb.remove_rows(1, 1)
    drain([a, b])
    assert ma.row_count == 2
    assert ma.to_list() == mb.to_list()
    assert ma.to_list()[1][1] == "kept"
    summ = ma.summarize_core()
    assert "gone" not in summ["cells"].values()  # unreachable cell GC'd


def test_concurrent_cell_write_lww():
    a, b = pair()
    ma, mb = a.get_channel("m"), b.get_channel("m")
    ma.insert_rows(0, 1)
    ma.insert_cols(0, 1)
    drain([a, b])
    ma.set_cell(0, 0, "A")
    mb.set_cell(0, 0, "B")
    a.flush()
    b.flush()
    drain([a, b])
    assert ma.get_cell(0, 0) == mb.get_cell(0, 0) == "B"


def test_summary_roundtrip():
    a, b = pair()
    ma = a.get_channel("m")
    ma.insert_rows(0, 2)
    ma.insert_cols(0, 2)
    drain([a, b])
    ma.set_cell(0, 1, 42)
    drain([a, b])
    svc2 = LocalFluidService()
    c = ContainerRuntime(svc2, "doc2", channels=(SharedMatrix("m"),))
    mc = c.get_channel("m")
    mc.load_core(ma.summarize_core())
    assert mc.to_list() == ma.to_list()


@pytest.mark.parametrize("seed", range(3))
def test_matrix_farm(seed):
    rng = np.random.default_rng(seed + 500)
    rts = pair(3)
    mats = [rt.get_channel("m") for rt in rts]
    mats[0].insert_rows(0, 2)
    mats[0].insert_cols(0, 2)
    drain(rts)

    for _ in range(60):
        i = int(rng.integers(0, 3))
        rt, m = rts[i], mats[i]
        act = rng.integers(0, 6)
        if act == 0 and m.row_count < 12:
            m.insert_rows(int(rng.integers(0, m.row_count + 1)), 1)
        elif act == 1 and m.col_count < 12:
            m.insert_cols(int(rng.integers(0, m.col_count + 1)), 1)
        elif act == 2 and m.row_count > 1:
            m.remove_rows(int(rng.integers(0, m.row_count)), 1)
        elif act == 3 and m.row_count and m.col_count:
            m.set_cell(
                int(rng.integers(0, m.row_count)),
                int(rng.integers(0, m.col_count)),
                int(rng.integers(0, 100)),
            )
        elif act == 4:
            rt.flush()
        else:
            rt.process_incoming(int(rng.integers(1, 5)))

    drain(rts)
    grids = [m.to_list() for m in mats]
    assert grids[0] == grids[1] == grids[2]


def test_handles_are_pulled_once_per_change_of_an_axis():
    """``set_cell``, ``get_cell`` and ``to_list`` read an axis's handles
    from a copy kept until that axis next changes: one device-to-host pull
    an axis a change, however many cells are touched."""
    a, b = pair()
    ma, mb = a.get_channel("m"), b.get_channel("m")
    ma.insert_rows(0, 4)
    ma.insert_cols(0, 3)
    drain([a, b])

    def pulls(m):
        return m._rows.pulls, m._cols.pulls

    ma.to_list()
    base = pulls(ma)
    for r in range(4):
        for c in range(3):
            ma.set_cell(r, c, r * 3 + c)
            assert ma.get_cell(r, c) == r * 3 + c
    assert ma.to_list()[3] == [9, 10, 11]
    assert ma.row_count == 4 and ma.col_count == 3
    assert pulls(ma) == base  # 12 writes, 12 reads, a grid: no pull
    drain([a, b])  # cell ops leave both axes as they are
    assert pulls(ma) == base
    mb.insert_rows(1, 1)  # a remote change of the row axis alone
    drain([a, b])
    assert ma.to_list()[1] == [None, None, None]
    ma.set_cell(1, 0, "new")
    assert pulls(ma) == (base[0] + 1, base[1])
    ma.remove_cols(0, 1)  # a local change of the column axis
    assert ma.to_list()[0] == [1, 2]
    drain([a, b])
    assert ma.to_list() == mb.to_list()
    # The local op and its ack each replace the column state.
    assert pulls(ma)[0] == base[0] + 1 and pulls(ma)[1] <= base[1] + 2
