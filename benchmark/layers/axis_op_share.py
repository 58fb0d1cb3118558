"""Front door + pipeline stages, stage ``device_stage``, matrix channels:
the share of the window's matrix ops that reach the kernel, in percent:
axis ops (``matrix_axis_ops``: row and column inserts and removes, each one
kernel row on its axis's fleet slot) of axis ops and cell writes
(``matrix_cell_ops``, which stay on the host). Also says one ``matrix``
line with the counts, the cells live at the window's end and the cells
dropped in it."""

from benchmark.layers import matrix_counts

snapshot = matrix_counts.snapshot


def read(ctx):
    w = ctx.window
    if "matrix.matrix_axis_ops" not in w:
        return None
    axis, cell = w["matrix.matrix_axis_ops"], w["matrix.matrix_cell_ops"]
    if axis + cell <= 0:
        return None
    ctx.out.say(
        "matrix", axis_ops=axis, cell_ops=cell,
        cells_live_delta=w["matrix.matrix_cells_live"],
        cells_dropped=w["matrix.matrix_cells_dropped"],
        grids_joined=w["matrix.matrix_reads"],
    )
    return 100.0 * axis / (axis + cell)
