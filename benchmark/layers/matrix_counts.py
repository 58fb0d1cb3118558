"""What the readers of a table deployment share: the pipeline's always-on
counts of its matrix channels (``PipelineFluidService.stats()``:
``matrix_axis_ops``, axis ops lowered to kernel rows; ``matrix_cell_ops``,
cell writes taken into the host store; ``matrix_cells_live``, cells the
stores hold; ``matrix_cells_dropped``, cells dropped under a removed row or
column; ``matrix_reads``, grids joined) as numbers a window delta can
subtract, beside the lane totals. A program without them (the parent of
the PR that added them) gives no keys, and every reader of them reads
nothing."""

from benchmark.layers import lanes

STATS = (
    "matrix_axis_ops", "matrix_cell_ops", "matrix_cells_live",
    "matrix_cells_dropped", "matrix_reads",
)


def snapshot(srv) -> dict:
    out = lanes.snapshot(srv)
    stats = getattr(srv.service, "stats", None)
    counts = stats() if stats is not None else {}
    if all(k in counts for k in STATS):
        out.update({f"matrix.{k}": counts[k] for k in STATS})
    return out
