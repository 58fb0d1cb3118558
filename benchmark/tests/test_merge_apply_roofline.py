"""The merge kernel's share of the memory roofline (PR 28), on hand-made
contexts: the share is of what the boxcar's busy documents need moved,
not of what a whole-pool step sweeps, so a step that mends ROADMAP S2
still reads under 100%.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_merge_apply_roofline.py -q

The times here are made up or copied from the ledger (PR 27's line):
nothing in this file is measured.
"""

import types

import pytest

from benchmark.layers import merge_apply_roofline as R

HBM = 819e9  # peaks.json, TPU v5 lite
BASE_POOL = (131_072, 128)  # h100k-ingest-zipf
MIX = {"frames_per_batch": 128, "ops_per_frame": 4}


def context(kernel_ms, calls=42, dispatches=42, rows_per_dispatch=512,
            kind="TPU v5 lite", platform="tpu", rehearsal=False, params=MIX,
            kernel="apply_ops_packed.1"):
    said = []
    ctx = types.SimpleNamespace(
        out=types.SimpleNamespace(
            device={"kind": kind, "platform": platform, "count": 1},
            say=lambda event, **kv: said.append((event, kv)),
        ),
        rehearsal=rehearsal, params=dict(params), base_pool=BASE_POOL,
        trace={
            "ops": {kernel: calls * kernel_ms / 1e3, "slice_bitcast_fusion": 0.1},
            "op_counts": {kernel: calls, "slice_bitcast_fusion": calls},
            "counters": {
                "real_rows": dispatches * rows_per_dispatch,
                "pump_dispatches": dispatches,
            },
        },
    )
    return ctx, said


def old_count(kernel_ms: float) -> float:
    """What the reader gave until PR 28: the whole base pool in and out."""
    slots, capacity = BASE_POOL
    return 100.0 * (slots * capacity * 15 * 4 * 2 / HBM) / (kernel_ms / 1e3)


def test_todays_whole_pool_step_reads_thousandths_of_a_per_cent():
    ctx, said = context(68.31)  # the ledger's kernel time (PR 27)
    share = R.read(ctx)
    assert R.step_bytes(128, 128) == 1_966_080
    assert share == pytest.approx(0.0035, rel=0.02)  # 2.40 us of 68.31 ms
    assert old_count(68.31) == pytest.approx(3.5985, rel=1e-4)  # the ledger's
    (event, line), = said
    assert event == "merge_apply_roofline" and line["read"] == share
    assert line["busy_documents_per_dispatch"] == 128
    assert line["least_bytes"] == 1_966_080 and line["kernel_calls"] == 42
    assert line["mean_kernel_ms"] == pytest.approx(68.31)
    assert line["base_pool_slots"] == 131_072 and line["dispatches"] == 42
    # By hand from the line, as a reader of a run's output would.
    assert share == pytest.approx(
        100 * line["least_bytes"] / line["hbm_bytes_per_s"]
        / (line["mean_kernel_ms"] / 1e3)
    )


def test_a_busy_set_step_of_a_millisecond_stays_a_share():
    ctx, _ = context(1.0)
    assert R.read(ctx) == pytest.approx(0.24, rel=0.01)
    assert old_count(1.0) == pytest.approx(246, rel=0.01)


@pytest.mark.parametrize("kernel_us", [2.5, 10.0, 70.0, 2_100.0, 2_340.0])
def test_the_count_stays_under_100_where_the_old_one_passed_105(kernel_us):
    """At 128 busy documents on the 131,072 x 128 base pool: under 100%
    for any kernel time above 2.4 us; the old count passed the driver's
    105% for every time under 2.34 ms (a grid that skips op-free blocks
    of 32 documents, about 2.1 ms, read 117%)."""
    ctx, _ = context(kernel_us / 1e3)
    share = R.read(ctx)
    assert 0 < share < 100
    assert share == pytest.approx(100 * 2.4006 / kernel_us, rel=1e-3)
    if kernel_us < 2_340.0:
        assert old_count(kernel_us / 1e3) > 105
    assert old_count(2.1) == pytest.approx(117, abs=1)


@pytest.mark.parametrize("case, kw, why", [
    ("two batches a boxcar", {"rows_per_dispatch": 1024}, "not one batch"),
    ("half a batch a boxcar", {"rows_per_dispatch": 256}, "not one batch"),
    ("no dispatch traced", {"dispatches": 0}, "no dispatch"),
    ("a mix without frames", {"params": {"rate": 80}}, "not batches"),
    ("a mix without batches", {"params": {"ops_per_frame": 4}}, "not batches"),
])
def test_a_boxcar_that_is_not_one_batch_reads_nothing_and_says_why(case, kw, why):
    ctx, said = context(68.31, **kw)
    assert R.read(ctx) is None, case
    (event, line), = said
    assert event == "merge_apply_roofline" and line["read"] is None
    assert why in line["why"], case


def test_a_program_without_the_counter_reads_nothing():
    ctx, said = context(68.31)
    del ctx.trace["counters"]["real_rows"]
    assert R.read(ctx) is None and "no real_rows" in said[0][1]["why"]
    srv = types.SimpleNamespace(service=types.SimpleNamespace(
        device=types.SimpleNamespace(flush_totals={}, pump_dispatches=3)
    ))
    assert R.snapshot(srv) == {}
    srv.service.device.flush_totals["real_rows"] = 9
    assert R.snapshot(srv) == {"real_rows": 9, "pump_dispatches": 3}


def test_an_unknown_device_kind_is_an_error_and_a_cpu_rehearsal_reads_nothing():
    ctx, _ = context(68.31, kind="TPU v9 imagined")
    with pytest.raises(KeyError, match="TPU v9 imagined"):
        R.read(ctx)
    ctx, said = context(68.31, kind="cpu", platform="cpu", rehearsal=True)
    assert R.read(ctx) is None and not said


def test_the_kernel_is_found_by_its_name_alone():
    for name, found in [("apply_ops_packed", True), ("apply_ops_packed.7", True),
                        ("apply_ops_packed_v2", False), ("compact_packed.1", False),
                        ("fused_apply_ops_packed.1", False)]:
        ctx, _ = context(68.31, kernel=name)
        assert (R.read(ctx) is not None) == found, name
