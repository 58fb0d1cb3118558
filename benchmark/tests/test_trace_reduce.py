"""The reduction from a profiler trace to busy, idle and operation times,
checked on hand-made events and on a small trace recorded on the chip
(0.3 s of `h100k-ingest-zipf`, a TPU v5 lite, PR 26; made with
``tools/trace_slice.py`` after a ``--trace 1`` run)."""

import os

import numpy as np

from benchmark import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV, OPS = "/device:TPU:0", "XLA Ops"


def ev(name, start, dur, plane=DEV, line=OPS):
    return T.Event(plane, line, name, start, dur)


def test_union_merges_overlap_and_touching():
    assert T.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_self_time_charges_a_parent_only_what_children_leave():
    events = [ev("loop", 0, 100), ev("body", 10, 30), ev("body", 50, 30), ev("tail", 120, 10)]
    own = T.self_times(events)
    assert own == {"loop": 40e-9, "body": 60e-9, "tail": 10e-9}


def test_busy_idle_and_gaps_on_hand_made_events():
    host = "/host:CPU"
    events = [
        ev("k", 1000, 200), ev("k", 1100, 300),       # overlap: busy 1000..1400
        ev("k", 2000, 500),                           # busy 2000..2500
        ev("other-line", 0, 5000, line="XLA Modules"),  # not an operation line
        T.Event(host, "python3", "bench.wait_front_door", 1350, 700),
        T.Event(host, "python3", "outer", 0, 5000),
    ]
    r = T.reduce(events)
    assert r["devices"] == 1
    assert r["busy_s"] == (400 + 500) / 1e9
    assert r["window_s"] == 5000 / 1e9
    # Own time: the second "k" starts inside the first, which is charged
    # only the 100 ns before it; the sum is the busy time again.
    assert r["ops"] == {"k": 900 / 1e9} and r["op_counts"] == {"k": 3}
    gaps = dict(map(tuple, r["idle_gaps"]))
    # 1400..2000 lies inside the tight host span; the rest only in "outer".
    assert gaps["bench.wait_front_door"] == 600 / 1e9
    assert abs(gaps["outer"] - (1000 + 2500) / 1e9) < 1e-12


def test_recorded_chip_trace():
    events = T.load_json(os.path.join(DATA, "trace_ingest_slice.json.gz"))
    r = T.reduce(events)
    assert r["devices"] == 1
    # Busy time again, the slow way: a microsecond timeline.
    on = [e for e in events if e.plane == DEV and e.line == OPS and e.dur_ns > 0]
    t0 = min(e.start_ns for e in events if e.dur_ns > 0)
    t1 = max(e.start_ns + e.dur_ns for e in events if e.dur_ns > 0)
    line = np.zeros((t1 - t0) // 1000 + 2, bool)
    for e in on:
        line[(e.start_ns - t0) // 1000:(e.start_ns + e.dur_ns - t0) // 1000 + 1] = True
    assert abs(r["busy_s"] - line.sum() / 1e6) < 0.001 * len(on) / 1e3 + 1e-3
    assert abs(r["window_s"] - (t1 - t0) / 1e9) < 1e-9
    idle_share = 1 - r["busy_s"] / r["window_s"]
    assert 0.0 < idle_share < 1.0
    # The merge-apply kernel is there under the name the trace shows today,
    # and it is where the device's time goes in this cell.
    top_name, top_s = r["device_ops"][0]
    assert top_name.startswith("apply_ops_packed")
    assert top_s > 0.5 * r["busy_s"]
    assert abs(sum(r["ops"].values()) - r["busy_s"]) < 0.02 * r["busy_s"]
