"""Tier-1 runs the benchmark's own tests: the cases of
``benchmark/tests/test_delivery_encode_share.py`` (PR 37: the reader of the
delivery sweep's encode count), which stays where it is (``pytest
benchmark/tests`` runs them too).

Every case runs as it stands there but one. The benchmark's
``test_both_cells_that_serve_websockets_list_the_metric`` ends by asserting
that the metric's two entries are the LAST two of ``BENCHMARK.json``'s
``per_layer``: true of the list as PR 37 left it, and of no list a later PR
has appended to (new entries go at the end of their lists; one put in the
middle reads as an edit of what was there, and no PR but a ``benchmark``
one may edit a file under ``benchmark/``). Here that case runs unedited
against the list as PR 37 left it for the cells it speaks of: ``per_layer``
without the entries that only cells added since PR 37 report, and without
those of a reader this file does not know (PR 45's ``deliveries_per_write``
is reported by PR 37's own cells; a later reader is left out the same way,
with no edit here).
"""

import json
import types

from benchmark.tests import test_delivery_encode_share as cases
from benchmark.tests.test_delivery_encode_share import *  # noqa: F401,F403

#: The cells ``BENCHMARK.json`` held when PR 37 wrote the case.
CELLS_AT_PR_37 = {"h100k-ingest-zipf", "p12k5-ws-edit", "tsl120-ws-meeting"}
#: The readers (a metric's name before the first dot) it held then.
READERS_AT_PR_37 = {
    "ack_p50_ms", "deli_host_ms", "deliveries_per_message",
    "delivery_encode_share", "device_step_ms", "flush_host_ms", "gc_pause_ms",
    "gen_late_p95_ms", "loop_blocked_share", "loop_wait_ms",
    "merge_apply_roofline", "msn_lag_ops", "noop_share", "pipeline_host_ms",
    "read_host_ms", "read_p50_ms", "read_p95_ms", "read_queue_ms",
    "read_transfer_ms", "reads_per_gather", "real_rows_per_dispatch",
    "rows_per_dispatch", "setup_aot_build_s", "setup_pipeline_s",
    "socket_out_ms", "step_glue_ms", "writer_slots_peak",
}


def test_both_cells_that_serve_websockets_list_the_metric(monkeypatch):
    def load(f):
        bench = json.load(f)
        later = [
            m["name"] for m in bench["per_layer"]
            if not CELLS_AT_PR_37 & set(m.get("workloads", CELLS_AT_PR_37))
            or m["name"].split(".", 1)[0] not in READERS_AT_PR_37
        ]
        # What is left out is a tail: entries appended after PR 37's two.
        kept = len(bench["per_layer"]) - len(later)
        assert later == [m["name"] for m in bench["per_layer"][kept:]]
        bench["per_layer"] = bench["per_layer"][:kept]
        return bench

    monkeypatch.setattr(cases, "json", types.SimpleNamespace(load=load))
    cases.test_both_cells_that_serve_websockets_list_the_metric()
