"""Snapshot/back-compat golden tests (SURVEY.md §4.8).

Reference: ``packages/test/snapshots`` replays stored op logs and validates
the generated summaries against golden files per format version
(``validateSnapshots.ts``). Here: a canonical deterministic session's op
log and its summary are committed under ``tests/goldens/``; every build
must (a) replay the log to the same observable state and (b) produce a
byte-identical summary, so any unnoticed format/semantic drift fails.

Regenerate (after an INTENTIONAL format change):
    python tests/test_snapshot_goldens.py regenerate
"""

import json
import os

import pytest

from fluidframework_tpu.models.shared_map import SharedMap
from fluidframework_tpu.models.shared_string import SharedString
from fluidframework_tpu.runtime.container import ContainerRuntime
from fluidframework_tpu.service.local_server import LocalFluidService

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "goldens")


def canonical_session(svc: LocalFluidService) -> ContainerRuntime:
    """A deterministic multi-op session exercising inserts, removes,
    annotates, maps, quorum and summary-relevant state."""
    a = ContainerRuntime(
        svc, "golden", channels=(SharedString("text"), SharedMap("map"))
    )
    b = ContainerRuntime(
        svc, "golden", channels=(SharedString("text"), SharedMap("map"))
    )

    def drain():
        for rt in (a, b):
            rt.flush()
        busy = True
        while busy:
            busy = any(rt.process_incoming() for rt in (a, b))

    sa, sb = a.get_channel("text"), b.get_channel("text")
    sa.insert_text(0, "hello world")
    drain()
    sb.insert_text(5, ",")
    sa.remove_range(0, 1)
    drain()
    sa.insert_text(0, "H")
    sa.annotate(0, 5, 3)
    a.get_channel("map").set("title", "golden doc")
    b.get_channel("map").set("count", 42)
    drain()
    b.get_channel("map").delete("count")
    sb.remove_range(5, 6)
    drain()
    b.disconnect()
    a.send_noop()
    a.process_incoming()
    return a


def generate():
    svc = LocalFluidService()
    a = canonical_session(svc)
    ops = [
        json.loads(
            json.dumps(
                {
                    "seq": m.sequence_number,
                    "cid": m.client_id,
                    "cseq": m.client_sequence_number,
                    "ref": m.reference_sequence_number,
                    "msn": m.minimum_sequence_number,
                    "type": int(m.type),
                    "contents": m.contents,
                },
                sort_keys=True,
            )
        )
        for m in svc._doc("golden").op_log
    ]
    summary = a.summarize()
    text = a.get_channel("text").get_text()
    annos = a.get_channel("text").annotations()
    return {
        "ops": ops,
        "summary": summary,
        "text": text,
        "annotations": annos,
    }


def test_canonical_session_matches_golden():
    with open(os.path.join(GOLDEN_DIR, "golden_session.json")) as f:
        golden = json.load(f)
    got = json.loads(json.dumps(generate(), sort_keys=True))
    want = json.loads(json.dumps(golden, sort_keys=True))
    assert got["text"] == want["text"], "replayed text drifted"
    assert got["annotations"] == want["annotations"]
    assert got["ops"] == want["ops"], (
        "sequenced op stream drifted — protocol/semantic change; regenerate "
        "goldens ONLY if intentional"
    )
    assert got["summary"] == want["summary"], (
        "summary format drifted — breaks loading old documents; regenerate "
        "goldens ONLY if intentional"
    )


def test_golden_summary_still_loads():
    """A summary produced by the golden format must load into a live
    container (back-compat with stored documents)."""
    with open(os.path.join(GOLDEN_DIR, "golden_session.json")) as f:
        golden = json.load(f)
    svc = LocalFluidService()
    handle = svc.store.put_summary(golden["summary"])
    doc = svc._doc("golden2")
    doc.latest_summary = (handle, golden["summary"]["sequence_number"])
    doc.sequencer.seq = golden["summary"]["sequence_number"]
    late = ContainerRuntime(
        svc, "golden2", channels=(SharedString("text"), SharedMap("map"))
    )
    assert late.get_channel("text").get_text() == golden["text"]
    assert late.get_channel("map").get("title") == "golden doc"


if __name__ == "__main__":
    import sys

    if len(sys.argv) > 1 and sys.argv[1] == "regenerate":
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        with open(os.path.join(GOLDEN_DIR, "golden_session.json"), "w") as f:
            json.dump(generate(), f, sort_keys=True, indent=1)
        print("goldens regenerated")


def test_r1_format_summary_still_loads():
    """Round-1 summaries (single removers-bitmask lane, no rbits2) must
    keep loading after the writer-mask widening: load_core leaves missing
    lanes at their empty defaults."""
    with open(os.path.join(GOLDEN_DIR, "golden_session_r1.json")) as f:
        golden = json.load(f)
    assert "rbits2" not in golden["summary"]["channels"]["text"]["lanes"]
    svc = LocalFluidService()
    handle = svc.store.put_summary(golden["summary"])
    doc = svc._doc("golden3")
    doc.latest_summary = (handle, golden["summary"]["sequence_number"])
    doc.sequencer.seq = golden["summary"]["sequence_number"]
    rt = ContainerRuntime(
        svc, "golden3", channels=(SharedString("text"), SharedMap("map"))
    )
    assert rt.get_channel("text").get_text() == golden["text"]
    assert rt.get_channel("map").get("title") == "golden doc"


def test_parent_format_summary_loads_and_replays():
    """A summary written before the fourth removers lane (PR 35's golden:
    rbits, rbits2, rbits3 and no rbits4; its noops sequenced with null
    contents) loads into a live container, whose replica then takes ops
    from writers in the new lane's slots like any other: load_core leaves
    the missing lane at its empty default."""
    import numpy as np

    from fluidframework_tpu.ops.segment_state import RBITS_LANES, to_host

    with open(os.path.join(GOLDEN_DIR, "golden_session_pr35.json")) as f:
        golden = json.load(f)
    lanes = golden["summary"]["channels"]["text"]["lanes"]
    assert "rbits3" in lanes and "rbits4" not in lanes
    assert RBITS_LANES[-1] == "rbits4"
    svc = LocalFluidService()
    handle = svc.store.put_summary(golden["summary"])
    doc = svc._doc("golden4")
    doc.latest_summary = (handle, golden["summary"]["sequence_number"])
    doc.sequencer.seq = golden["summary"]["sequence_number"]
    a = ContainerRuntime(
        svc, "golden4", channels=(SharedString("text"), SharedMap("map"))
    )
    b = ContainerRuntime(
        svc, "golden4", channels=(SharedString("text"), SharedMap("map"))
    )
    assert a.get_channel("text").get_text() == golden["text"]
    assert [list(x) for x in a.get_channel("text").annotations()] == (
        golden["annotations"])
    h = to_host(a.get_channel("text")._state)
    assert not np.asarray(h.rbits4).any()
    # The loaded replicas go on: concurrent overlapping removes, then an
    # insert, converge.
    n = len(golden["text"])
    a.get_channel("text").remove_range(1, n - 1)
    b.get_channel("text").remove_range(2, n)
    b.get_channel("text").insert_text(0, "!")
    for rt in (a, b):
        rt.flush()
    while any(rt.process_incoming() for rt in (a, b)):
        pass
    assert a.get_channel("text").get_text() == b.get_channel("text").get_text()
    assert a.get_channel("text").get_text() == "!" + golden["text"][0]
