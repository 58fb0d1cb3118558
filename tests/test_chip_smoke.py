"""The bring-up walk's own code on the CPU: ``chip_smoke.py`` is run on the
chip through the chip tool, so what it carries itself (the ``tree``
phase's body, the bulk ``Feeder``) is driven here at a small size.
Importing it needs no TPU; only its ``device`` phase demands one."""

import numpy as np

import chip_smoke
from fluidframework_tpu.service.pipeline import PipelineFluidService


def test_tree_phase_rides_the_device_path_and_matches_the_host_engine():
    rec = chip_smoke.phase_tree(
        n_docs=8, n_commits=32, scripts=4, wave=16, move_prob=0.1
    )
    assert rec["device_fraction"] == 1.0
    assert rec["parity_with_host_engine"] == "ok"
    assert rec["n_docs"] == 8 and rec["waves"] == 2
    assert rec["move_commit_fraction"] > 0  # moves rode the device too


def test_feeder_joins_and_feeds_an_in_process_service():
    svc = PipelineFluidService()
    docs = [f"f{i}" for i in range(5)]
    f = chip_smoke.Feeder(lambda fn: fn(), svc, docs)
    assert all(c >= 0 for c in f.clients)  # one writer joined per doc
    joined = f.heads.copy()
    f.inserts(3)
    f.inserts(2, sel=np.arange(2))  # a narrower set, the same every time
    f.remove(0, 1, np.arange(2))
    svc.flush_device()
    sent = np.array([6, 6, 3, 3, 3])
    for i, doc in enumerate(docs):
        # Every op was sequenced: the head moved by exactly what was sent.
        assert svc.doc_head(doc) == joined[i] + sent[i] == f.heads[i]
        want = f.expected(5)[1:] if i < 2 else f.expected(3)
        got = svc.device.text(doc, chip_smoke.CHANNEL)
        assert got == want == chip_smoke.oracle_replay(svc, doc), (doc, got)
    assert f.throttled == 0
    assert svc.device.stats()["docs_with_errors"] == 0
