"""Traffic kind ``ws_meeting``: a few documents with a meeting's worth of
writers each (the reference's ``test-service-load`` profile ``ci``: 120
clients in one document), over the websocket front door, open loop, with
REST channel reads meanwhile.

The kind is ``ws_edit`` with another child (``ws_meeting_child.py``) and
what a many-writer document adds around it: before anything is loaded it
asks the program whether it admits that many concurrent writers a document
and otherwise fails with one line; every writer takes in every broadcast as
it arrives (so the collab-window heartbeat runs) and sends a signal with
every frame; a seeded third of every document's writers drop their socket
and rejoin during the warm-up, which then lasts until no program was built
and no document changed tier for ``warm_flat_seconds``; and the comparison
holds, beside everything ``ws_edit`` holds, each of a document's 120
clients' text to the served one, and the joins nacked for want of a writer
slot, the ops refused for their writer's slot and the documents that held
fewer write slots than writers each to 0.

A document's writers are dealt round robin over ALL the children (writer k
lives in child k mod children), so that no child carries a whole document's
120 replicas, each of which applies every op of the document.
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import harness as H
from benchmark.traffic import ws_edit
from benchmark.traffic.ws_edit import State, _expect, _tell

run, teardown = ws_edit.run, ws_edit.teardown
ERR_CLIENT = 4  # SegmentState.err: an op's writer slot beyond the removers set


def rejoins_per_doc(p: dict) -> int:
    return int(round(p["rejoin_share"] * int(p["writers_per_doc"])))


def setup(ctx) -> State:
    p, cfg = ctx.params, ctx.config
    writers = int(p["writers_per_doc"])
    # Before the fleet is loaded: what the program admits. A program
    # whose removers set is narrower than the meeting refuses the
    # writers past its cap with a 429, and they would wait out
    # ``children_ready_seconds`` for a join that was nacked.
    from fluidframework_tpu.protocol.constants import MAX_WRITERS

    if MAX_WRITERS < writers or cfg["writers_per_document"] != writers:
        H.fail(
            f"benchmark: ws-meeting: the program admits {MAX_WRITERS} concurrent "
            f"writers a document (protocol.constants.MAX_WRITERS); configuration "
            f"{cfg['name']} needs {cfg['writers_per_document']} and the mix "
            f"offers {writers}"
        )
    if writers % int(p["children"]):
        H.fail("benchmark: ws-meeting: children must divide writers_per_doc")
    # The names ``ws_edit`` knows the same numbers by.
    p["documents"] = int(p["meeting_documents"])
    p["frames_per_s"] = (
        p["ops_per_writer_per_min"] * writers * p["documents"]
        / 60.0 / int(p["ops_per_frame"])
    )
    # When the children go, every leave is broadcast to sockets whose
    # peers are gone already, and asyncio warns of each such write
    # ("socket.send() raised exception", half a megabyte a run).
    logging.getLogger("asyncio").setLevel(logging.ERROR)
    st = State()
    st.srv, st.server_cfg = H.start_server(ctx.out, ctx.rehearsal)
    n = cfg["resident_documents"]
    rng = np.random.default_rng([ctx.seed, 1])
    picks = rng.choice(n, p["documents"] + int(p["verify_documents"]), replace=False)
    st.ws_docs = picks[: p["documents"]]
    st.watch = picks[p["documents"]:]
    gen = H.EditGen(n, rng, p["insert_share"], p["cut_at"], p["cut_to"])
    st.feeder = H.BulkFeeder(st.srv, [f"d{i}" for i in range(n)], gen)
    st.feeder.load(
        cfg["assumed"]["ops_per_document_at_load"], p["load_chunk"], ctx.out.say
    )
    # The loader leaves the meeting documents: an idle writer in the
    # quorum would hold the minimum sequence number back.
    svc = st.srv.service

    def leave():
        for i in st.ws_docs.tolist():
            svc.disconnect(st.feeder.doc_ids[i], int(st.feeder.clients[i]))
        svc.pump()

    H.on_loop(st.srv, leave)
    H.settle(st.srv)
    st.children = []
    _spawn(ctx, st)
    return st


def _spawn(ctx, st) -> None:
    p = ctx.params
    n_children = int(p["children"])
    docs = [st.feeder.doc_ids[i] for i in st.ws_docs.tolist()]
    writers = int(p["writers_per_doc"])
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BENCH_RUN", None)
    for c in range(n_children):
        spec = {
            "index": c, "seed": ctx.seed, "host": "127.0.0.1",
            "port": st.srv.port, "docs": docs, "written_docs": docs,
            # This child's share of every document's writers.
            "writers_per_doc": writers // n_children,
            "meeting_writers": writers,
            "total_writers": len(docs) * writers, "children": n_children,
            "frames_per_s": p["frames_per_s"],
            "ops_per_frame": int(p["ops_per_frame"]),
            "signals_per_op": int(p["signals_per_op"]),
            "insert_share": p["insert_share"], "cut_at": int(p["cut_at"]),
            "cut_to": int(p["cut_to"]),
            "rejoins_per_doc": rejoins_per_doc(p),
            "rejoin_start_s": p["rejoin_start_s"],
            "rejoin_gap_s": p["rejoin_gap_s"], "poll_s": p["poll_ms"] / 1e3,
            "reads_per_s": p["reads_per_s"] if c == 0 else 0,
            "resident_documents": ctx.config["resident_documents"],
            "zipf_s": p["zipf_s"], "drain_seconds": p["drain_seconds"],
        }
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(H.BENCH, "traffic", "ws_meeting_child.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True,
            cwd=H.ROOT,
        )
        proc.stdin.write(json.dumps(spec) + "\n")
        proc.stdin.flush()
        st.children.append(proc)
    ctx.out.say("children_spawned", children=n_children,
                writers=len(docs) * writers, documents=len(docs))
    deadline = time.monotonic() + p["children_ready_seconds"]
    for proc in st.children:
        _expect(proc, "ready", max(1.0, deadline - time.monotonic()))
    peaks = H.on_loop(st.srv, lambda: _slot_peaks(st))
    ctx.out.say("children_ready", writer_slots_peak=peaks)


def _slot_peaks(st) -> dict:
    """Every meeting document's most write slots held at once (its
    sequencer's own count); -1 where the program keeps none."""
    return {
        doc: getattr(seq, "writer_slots_peak", -1)
        for doc, seq in _sequencers(st).items()
    }


def _stats(st) -> dict:
    """The pipeline's always-on counts; none where the program keeps none."""
    return H.on_loop(st.srv, lambda: dict(
        getattr(st.srv.service, "stats", dict)()
    ))


def _sequencers(st) -> dict:
    svc = st.srv.service
    docs = [st.feeder.doc_ids[i] for i in st.ws_docs.tolist()]
    return {doc: svc._deli_doc(doc).sequencer for doc in docs}


def _rejoins_done(st, p: dict) -> int:
    """Rejoins through so far, over the meeting documents: joins deli has
    sequenced beyond the loader's and the writers' first."""
    first = 1 + int(p["writers_per_doc"])
    return sum(
        seq.checkpoint_dict()["connection_count"] - first
        for seq in _sequencers(st).values()
    )


def warm(ctx, st) -> None:
    """``ws_edit``'s warm-up (every boxcar shape first, then the schedule
    until no program is built any more, reads of every small batch size
    meanwhile), held open until the rejoins are through and until no
    document has changed tier either for ``warm_flat_seconds``."""
    p = ctx.params
    ws_edit.warm_boxcars(ctx, st)
    _tell(st, cmd="go", at=time.monotonic() + 0.3)
    from fluidframework_tpu.drivers.network_driver import NetworkFluidService

    reader = NetworkFluidService("127.0.0.1", st.srv.port)
    docs = st.feeder.doc_ids

    def burst(k: int) -> None:
        ts = [
            threading.Thread(
                target=reader.get_channel_text, args=(docs[j], H.CHANNEL)
            )
            for j in range(k)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    t0 = time.monotonic()
    flat_since, last = t0, None
    rejoins = p["documents"] * rejoins_per_doc(p)
    while True:
        for k in (1, 2, 3, 4):
            burst(k)
        time.sleep(0.5)
        now = time.monotonic()
        try:
            c = H.on_loop(st.srv, lambda: H.counters_now(st.srv), timeout=30.0)
        except TimeoutError:
            import faulthandler

            faulthandler.dump_traceback()
            raise RuntimeError("warm-up: the server's loop does not answer")
        rejoined = H.on_loop(st.srv, lambda: _rejoins_done(st, p))
        # A rejoin is no reason to wait longer, the last one apart: the
        # window starts three heartbeats after it at the soonest.
        cur = (c["aot_builds"], ctx.meter.compiles, c["migrations"],
               c["demotions"], rejoined >= rejoins)
        ctx.out.say(
            "warming", seconds=now - t0, aot_builds=c["aot_builds"],
            compiles=ctx.meter.compiles, migrations=c["migrations"],
            rejoined=rejoined, dispatches=c["pump_dispatches"],
            frames_received=c["frames_received"], ops_applied=c["ops_applied"],
        )
        if cur != last:
            flat_since, last = now, cur
        if (
            now - t0 >= p["warm_seconds"] and rejoined >= rejoins
            and now - flat_since >= p["warm_flat_seconds"]
        ):
            break
        if now - t0 > p["warm_max_seconds"]:
            raise RuntimeError(
                "warm-up: programs are still being built, documents still "
                f"change tier, or rejoins are missing ({rejoined} of {rejoins})"
            )
    ctx.out.say("warm", seconds=now - t0)


def collect(ctx, st, res: dict) -> dict:
    # A document's writers live in every child, so the children cannot
    # tell on their own when it has come to rest: each says when its own
    # frames have all come back, and only then is each document's head
    # read, up to which every client takes in before its text is reported.
    deadline = time.monotonic() + ctx.params["drain_seconds"] + 60.0
    for proc in st.children:
        _expect(proc, "sent", max(1.0, deadline - time.monotonic()))
    heads = H.on_loop(
        st.srv, lambda: {d: seq.seq for d, seq in _sequencers(st).items()}
    )
    ctx.out.say("children_sent", heads=heads)
    _tell(st, cmd="finish", heads=heads)
    res = ws_edit.collect(ctx, st, res)
    reports = st.reports
    stats = _stats(st)
    res["layer"]["writer_slots_peak"] = stats.get("writer_slots_peak")
    res["notes"].update(
        signals_sent=sum(r["signals_sent"] for r in reports),
        signals_taken_in_by_clients=sum(r["signals_received"] for r in reports),
        rejoins=sum(r["rejoins"] for r in reports),
        connect_retries=sum(r["connect_retries"] for r in reports),
        heartbeat_noops=sum(r["heartbeat_noops"] for r in reports),
        polls=sum(r["polls"] for r in reports),
        poll_round_p95_ms=max(r["poll_round_p95_ms"] for r in reports),
        pipeline_stats=stats,
    )
    return res


def verify(ctx, st) -> list:
    """Everything ``ws_edit`` compares (with 120 clients' texts a document
    instead of four), and the three numbers of the many-writer document,
    each beside its limit 0."""
    checks = ws_edit.verify(ctx, st)
    p = ctx.params
    writers = int(p["writers_per_doc"])
    svc = st.srv.service
    reported: dict = {}
    for r in st.reports:
        for w in r["writers"]:
            reported[w["doc"]] = reported.get(w["doc"], 0) + 1
    docs = [st.feeder.doc_ids[i] for i in st.ws_docs.tolist()]
    peaks = H.on_loop(st.srv, lambda: _slot_peaks(st))
    short = sum(
        peaks[d] < writers or reported.get(d, 0) != writers for d in docs
    )
    stats = _stats(st)
    nacked = stats.get("join_nacks_slots", -1)

    def refused():
        dev = svc.device
        return sum(
            bool(int(dev._doc_state(idx).err) & ERR_CLIENT)
            for idx in sorted(getattr(dev, "_errored", ()))
        )

    rejoins = sum(r["rejoins"] for r in st.reports)
    ctx.out.say("meeting", writer_slots_peak=peaks, clients_reported=reported,
                rejoins=rejoins, pipeline_stats=stats)
    return checks + [
        ("clients_compared", sum(reported.values()), None),
        ("joins_nacked_for_want_of_a_slot", nacked if nacked >= 0 else 1, 0),
        ("ops_refused_with_err_client", H.on_loop(st.srv, refused), 0),
        ("meeting_documents_under_their_writers_in_slots", short, 0),
        ("rejoins_missing", len(docs) * rejoins_per_doc(p) - rejoins, 0),
    ]
