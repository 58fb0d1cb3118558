"""Traffic kind ``ws_edit``: interactive editing over the websocket front
door (open loop), with REST channel reads meanwhile.

The server runs in this process, on its loop thread, and keeps the chip.
Writers live in children (``ws_child.py``) pinned to the CPU; each child
holds whole documents with all their writers. The fleet is loaded through
the bulk front door first, as in ``bulk_ingest``; the documents the
writers edit are taken from it by the seed, and the loader leaves them so
that the minimum sequence number follows the live writers.
"""

from __future__ import annotations

import bisect
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import harness as H
from benchmark.reference.replay import replay
from benchmark.traffic.ws_child import percentile


class State:
    pass


def setup(ctx) -> State:
    p, cfg = ctx.params, ctx.config
    st = State()
    st.srv, st.server_cfg = H.start_server(ctx.out, ctx.rehearsal)
    n = cfg["resident_documents"]
    rng = np.random.default_rng([ctx.seed, 1])
    picks = rng.choice(n, int(p["documents"]) + p["verify_documents"], replace=False)
    st.ws_docs = picks[: int(p["documents"])]
    st.watch = picks[int(p["documents"]):]
    gen = H.EditGen(n, rng, p["insert_share"], p["cut_at"], p["cut_to"])
    st.feeder = H.BulkFeeder(st.srv, [f"d{i}" for i in range(n)], gen)
    st.feeder.load(
        cfg["assumed"]["ops_per_document_at_load"], p["load_chunk"], ctx.out.say
    )
    # The loader leaves the documents the writers will edit: an idle
    # writer in the quorum would hold the minimum sequence number back.
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
    total_writers = len(docs) * int(p["writers_per_doc"])
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BENCH_RUN", None)
    children = st.children
    for c in range(n_children):
        spec = {
            "index": c, "seed": ctx.seed, "host": "127.0.0.1",
            "port": st.srv.port, "docs": docs[c::n_children],
            "written_docs": docs,
            "writers_per_doc": int(p["writers_per_doc"]),
            "total_writers": total_writers, "children": n_children,
            "frames_per_s": p["frames_per_s"],
            "ops_per_frame": int(p["ops_per_frame"]),
            "insert_share": p["insert_share"], "cut_at": int(p["cut_at"]),
            "cut_to": int(p["cut_to"]),
            "reads_per_s": p["reads_per_s"] if c == 0 else 0,
            "resident_documents": ctx.config["resident_documents"],
            "zipf_s": p["zipf_s"], "drain_seconds": p["drain_seconds"],
        }
        proc = subprocess.Popen(
            [sys.executable, os.path.join(H.BENCH, "traffic", "ws_child.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True,
            cwd=H.ROOT,
        )
        proc.stdin.write(json.dumps(spec) + "\n")
        proc.stdin.flush()
        children.append(proc)
    ctx.out.say("children_spawned", children=n_children, writers=total_writers)
    deadline = time.monotonic() + p["children_ready_seconds"]
    for proc in children:
        _expect(proc, "ready", max(1.0, deadline - time.monotonic()))
    ctx.out.say("children_ready")


def _expect(proc, key: str, timeout: float = 600.0) -> dict:
    """The child's next line that carries ``key``."""
    box: list = []

    def read():
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("{") and key in json.loads(line):
                box.append(json.loads(line)[key])
                return

    t = threading.Thread(target=read, daemon=True)
    t.start()
    t.join(timeout)
    if not box:
        raise RuntimeError(
            f"ws child gave no {key!r} (exit code {proc.poll()})"
        )
    return box[0]


def _tell(st, **cmd) -> None:
    for proc in st.children:
        proc.stdin.write(json.dumps(cmd) + "\n")
        proc.stdin.flush()


def warm_boxcars(ctx, st) -> None:
    """One boxcar of each shape of the mix's ``warm_boxcars``, before the
    writers start.

    A step's program is built per boxcar shape ``[B, K]``: B the documents
    of the boxcar and K the most ops of one of them, each rounded up to a
    power of two (K from 8). The schedule below meets the smallest shapes
    only; the window then builds the next one on the serving loop the
    first time a few frames pile up: 0.25 s when the deadline ticker meets
    it, 3.1 s when the flush that a read forces does, and more on a busy
    host (PERF.md section 6, PR 28). So the loader sends ``[documents,
    ops]`` of each listed shape to documents nobody else writes or
    compares, one batch a boxcar, and leaves each to the ticker."""
    f, dev = st.feeder, st.srv.service.device
    taken = np.concatenate([st.ws_docs, st.watch])
    spare = np.setdiff1d(np.arange(len(f.doc_ids)), taken)
    spare = np.random.default_rng([ctx.seed, 5]).permutation(spare)
    before = H.on_loop(st.srv, lambda: H.counters_now(st.srv))
    at, by_flush = 0, 0
    for n, k in ctx.params["warm_boxcars"]:
        sent = H.on_loop(st.srv, lambda: dev.pump_dispatches)
        f.land(f.build(np.sort(spare[at:at + int(n)]), int(k)))
        give_up = time.monotonic() + 2.0
        while H.on_loop(st.srv, lambda: dev.pump_dispatches) == sent:
            if time.monotonic() > give_up:
                by_flush += 1
                break
            time.sleep(0.005)
        H.settle(st.srv)
        at += int(n)
    c = H.delta(H.on_loop(st.srv, lambda: H.counters_now(st.srv)), before)
    ctx.out.say("warm_boxcars", shapes=ctx.params["warm_boxcars"],
                aot_builds=c["aot_builds"], dispatches=c["pump_dispatches"],
                seconds=c["t"], left_to_the_flush=by_flush,
                reoffers=f.reoffers)


def warm(ctx, st) -> None:
    """Every boxcar shape first; then start the schedule and let it run
    until no program is built any more; concurrent reads of every small
    batch size warm the gather."""
    p = ctx.params
    warm_boxcars(ctx, st)
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
    while True:
        for k in (1, 2, 3, 4):
            burst(k)
        time.sleep(0.5)
        now = time.monotonic()
        try:
            c = H.on_loop(st.srv, lambda: H.counters_now(st.srv), timeout=30.0)
        except TimeoutError:
            # The server's loop did not answer in 30 s: say where it is.
            import faulthandler

            faulthandler.dump_traceback()
            raise RuntimeError("warm-up: the server's loop does not answer")
        cur = (c["aot_builds"], ctx.meter.compiles)
        ctx.out.say(
            "warming", seconds=now - t0, aot_builds=c["aot_builds"],
            compiles=ctx.meter.compiles, dispatches=c["pump_dispatches"],
            frames_received=c["frames_received"], ops_applied=c["ops_applied"],
        )
        if cur != last:
            flat_since, last = now, cur
        if now - t0 >= p["warm_seconds"] and now - flat_since >= p["warm_flat_seconds"]:
            break
        if now - t0 > p["warm_max_seconds"]:
            raise RuntimeError("warm-up: programs are still being built")
    ctx.out.say("warm", seconds=now - t0)


def run(ctx, st, seconds: float, tracer) -> dict:
    p = ctx.params
    t0 = time.monotonic() + 0.25  # every child hears of it before it starts
    _tell(st, cmd="window", at=t0, seconds=seconds)
    trace_at, trace_s = p["trace_after_s"], p["trace_seconds"]
    if tracer.on:
        time.sleep(max(0.0, t0 + trace_at - time.monotonic()))
        tracer.start()
        time.sleep(trace_s)
        tracer.stop()
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    window_s = time.monotonic() - t0
    return {"window_s": window_s}


def collect(ctx, st, res: dict) -> dict:
    """After the window's counters are taken: the children drain and
    report, and the window's latencies are reduced."""
    deadline = time.monotonic() + ctx.params["drain_seconds"] + 60.0
    reports = [
        _expect(proc, "done", max(1.0, deadline - time.monotonic()))
        for proc in st.children
    ]
    for proc in st.children:
        proc.stdin.close()
        proc.wait(60)
    st.reports = reports
    ack = [x for r in reports for x in r["ack_ms"]]
    late = [x for r in reports for x in r["late_ms"]]
    reads = [x for r in reports for x in r["read_ms"]]
    frames = sum(r["frames_attempted"] for r in reports)
    frames_failed = sum(r["frames_failed"] for r in reports)
    n_reads = sum(r["reads_attempted"] for r in reports)
    reads_failed = sum(r["reads_failed"] for r in reports)
    k = int(ctx.params["ops_per_frame"])
    res.update(
        attempted=frames * k + n_reads,
        failed=frames_failed * k + reads_failed,
        metrics={
            "ack_p50_ms": percentile(ack, 0.50),
            "ack_p95_ms": percentile(ack, 0.95),
            "read_p95_ms": percentile(reads, 0.95),
        },
        layer={"gen_late_p95_ms": percentile(late, 0.95)},
        notes={
            "frames": frames, "frames_failed": frames_failed,
            "ack_samples": len(ack), "read_samples": len(reads),
            "reads_failed": reads_failed,
            "reads_reoffered_after_503": sum(r["reads_shed"] for r in reports),
            "frames_regenerated_after_nack": sum(
                r["frames_regenerated_after_nack"] for r in reports
            ),
            "gen_late_p50_ms": percentile(late, 0.5),
            "gen_late_p95_ms": percentile(late, 0.95),
            "gen_late_max_ms": max(late) if late else None,
            "ack_p50_first_half_ms": [
                r["ack_p50_first_half_ms"] for r in reports
            ],
            "ack_p50_second_half_ms": [
                r["ack_p50_second_half_ms"] for r in reports
            ],
            "ack_p99_ms": percentile(ack, 0.99),
            "ack_max_ms": max(ack) if ack else None,
            "read_p50_ms": percentile(reads, 0.5),
            "offered_frames_per_s": frames / res["window_s"],
        },
    )
    return res


def verify(ctx, st) -> list:
    """Every number compared, beside its limit. All of what is compared
    comes through the entries the window timed: the writers' own replicas
    and acknowledgements off their websockets, and the texts the REST read
    entry replied, those of the window's reads first."""
    svc, f = st.srv.service, st.feeder
    H.settle(st.srv)
    by_doc: dict = {}
    for r in st.reports:
        for w in r["writers"]:
            by_doc.setdefault(w["doc"], []).append(w)
    reads = [rd for r in st.reports for rd in r["reads"] if rd["text"] is not None]
    read_docs = {rd["doc"] for rd in reads}
    bad_text = bad_log = missing = client_differs = pending = caught = 0
    compared = [f.doc_ids[i] for i in np.concatenate([st.ws_docs, st.watch]).tolist()]
    index = f._index
    # doc -> (texts after each sequence number, [(when acked, seq)] sorted)
    history: dict = {}
    for doc in compared + sorted(read_docs - set(compared)):
        i = index[doc]
        got = H.log_and_replay(ctx, st.srv, doc)
        if got is None:
            bad_log += 1
            continue
        head, log, texts, acked = got
        # What the loader sent is in the log, in order, ahead of the rest.
        sent = f.sent_ops(i)
        logged = [op for op in log if op.contents is not None][: len(sent)]
        bad_log += [o.contents for o in logged] != [o.contents for o in sent]
        seq_of = {
            (op.client, op.csn): op.seq for op in log
            if op.contents is not None
        }
        acks = []
        for w in by_doc.get(doc, []):
            for csn, at in w["acked"]:
                seq = seq_of.get((w["client"], csn), 0)
                if seq <= w["join_seq"]:
                    missing += 1  # acknowledged, and not in the durable log
                else:
                    acks.append((at, seq))
        acks.sort()
        # What was in the log before the window began is the least any
        # reply holds: the loader's ops, and for a document nobody edits
        # the whole log.
        floor = int(f.join_seq[i]) + len(sent) if doc in by_doc else head
        history[doc] = (
            texts, [a for a, _ in acks], _running_max(acks, floor), floor
        )
        if doc not in compared:
            continue
        served = H.rest_text(st.srv, doc)
        for w in by_doc.get(doc, []):
            pending += w["pending"]
            if w["text"] != served:
                client_differs += 1
                ctx.out.say("client_mismatch", doc=doc, client=w["client"],
                            client_text=w["text"][:64], served=served[:64])
        if not by_doc.get(doc):
            first = int(f.join_seq[i]) + 1
            want, _, _ = replay(sent, first + len(sent) - 1, first=first)
            bad_text += want != served
        if served != texts[-1]:
            bad_text += 1
            ctx.out.say("text_mismatch", doc=doc, served=served[:64],
                        log_replay=texts[-1][:64])
        if ctx.control:
            caught += H.control_caught(log, head, served)
    # The window's reads: a reply is the replay of a prefix of the
    # document's log that holds at least every op acknowledged to its
    # writer before the read was asked for (for a document nobody edits,
    # the whole log). The control: the reference's own reply one op short
    # of that, a read served stale by a single op.
    stale = on_written = ctl_stale = 0
    for rd in reads:
        if rd["doc"] not in history:
            stale += 1  # its log was at fault, counted above
            continue
        texts, at, upto, floor = history[rd["doc"]]
        on_written += rd["doc"] in by_doc
        n = bisect.bisect_left(at, rd["asked"])
        least = upto[n - 1] if n else floor
        if rd["text"] not in texts[least:]:
            stale += 1
            ctx.out.say("read_mismatch", doc=rd["doc"], reply=rd["text"][:64],
                        at_least_seq=least, log_replay=texts[-1][:64])
        if ctx.control:
            ctl_stale += texts[least - 1] not in texts[least:]
    if ctx.control:
        ctx.out.say("control", what="replay with the last op withheld",
                    documents=len(compared), told_apart=caught, needed=1)
        ctx.out.say("control", what="a reply one acknowledged op stale",
                    reads=len(reads), told_apart=ctl_stale, needed=1)
    stats = H.on_loop(st.srv, svc.device.stats)
    if stats["docs_with_errors"]:
        ctx.out.say("device_errors", **H.on_loop(st.srv, lambda: H.errored(svc, ctx.run_dir)))
    return [
        ("documents_compared", len(compared), None),
        ("read_replies_compared", len(reads), None),
        ("read_replies_of_written_documents", on_written, None),
        ("read_replies_differ_from_replay", stale, 0),
        ("served_text_differs_from_replay", bad_text, 0),
        ("durable_log_faults", bad_log, 0),
        ("acked_ops_missing_from_log", missing, 0),
        ("client_text_differs_from_served", client_differs, 0),
        ("client_ops_still_pending", pending, 0),
        ("docs_with_errors", stats["docs_with_errors"], 0),
    ]


def _running_max(acks: list, top: int) -> list:
    out = []
    for _at, seq in acks:
        top = max(top, seq)
        out.append(top)
    return out


def teardown(ctx, st) -> None:
    for proc in st.children:
        if proc.poll() is None:
            proc.kill()
        proc.wait(30)
    st.srv.stop()
