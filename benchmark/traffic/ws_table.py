"""Traffic kind ``ws_table``: people filling in shared tables over the
websocket front door (open loop), with REST reads of the tables' grids
meanwhile.

The kind is ``ws_edit`` on another schema: the same sockets, children,
arrivals, acknowledgement stamps and window (``ws_edit.run``,
``ws_edit.teardown``, the reduction of ``ws_edit.collect``), with
``SharedMatrix`` documents instead of ``SharedString`` ones. What differs:

- before anything is loaded it asks the program whether it serves a matrix
  channel at all: one ``insrow`` to a probe document and a REST read of its
  channel; anything but a 200 with a grid ends the run with one line and
  exit code 1;
- the fleet is tables: every resident document holds one matrix channel,
  loaded through the JSON wire on the server's loop (joins in bulk as the
  other kinds', then each document's ops as raw records, one pipeline
  sweep a chunk): one insert of the rows, one of the columns, and cells;
- a frame is one user action (``ws_table_child.py``), and the reader's
  replies are grids;
- the comparison holds every grid to the plain reference's replay of the
  document's log (``benchmark/reference/matrix_replay.py``): the window's
  replies each to a prefix holding every op acknowledged before the read
  was asked for, and after the window, once every child has said ``sent``
  and every client has taken in its document's head, served grid == replay
  == every client's grid; the summary view serves no cell under a handle
  the replay does not hold live. Controls: the last op withheld, a reply
  one acknowledged cell write stale, and a grid whose cells are ahead of
  its axes.
"""

from __future__ import annotations

import bisect
import json
import os
import subprocess
import sys
import threading
import time
from urllib.error import HTTPError

import numpy as np

from benchmark import harness as H
from benchmark.reference import matrix_replay
from benchmark.reference.replay import LogFault
from benchmark.traffic import ws_edit
from benchmark.traffic.ws_edit import State, _expect, _running_max, _tell
from benchmark.traffic.ws_table_child import get_grid

run, teardown = ws_edit.run, ws_edit.teardown
PROBE = "table-probe"


def _op(csn: int, ref: int, contents: dict):
    from fluidframework_tpu.protocol.types import DocumentMessage, MessageType

    return DocumentMessage(
        client_sequence_number=csn, reference_sequence_number=ref,
        type=MessageType.OPERATION,
        contents={"address": H.CHANNEL, "contents": contents},
    )


def _send(svc, doc: str, client: int, msgs: list) -> None:
    """Ops of one writer onto the raw log (the front door's produce,
    without a pipeline sweep an op); the caller pumps."""
    for msg in msgs:
        svc._send_raw(doc, {"t": "op", "client": client, "msg": msg})


def probe(ctx, st) -> None:
    """Whether the program serves a table: one ``insrow`` and a read of
    its channel through the REST entry. A program without matrix channels
    drops the op and answers 404 ``unknown channel``."""
    svc = st.srv.service

    def go():
        conn = H.bulk_connect(svc, [PROBE])[0]
        _send(svc, PROBE, conn.client_id, [_op(1, conn.join_seq, {
            "k": "insrow", "pos": 0, "count": 1,
            "orig": conn.conn_no * H.MINT_STRIDE + 1,
        })])
        svc.pump()

    H.on_loop(st.srv, go)
    try:
        reply = get_grid("127.0.0.1", st.srv.port, PROBE)
    except HTTPError as e:
        reply = {"status": e.code, "body": e.read().decode()[:80]}
    if reply.get("grid") != [[]]:
        st.srv.stop()
        H.fail(
            "benchmark: ws-table: the program serves no matrix channel: one "
            f"insrow to document {PROBE!r} and a read of its channel gave "
            f"{json.dumps(reply)[:120]}, not a grid of one row"
        )
    ctx.out.say("probe", grid=reply["grid"])


class Loader:
    """One writer a document: the table at load, as ops on the JSON wire.
    Knows what it sent, so that the comparison can hold the log to it."""

    def __init__(self, srv, n: int, cfg: dict, seed: int):
        self.srv, self.svc = srv, srv.service
        self.doc_ids = [f"d{i}" for i in range(n)]
        self._index = {d: i for i, d in enumerate(self.doc_ids)}
        self.rows, self.cols = cfg["rows_at_load"], cfg["cols_at_load"]
        self.cells, self.seed = cfg["cells_at_load"], seed
        self.clients = np.zeros(n, np.int64)
        self.join_seq = np.zeros(n, np.int64)
        self.connno = np.zeros(n, np.int64)
        self.csn = np.zeros(n, np.int64)
        self.full: set = set()  # documents loaded with every cell set

    def contents(self, i: int) -> list:
        """What the loader sends document ``i``: its wire ops in order."""
        base = int(self.connno[i]) * H.MINT_STRIDE
        ops = [
            {"k": "insrow", "pos": 0, "count": self.rows, "orig": base + 1},
            {"k": "inscol", "pos": 0, "count": self.cols, "orig": base + 2},
        ]
        rng = np.random.default_rng([self.seed, 7, i])
        if i in self.full:
            where = np.arange(self.rows * self.cols)
        else:
            where = rng.choice(self.rows * self.cols, self.cells, replace=False)
        for at, v in zip(where.tolist(), rng.integers(0, 10**6, len(where)).tolist()):
            ops.append({
                "k": "cell", "row": [base + 1, at // self.cols],
                "col": [base + 2, at % self.cols], "val": f"v{v}",
            })
        return ops

    def load(self, chunk: int, full, say) -> None:
        n = len(self.doc_ids)
        self.full = set(int(i) for i in full)
        for lo in range(0, n, chunk):
            hi = min(n, lo + chunk)
            ids = self.doc_ids[lo:hi]

            def go():
                conns = H.bulk_connect(self.svc, ids)
                for i, c in enumerate(conns, lo):
                    self.clients[i], self.join_seq[i] = c.client_id, c.join_seq
                    self.connno[i] = c.conn_no
                    ops = self.contents(i)
                    _send(self.svc, self.doc_ids[i], c.client_id, [
                        _op(k + 1, c.join_seq, op) for k, op in enumerate(ops)
                    ])
                    self.csn[i] = len(ops)
                    c.inbox.clear()
                self.svc.pump()
                for c in conns:
                    if c.nacks:
                        raise RuntimeError(f"{c.doc_id}: nacked: {c.nacks[0]}")
                    c.inbox.clear()

            H.on_loop(self.srv, go, timeout=900.0)
            say("load", docs=hi, of=n)

    def warm(self, docs, k: int) -> None:
        """``k`` axis ops for each of ``docs`` in one sweep: a row inserted
        at the end and taken out again, so the tables stay as loaded."""
        svc = self.svc

        def go():
            for i in docs:
                i, msgs = int(i), []
                for j in range(k):
                    self.csn[i] += 1
                    op = (
                        {"k": "insrow", "pos": self.rows, "count": 1,
                         "orig": int(self.connno[i]) * H.MINT_STRIDE
                         + int(self.csn[i])}
                        if j % 2 == 0 else
                        {"k": "remrow", "start": self.rows, "end": self.rows + 1}
                    )
                    # The writer has seen its own ops so far: no stale ref.
                    ref = int(self.join_seq[i] + self.csn[i] - 1)
                    msgs.append(_op(int(self.csn[i]), ref, op))
                _send(svc, self.doc_ids[i], int(self.clients[i]), msgs)
            svc.pump()

        H.on_loop(self.srv, go)


def setup(ctx) -> State:
    p, cfg = ctx.params, ctx.config
    st = State()
    st.srv, st.server_cfg = H.start_server(ctx.out, ctx.rehearsal)
    probe(ctx, st)
    n = cfg["resident_documents"]
    rng = np.random.default_rng([ctx.seed, 1])
    picks = rng.choice(n, int(p["documents"]) + p["verify_documents"], replace=False)
    st.ws_docs = picks[: int(p["documents"])]
    st.watch = picks[int(p["documents"]):]
    st.feeder = f = Loader(st.srv, n, cfg["assumed"], ctx.seed)
    f.load(int(p["load_chunk"]), st.ws_docs, ctx.out.say)
    # The loader leaves the documents the writers will edit: an idle
    # writer in the quorum would hold the minimum sequence number back.
    svc = st.srv.service

    def leave():
        for i in st.ws_docs.tolist():
            svc.disconnect(f.doc_ids[i], int(f.clients[i]))
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
    for c in range(n_children):
        spec = {
            "index": c, "seed": ctx.seed, "host": "127.0.0.1",
            "port": st.srv.port, "docs": docs[c::n_children],
            "written_docs": docs,
            "writers_per_doc": int(p["writers_per_doc"]),
            "total_writers": total_writers, "children": n_children,
            "frames_per_s": p["actions_per_s"], "ops_per_frame": 1,
            "action_weights": p["action_weights"],
            "rows_min": int(p["rows_min"]), "rows_max": int(p["rows_max"]),
            "cols_min": int(p["cols_min"]), "cols_max": int(p["cols_max"]),
            "reads_per_s": p["reads_per_s"] if c == 0 else 0,
            "resident_documents": ctx.config["resident_documents"],
            "zipf_s": p["zipf_s"], "drain_seconds": p["drain_seconds"],
        }
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(H.BENCH, "traffic", "ws_table_child.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True,
            cwd=H.ROOT,
        )
        proc.stdin.write(json.dumps(spec) + "\n")
        proc.stdin.flush()
        st.children.append(proc)
    ctx.out.say("children_spawned", children=n_children, writers=total_writers)
    deadline = time.monotonic() + p["children_ready_seconds"]
    for proc in st.children:
        _expect(proc, "ready", max(1.0, deadline - time.monotonic()))
    ctx.out.say("children_ready")


def warm_boxcars(ctx, st) -> None:
    """One boxcar of each shape of the mix's ``warm_boxcars`` (``[axis
    slots, rows]``: only axis ops reach the device, one slot an op's axis),
    to tables nobody else writes or compares, before the writers start
    (``ws_edit.warm_boxcars``: a step's program is built per shape)."""
    f, dev = st.feeder, st.srv.service.device
    taken = np.concatenate([st.ws_docs, st.watch])
    spare = np.setdiff1d(np.arange(len(f.doc_ids)), taken)
    spare = np.random.default_rng([ctx.seed, 5]).permutation(spare)
    before = H.on_loop(st.srv, lambda: H.counters_now(st.srv))
    at = 0
    for n, k in ctx.params["warm_boxcars"]:
        sent = H.on_loop(st.srv, lambda: dev.pump_dispatches)
        f.warm(np.sort(spare[at:at + int(n)]), int(k))
        give_up = time.monotonic() + 2.0
        while H.on_loop(st.srv, lambda: dev.pump_dispatches) == sent:
            if time.monotonic() > give_up:
                break
            time.sleep(0.005)
        H.settle(st.srv)
        at += int(n)
    c = H.delta(H.on_loop(st.srv, lambda: H.counters_now(st.srv)), before)
    ctx.out.say("warm_boxcars", shapes=ctx.params["warm_boxcars"],
                aot_builds=c["aot_builds"], dispatches=c["pump_dispatches"],
                seconds=c["t"])


def warm(ctx, st) -> None:
    """Every boxcar shape first; then the schedule runs until no program
    was built for ``warm_flat_seconds``; concurrent grid reads of every
    small batch size warm the gather meanwhile."""
    p = ctx.params
    warm_boxcars(ctx, st)
    _tell(st, cmd="go", at=time.monotonic() + 0.3)
    docs, port = st.feeder.doc_ids, st.srv.port

    def burst(k: int) -> None:
        ts = [
            threading.Thread(target=get_grid, args=("127.0.0.1", port, docs[j]))
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
            import faulthandler

            faulthandler.dump_traceback()
            raise RuntimeError("warm-up: the server's loop does not answer")
        cur = (c["aot_builds"], ctx.meter.compiles, c["migrations"])
        ctx.out.say(
            "warming", seconds=now - t0, aot_builds=c["aot_builds"],
            compiles=ctx.meter.compiles, migrations=c["migrations"],
            dispatches=c["pump_dispatches"], ops_applied=c["ops_applied"],
        )
        if cur != last:
            flat_since, last = now, cur
        if now - t0 >= p["warm_seconds"] and now - flat_since >= p["warm_flat_seconds"]:
            break
        if now - t0 > p["warm_max_seconds"]:
            raise RuntimeError("warm-up: programs are still being built")
    ctx.out.say("warm", seconds=now - t0)


def collect(ctx, st, res: dict) -> dict:
    """The children say ``sent``; then each written document's head is
    read off the server and named to them, and they report once every
    client has taken in up to it (``ws_meeting.collect``)."""
    deadline = time.monotonic() + ctx.params["drain_seconds"] + 60.0
    for proc in st.children:
        _expect(proc, "sent", max(1.0, deadline - time.monotonic()))
    svc = st.srv.service
    docs = [st.feeder.doc_ids[i] for i in st.ws_docs.tolist()]
    heads = H.on_loop(st.srv, lambda: {d: svc.doc_head(d) for d in docs})
    ctx.out.say("children_sent", heads=heads)
    _tell(st, cmd="finish", heads=heads)
    res = ws_edit.collect(ctx, st, res)
    reports = st.reports
    stats = H.on_loop(st.srv, lambda: dict(getattr(svc, "stats", dict)()))
    actions = np.sum([r["actions"] for r in reports], axis=0).tolist()
    res["notes"].update(
        actions=dict(zip(
            ("set_cell", "insert_row", "insert_col", "remove_row",
             "remove_col"), actions,
        )),
        ops_sent=sum(r["ops_sent"] for r in reports),
        handle_pulls=int(sum(
            sum(w["handle_pulls"]) for r in reports for w in r["writers"]
        )),
        pipeline_stats=stats,
    )
    return res


def _history(ctx, st, doc: str):
    """(head, log, History) of one document, or None and a line saying
    why when the log breaks its own guarantees."""
    head, log = H.on_loop(st.srv, lambda: H.read_log(st.srv.service, doc))
    try:
        hist = matrix_replay.replay(log, head, every=True)[0]
    except LogFault as e:
        ctx.out.say("log_fault", doc=doc, error=str(e))
        return None
    return head, log, hist


def rest_grid(srv, doc: str, view: str = "") -> dict:
    """A table through the REST read entry the window timed."""
    for _ in range(30):
        try:
            return get_grid("127.0.0.1", srv.port, doc, view)
        except HTTPError as e:
            if e.code != 503:
                raise
            time.sleep(float(e.headers.get("Retry-After") or 1.0))
    raise RuntimeError(f"the REST read entry sheds every read of {doc}")


def stale_by_a_cell_write(hist, least: int):
    """The control of the window's reads: the reference's own grid from
    just before the last cell write at or under ``least`` that shows in
    the table, one acknowledged cell write stale. None where there is no
    such write."""
    for n in range(least, 0, -1):
        if hist.kinds[n] == "cell" and hist.grids[n] is not hist.grids[n - 1]:
            return hist.grids[n - 1]
    return None


def cells_ahead_of_axes(hist):
    """The control of the cut: the axes as of just before the log's last
    axis op under the cells as of a later cell write, one that shows in
    the table on both sides of that axis op. None where the log holds no
    such pair."""
    n = len(hist.grids) - 1
    for a in range(n, 0, -1):
        if hist.kinds[a] not in matrix_replay.AXIS_KINDS:
            continue
        rows, cols = (set(h) for h in hist.handles_at(a - 1))
        for c, key, _val in hist.writes:
            if (
                c > a and key[0] in rows and key[1] in cols
                and hist.grids[c] is not hist.grids[c - 1]
            ):
                return hist.skew(a - 1, c)
    return None


def verify(ctx, st) -> list:
    """Every number compared, beside its limit. All of what is compared
    comes through the entries the window timed: the writers' own replicas
    and acknowledgements off their websockets, and the grids the REST read
    entry replied, those of the window's reads first."""
    svc, f = st.srv.service, st.feeder
    H.settle(st.srv)
    by_doc: dict = {}
    for r in st.reports:
        for w in r["writers"]:
            by_doc.setdefault(w["doc"], []).append(w)
    reads = [rd for r in st.reports for rd in r["reads"] if rd["text"] is not None]
    read_docs = {rd["doc"] for rd in reads}
    bad_grid = bad_log = missing = client_differs = pending = 0
    removed_served = caught = skew_caught = skew_made = 0
    compared = [f.doc_ids[i] for i in np.concatenate([st.ws_docs, st.watch]).tolist()]
    index = f._index
    history: dict = {}
    for doc in compared + sorted(read_docs - set(compared)):
        i = index[doc]
        got = _history(ctx, st, doc)
        if got is None:
            bad_log += 1
            continue
        head, log, hist = got
        # What the loader sent is in the log, in order, ahead of the rest.
        sent = f.contents(i)
        logged = [op.contents for op in log if op.contents is not None]
        bad_log += logged[: len(sent)] != sent
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
        # reply holds: the loader's ops, and for a table nobody edits the
        # whole log.
        floor = int(f.join_seq[i]) + len(sent) if doc in by_doc else head
        history[doc] = (
            hist, [a for a, _ in acks], _running_max(acks, floor), floor
        )
        if doc not in compared:
            continue
        served = rest_grid(st.srv, doc)["grid"]
        for w in by_doc.get(doc, []):
            pending += w["pending"]
            if w["grid"] != served:
                client_differs += 1
                ctx.out.say("client_mismatch", doc=doc, client=w["client"],
                            client_grid=w["grid"][:2], served=served[:2])
        if served != hist.grids[-1]:
            bad_grid += 1
            ctx.out.say("grid_mismatch", doc=doc, served=served[:2],
                        log_replay=hist.grids[-1][:2])
        # The summary view: no cell under a handle the replay does not
        # hold live, and every cell it serves as the replay has it.
        want = hist.cells_at(head)
        rows, cols = (set(h) for h in hist.handles_at(head))
        for key, val in rest_grid(st.srv, doc, "summary")["cells"].items():
            a, b, c, d = (int(x) for x in key.split(":"))
            if (a, b) not in rows or (c, d) not in cols or want.get(
                ((a, b), (c, d))
            ) != val:
                removed_served += 1
        if ctx.control:
            n = sum(op.contents is not None for op in log)
            short = matrix_replay.replay(log, head, withhold=n - 1)[0]
            caught += short != served
            if doc in by_doc:
                skew = cells_ahead_of_axes(hist)
                if skew is not None:
                    skew_made += 1
                    skew_caught += skew not in hist.grids
    # The window's reads: a reply is the replay of a prefix of the
    # document's log that holds at least every op acknowledged to its
    # writer before the read was asked for (for a table nobody edits, the
    # whole log).
    stale = on_written = ctl_stale = 0
    for rd in reads:
        if rd["doc"] not in history:
            stale += 1  # its log was at fault, counted above
            continue
        hist, at, upto, floor = history[rd["doc"]]
        on_written += rd["doc"] in by_doc
        n = bisect.bisect_left(at, rd["asked"])
        least = upto[n - 1] if n else floor
        if rd["text"] not in hist.grids[least:]:
            stale += 1
            ctx.out.say("read_mismatch", doc=rd["doc"], reply=rd["text"][:2],
                        at_least_seq=least, log_replay=hist.grids[-1][:2])
        if ctx.control:
            old = stale_by_a_cell_write(hist, least)
            ctl_stale += old is not None and old not in hist.grids[least:]
    if ctx.control:
        ctx.out.say("control", what="replay with the last op withheld",
                    documents=len(compared), told_apart=caught, needed=1)
        ctx.out.say("control", what="a reply one acknowledged cell write stale",
                    reads=len(reads), told_apart=ctl_stale, needed=1)
        ctx.out.say("control", what="a grid whose cells are ahead of its axes",
                    documents=skew_made, told_apart=skew_caught, needed=1)
    stats = H.on_loop(st.srv, svc.device.stats)
    if stats["docs_with_errors"]:
        ctx.out.say("device_errors", **H.on_loop(st.srv, lambda: H.errored(svc, ctx.run_dir)))
    ctx.out.say("matrix_totals", **{
        k: v for k, v in stats.items() if k.startswith("matrix_")
    })
    return [
        ("documents_compared", len(compared), None),
        ("clients_compared", sum(len(v) for v in by_doc.values()), None),
        ("read_replies_compared", len(reads), None),
        ("read_replies_of_written_documents", on_written, None),
        ("read_replies_differ_from_replay", stale, 0),
        ("served_grid_differs_from_replay", bad_grid, 0),
        ("durable_log_faults", bad_log, 0),
        ("acked_ops_missing_from_log", missing, 0),
        ("client_grid_differs_from_served", client_differs, 0),
        ("client_ops_still_pending", pending, 0),
        ("cells_served_under_a_removed_handle", removed_served, 0),
        ("docs_with_errors", stats["docs_with_errors"], 0),
    ]
