"""What every traffic kind of the benchmark shares: the output lines, the
device check, the server under test started the way a deployment starts
it, the bulk front-door feeder, the durable-log reader, the counters'
snapshot and the traced window.

From the program this takes the system under test (``server_main``), its
counters and its read interfaces — never a yardstick. The feeder's join
recipe (``bulk_connect``) and ``on_loop`` are copies of what
``chip_smoke.py``/``bench_configs.py`` ran on the chip in PR 24.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import shutil
import sys
import time
import uuid

import numpy as np

from benchmark.reference.replay import LogOp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CHANNEL = "s"
ALPHABET = np.array(list("abcdefghijklmnopqrstuvwxyz"))
MINT_STRIDE = 1 << 14  # content ids scope to the connection (SharedString)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


class Out:
    """Every line the benchmark prints is one JSON object that names the
    device it ran on. The result line goes through ``result``."""

    def __init__(self):
        self.device = {"platform": None, "kind": None, "count": 0}
        self.t0 = time.time()

    def say(self, event: str, **kv) -> None:
        d = self.device
        print(json.dumps({
            "event": event, "at_s": round(time.time() - self.t0, 3),
            "platform": d["platform"], "device_kind": d["kind"],
            "device_count": d["count"], **kv,
        }), flush=True)

    def result(self, line: dict) -> None:
        print(json.dumps(line), flush=True)


def find_devices(out: Out, chips: int, rehearsal: bool) -> None:
    """Touch JAX once, here, and refuse anything but the chips the cell
    asks for. Only a workload marked ``rehearsal`` may run elsewhere, and
    its lines say where it ran."""
    import jax

    devs = jax.devices()
    out.device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if rehearsal:
        return
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX found {devs[0].platform!r} ({devs[0].device_kind}),"
            " not a TPU; a listed workload never falls back"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"benchmark: {len(devs)} chip(s) present, the cell needs {chips}"
        )


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileMeter:
    """Backend compilations and persistent-cache traffic, as JAX's own
    monitoring reports them."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def snapshot(self) -> dict:
        return {
            "compiles": self.compiles, "compile_s": self.seconds,
            "cache_hits": self.hits, "cache_misses": self.misses,
        }


# -- the system under test ----------------------------------------------------


def start_server(out: Out, rehearsal: bool):
    """``server_main`` as a deployment starts it: defaults, no override
    but host and port."""
    from fluidframework_tpu.service import server_main
    from fluidframework_tpu.utils.native import native_status

    native = native_status()
    out.say("native", loaded=native)
    if not rehearsal and not all(native.values()):
        raise SystemExit(f"benchmark: native libraries did not build: {native}")
    cfg = server_main.load_config(
        env={}, overrides={"host": "127.0.0.1", "port": 0}
    )
    srv = server_main.build_server(cfg)
    srv.start()
    return srv, cfg


def on_loop(srv, fn, timeout: float = 300.0):
    """Run ``fn`` on the server's event loop: the service is single-
    threaded by design, and the loop is the thread that owns it."""
    return submit_on_loop(srv, fn).result(timeout)


def submit_on_loop(srv, fn):
    async def run():
        return fn()

    return asyncio.run_coroutine_threadsafe(run(), srv._loop)


def bulk_connect(svc, doc_ids):
    """One writer connection per document through the real join path
    (a sequenced ClientJoin via deli), batched: all join records land on
    rawdeltas first, one pipeline drain sequences them, then tokens match
    up. Copy of ``bench_configs._bulk_connect`` (PR 24)."""
    from fluidframework_tpu.protocol.types import MessageType
    from fluidframework_tpu.service.lambdas import RAW_TOPIC
    from fluidframework_tpu.service.pipeline import PipelineConnection

    conns = []
    for d in doc_ids:
        token = f"c-{uuid.uuid4().hex[:12]}"
        conn = PipelineConnection(svc, d, token)
        svc.rooms.setdefault(d, []).append(conn)
        svc.log.send(RAW_TOPIC, d, {"t": "join", "mode": "write",
                                    "token": token})
        conns.append(conn)
    svc.pump()
    for conn in conns:
        for msg in conn.take_inbox():
            if (
                msg.type == MessageType.CLIENT_JOIN
                and msg.contents.get("token") == conn.token
            ):
                conn.client_id = msg.contents["clientId"]
                conn.join_seq = msg.sequence_number
                conn.conn_no = msg.contents.get("connNo", 0)
        if conn.client_id < 0:
            raise RuntimeError(f"join of {conn.doc_id} was not sequenced")
    return conns


class EditGen:
    """Single-writer editing of many documents, vectorised over the
    documents of a frame batch. An op is a one-character insert or remove
    at a position uniform in the live text, ``insert_share`` of them
    inserts; a document whose live text reaches ``cut_at`` characters gets
    one range-remove that leaves ``cut_to``. The generator knows every
    document's live length because it is the only writer."""

    def __init__(self, n_docs: int, rng, insert_share: float, cut_at: int,
                 cut_to: int):
        self.rng = rng
        self.insert_share, self.cut_at, self.cut_to = insert_share, cut_at, cut_to
        self.live = np.zeros(n_docs, np.int64)
        self.minted = np.zeros(n_docs, np.int64)

    def frames(self, sel: np.ndarray, k: int):
        """``k`` ops for each document of ``sel``: (type, pos1, pos2,
        mint ordinal or 0, letters) as [n, k] arrays."""
        from fluidframework_tpu.protocol.constants import OP_INSERT, OP_REMOVE

        n = len(sel)
        ty = np.empty((n, k), np.int32)
        p1 = np.zeros((n, k), np.int64)
        p2 = np.zeros((n, k), np.int64)
        mint = np.zeros((n, k), np.int64)
        live, minted = self.live[sel], self.minted[sel]
        u = self.rng.random((n, k))
        v = self.rng.random((n, k))
        for j in range(k):
            cut = live >= self.cut_at
            ins = ~cut & ((u[:, j] < self.insert_share) | (live == 0))
            rem = ~cut & ~ins
            # insert: position in [0, live]; remove: one character in
            # [0, live); cut: a range of live - cut_to characters.
            start = np.where(
                ins, np.floor(v[:, j] * (live + 1)),
                np.where(rem, np.floor(v[:, j] * np.maximum(live, 1)),
                         np.floor(v[:, j] * (self.cut_to + 1))),
            ).astype(np.int64)
            ty[:, j] = np.where(ins, OP_INSERT, OP_REMOVE)
            p1[:, j] = start
            p2[:, j] = np.where(
                ins, 0, np.where(rem, start + 1, start + live - self.cut_to)
            )
            minted = minted + ins
            mint[:, j] = np.where(ins, minted, 0)
            live = np.where(ins, live + 1, np.where(rem, live - 1, self.cut_to))
        self.live[sel], self.minted[sel] = live, minted
        letters = ALPHABET[self.rng.integers(0, 26, (n, k))]
        return ty, p1, p2, mint, letters


class BulkFeeder:
    """Partition-consumer ingest: one writer per document, frames through
    ``submit_frames_bulk`` on the server's loop. Like a real client it
    honours the overload envelope: a frame nacked with THROTTLING is
    offered again after its retry-after (``chip_smoke.Feeder._send``,
    generalised from lockstep to per-document counters)."""

    def __init__(self, srv, doc_ids, gen: EditGen):
        self.srv, self.svc = srv, srv.service
        self.doc_ids = list(doc_ids)
        self._index = {d: i for i, d in enumerate(self.doc_ids)}
        self.gen = gen
        n = len(self.doc_ids)
        self.conns = [None] * n
        self.clients = np.zeros(n, np.int64)
        self.heads = np.zeros(n, np.int64)
        self.join_seq = np.zeros(n, np.int64)
        self.connno = np.zeros(n, np.int64)
        self.csn = np.zeros(n, np.int64)
        self.reoffers = 0  # frames offered again after a throttle nack
        self.ops_sent = 0
        self.broadcast_ops = 0  # ops the writers' own rooms were sent back
        # Every batch built, as arrays, so that the comparison can choose
        # its documents after the window: (documents, first sequence
        # number of each, op rows, letters).
        self.batches: list = []
        self._where = None  # document -> [(batch, row of the batch)]
        self.annotate = lambda _name: contextlib.nullcontext()  # a tracer's

    def connect(self, lo: int, hi: int) -> None:
        ids = self.doc_ids[lo:hi]
        conns = on_loop(self.srv, lambda: bulk_connect(self.svc, ids))
        self.conns[lo:hi] = conns
        for i, c in enumerate(conns, lo):
            self.clients[i] = c.client_id
            self.heads[i] = self.join_seq[i] = c.join_seq
            self.connno[i] = c.conn_no

    def build(self, sel: np.ndarray, k: int) -> list:
        """The next frame of ``k`` ops for every document of ``sel``."""
        from fluidframework_tpu.protocol.constants import (
            F_ARG, F_LEN, F_POS1, F_POS2, F_REF, F_SEQ, F_TYPE, OP_INSERT,
            OP_WIDTH,
        )
        from fluidframework_tpu.protocol.opframe import OpFrame

        ty, p1, p2, mint, letters = self.gen.frames(sel, k)
        ins = ty == OP_INSERT
        rows = np.zeros((len(sel), k, OP_WIDTH), np.int32)
        rows[:, :, F_TYPE] = ty
        rows[:, :, F_POS1] = p1
        rows[:, :, F_POS2] = p2
        rows[:, :, F_LEN] = ins
        rows[:, :, F_SEQ] = self.csn[sel, None] + 1 + np.arange(k)[None, :]
        rows[:, :, F_REF] = self.heads[sel, None]
        rows[:, :, F_ARG] = np.where(
            ins, self.connno[sel, None] * MINT_STRIDE + mint, 0
        )
        items = []
        for j, i in enumerate(sel.tolist()):
            texts = tuple(letters[j][ins[j]].tolist())
            items.append(
                (self.doc_ids[i], int(self.clients[i]),
                 OpFrame(CHANNEL, rows[j], texts))
            )
        self.batches.append((sel, self.heads[sel] + 1, rows, letters))
        self._where = None
        self.csn[sel] += k
        self.heads[sel] += k
        return items

    def unbuild(self) -> None:
        """Forget the batch built last: it was never offered. Its
        documents are not edited again, and the generator's idea of their
        length is not used again."""
        sel, _first, rows, _letters = self.batches.pop()
        self._where = None
        self.csn[sel] -= rows.shape[1]
        self.heads[sel] -= rows.shape[1]

    def sent_ops(self, i: int) -> list:
        """Every op sent to document ``i``, in order, as the durable log
        should hold it."""
        from fluidframework_tpu.protocol.constants import (
            F_ARG, F_POS1, F_POS2, F_REF, F_SEQ, F_TYPE, OP_INSERT,
        )

        if self._where is None:
            self._where = {}
            for b, batch in enumerate(self.batches):
                for j, d in enumerate(batch[0].tolist()):
                    self._where.setdefault(d, []).append((b, j))
        ops = []
        for b, j in self._where.get(i, ()):
            _sel, first, rows, letters = self.batches[b]
            for n, r in enumerate(rows[j].tolist()):
                if r[F_TYPE] == OP_INSERT:
                    contents = {"k": "ins", "pos": r[F_POS1],
                                "text": str(letters[j][n]), "orig": r[F_ARG]}
                else:
                    contents = {"k": "rem", "start": r[F_POS1],
                                "end": r[F_POS2]}
                ops.append(LogOp(
                    seq=int(first[j]) + n, ref=r[F_REF],
                    client=int(self.clients[i]), csn=r[F_SEQ], msn=r[F_REF],
                    contents=contents,
                ))
        return ops

    def offer(self, items: list):
        """Offer the frames on the server's loop; returns the future of
        (frames nacked for throttling, longest retry-after)."""
        from fluidframework_tpu.protocol.types import NackErrorType

        svc, conns_of = self.svc, self._conn_of

        def go():
            with self.annotate("bench.submit_frames_bulk"):
                svc.submit_frames_bulk(items)
            again, wait = [], 0.0
            for item in items:
                conn = conns_of(item[0])
                for m in conn.inbox:  # a real room's sockets drain
                    self.broadcast_ops += getattr(m, "n", 1)
                conn.inbox.clear()
                if conn.nacks:
                    for nack in conn.nacks:
                        if nack.error_type != NackErrorType.THROTTLING:
                            raise RuntimeError(f"{item[0]}: nacked: {nack}")
                        wait = max(wait, nack.retry_after_s)
                    conn.nacks.clear()
                    again.append(item)
            return again, wait

        return submit_on_loop(self.srv, go)

    def _conn_of(self, doc_id: str):
        return self.conns[self._index[doc_id]]

    def land(self, items: list, fut=None) -> None:
        """Offer until the front door took every frame of the batch."""
        n_ops = sum(it[2].n for it in items)
        pending = items
        for _ in range(400):
            again, wait = (fut or self.offer(pending)).result(900)
            fut = None
            if not again:
                self.ops_sent += n_ops
                return
            pending = again
            self.reoffers += len(again)
            time.sleep(min(max(wait, 0.005), 0.5))
        raise RuntimeError("feeder: frames still throttled after 400 offers")

    def load(self, k: int, chunk: int, say) -> None:
        """Join every document and give it its first ``k`` ops."""
        n = len(self.doc_ids)
        for lo in range(0, n, chunk):
            hi = min(n, lo + chunk)
            self.connect(lo, hi)
            self.land(self.build(np.arange(lo, hi), k))
            say("load", docs=hi, of=n)


def read_log(svc, doc_id: str) -> tuple:
    """(head, [LogOp]) of one document: every sequenced message of the
    durable log 1..head, read through the program's own log reader."""
    from fluidframework_tpu.protocol.types import MessageType
    from fluidframework_tpu.service.lambdas import stored_message

    head = svc.doc_head(doc_id)
    ops = []
    for _lo, _hi, obj in svc.log_entries(doc_id, 1, head):
        msgs = obj.messages() if hasattr(obj, "messages") else [
            stored_message(obj)
        ]
        for m in msgs:
            env = m.contents
            mine = (
                m.type == MessageType.OPERATION and isinstance(env, dict)
                and env.get("address") == CHANNEL
            )
            ops.append(LogOp(
                seq=m.sequence_number, ref=m.reference_sequence_number,
                client=m.client_id, csn=m.client_sequence_number,
                msn=m.minimum_sequence_number,
                contents=env["contents"] if mine else None,
            ))
    return head, ops


def served_text(srv, doc: str) -> str:
    """What the device serves for one document, read in this process on
    the server's loop (a cell whose window times no read entry)."""
    return on_loop(srv, lambda: srv.service.device.text(doc, CHANNEL))


def rest_text(srv, doc: str) -> str:
    """The same through the REST read entry that ``read_p95_ms`` times."""
    from urllib.error import HTTPError

    from fluidframework_tpu.drivers.network_driver import NetworkFluidService

    service = NetworkFluidService("127.0.0.1", srv.port)
    for _ in range(30):
        try:
            return service.get_channel_text(doc, CHANNEL)
        except HTTPError as e:
            # A read shed by the overload envelope: come back after its
            # Retry-After, as the window's readers do.
            if e.code != 503:
                raise
            time.sleep(float(e.headers.get("Retry-After") or 1.0))
    raise RuntimeError(f"the REST read entry sheds every read of {doc}")


def log_and_replay(ctx, srv, doc: str):
    """One document's durable log and the log's replay by the reference:
    (head, log, text after each sequence number, acknowledged pairs).
    None, and a line saying why, when the log breaks its own guarantees."""
    from benchmark.reference.replay import LogFault, replay

    head, log = on_loop(srv, lambda: read_log(srv.service, doc))
    try:
        texts, acked, _ = replay(log, head, every=True)
    except LogFault as e:
        ctx.out.say("log_fault", doc=doc, error=str(e))
        return None
    return head, log, texts, acked


def errored(svc, run_dir: str) -> dict:
    """Which documents the device flagged and with which error bits; the
    whole log of each goes to a file of the run's directory: what a run
    that was not correct leaves to go by."""
    dev, out = svc.device, {}
    for idx in sorted(getattr(dev, "_errored", ())):
        doc, _address = dev._keys[idx]
        _head, log = read_log(svc, doc)
        path = os.path.join(run_dir, f"errored_{doc}.json")
        with open(path, "w") as f:
            json.dump([list(op) for op in log], f)
        out[doc] = {"err": int(dev._doc_state(idx).err), "log": path}
    return {"documents": out}


def control_caught(log: list, head: int, served: str) -> bool:
    """The comparison's control on one document: the reference put in
    the program's place with the log's last channel op withheld. True
    when the comparison tells that text from the served one."""
    from benchmark.reference.replay import replay

    n = sum(op.contents is not None for op in log)
    text, _, _ = replay(log, head, withhold=n - 1)
    return text != served


def counters(srv, readers=()) -> dict:
    """The program's counters, taken on the loop in one turn of it."""
    return on_loop(srv, lambda: counters_now(srv, readers))


def counters_now(srv, readers=()) -> dict:
    """``counters`` for a caller that is on the server's loop already:
    what the harness itself prints and waits on, and whatever each layer
    reader's own ``snapshot(srv)`` asks for (numbers only; two readers
    that name the same key read the same counter)."""
    from fluidframework_tpu.parallel import aot

    dev = srv.service.device
    c = dict(
        pump_dispatches=dev.pump_dispatches, ops_applied=dev.ops_applied,
        migrations=dev.fleet.migrations, demotions=dev.fleet.demotions,
        aot_builds=aot.stats()["builds"], aot_calls=aot.stats()["calls"],
        frames_received=srv.frames_received, t=time.perf_counter(),
        reads_shed=srv.reads_shed, connections_refused=srv.connections_refused,
        overload_tier_transitions=sum(
            srv.service.overload.transition_counts().values()
        ),
    )
    for reader in readers:
        if hasattr(reader, "snapshot"):
            c.update(reader.snapshot(srv))
    return c


def aot_keys() -> set:
    """The shape keys of the step and compaction programs built so far:
    which of them a window built is the difference of two of these."""
    from fluidframework_tpu.parallel import aot

    return {str(key) for key in aot._ENTRIES}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def settle(srv) -> None:
    """Everything buffered reaches the device and the device finishes:
    ``flush_device`` and a ``block_until_ready`` on every pool."""
    import jax

    def go():
        srv.service.flush_device()
        return [p.state for p in srv.service.device.fleet.pools.values()]

    jax.block_until_ready(on_loop(srv, go))


def pool_shape(srv, capacity: int) -> tuple:
    pool = srv.service.device.fleet.pools[capacity]
    return pool.n_slots, pool.capacity


# -- the traced window ----------------------------------------------------------


class Tracer:
    """A profiler trace of a part of the window, taken by the process that
    holds the chip. ``--trace 0`` makes every method a no-op."""

    def __init__(self, on: bool, out_dir: str, srv, readers=()):
        self.on, self.dir, self.srv, self.readers = on, out_dir, srv, readers
        self.before = self.after = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        shutil.rmtree(self.dir, ignore_errors=True)  # one trace, the newest
        self.before = counters(self.srv, self.readers)
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        if not self.on:
            return
        import jax

        jax.profiler.stop_trace()
        self.after = counters(self.srv, self.readers)

    def annotate(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


def zipf_cdf(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return np.cumsum(w) / np.sum(w)


def draw_distinct(rng, cdf: np.ndarray, perm: np.ndarray, k: int) -> np.ndarray:
    """``k`` distinct documents, drawn one after another from the Zipf
    weights without replacement (repeats are drawn again)."""
    got: dict = {}
    while len(got) < k:
        for r in np.searchsorted(cdf, rng.random(2 * k)).tolist():
            got.setdefault(r, None)
            if len(got) == k:
                break
    return perm[np.fromiter(got, np.int64, k)]


def fail(msg: str):
    print(msg, file=sys.stderr, flush=True)
    raise SystemExit(1)
