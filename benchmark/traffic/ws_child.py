"""A child of the ``ws_edit`` traffic kind: websocket writers and the REST
reader, in a process that never touches the chip.

The parent starts it with ``JAX_PLATFORMS=cpu`` in its environment (the
clients' own replicas are JAX programs, and the chip belongs to the
server). It holds real ``ContainerRuntime`` + ``SharedString`` clients on
``NetworkFluidService``, so positions and refSeqs are what a client
computes under concurrency. One scheduler thread walks the frames in
due-time order (open loop, latency taken from the due time); arrival of a
writer's own sequenced ops is stamped on its connection's reader thread.

Arrivals: the frames of all writers together are one Poisson process at
the mix's rate, and its realisation is fixed (``ARRIVALS``), so every run
offers the same instants and the same number of frames. ``--seed`` deals
each instant to a writer, uniformly, which makes every writer's own stream
Poisson too and another one in each run, and it picks the documents and
the edits. A writer's turn (take in the others' ops, make the edits) is
Python that a real client spends microseconds on, so it is done ``LEAD_S``
ahead of the frame's due time and the frame goes on the wire at that time:
``ack`` then times the service and not this library's turn, and what is
left shows as ``gen_late``.

Protocol: one JSON spec on the first line of stdin; ``{"ready": ...}`` on
stdout; then commands, one JSON object a line: ``{"cmd": "go", "at": t}``
starts the schedule, ``{"cmd": "window", "at": t, "seconds": s}`` marks the
measured span, after which the child drains, prints ``{"done": ...}`` and
exits. Times are ``time.monotonic()``, which processes of one machine
share.
"""

from __future__ import annotations

import heapq
import json
import os
import queue
import sys
import threading
import time
from http.client import HTTPException
from urllib.error import HTTPError

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CHANNEL = "s"
ALPHABET = "abcdefghijklmnopqrstuvwxyz"
ARRIVALS = 20260927  # the one realisation of the aggregate arrival process
LEAD_S = 0.02  # a writer's turn is prepared this long before its frame is due
READER_THREADS = 4  # a slow read delays only what is due on its own thread
PREP, SEND = 0, 1  # what an entry of the schedule asks for


def make_connection_class():
    from fluidframework_tpu.drivers.network_driver import NetworkConnection
    from fluidframework_tpu.protocol.types import MessageType

    class StampedConnection(NetworkConnection):
        """Stamps, on the reader thread, when each of this connection's
        own ops came back sequenced."""

        def __init__(self, *a, **kw):
            self.acked_at: dict = {}
            super().__init__(*a, **kw)

        def _ingest(self, m) -> None:
            if (
                m.client_id == self.client_id
                and m.type == MessageType.OPERATION
                and m.sequence_number > self.join_seq > 0
            ):
                self.acked_at.setdefault(
                    m.client_sequence_number, time.monotonic()
                )
            super()._ingest(m)

    return StampedConnection


def make_service_class():
    from fluidframework_tpu.drivers.network_driver import NetworkFluidService

    stamped = make_connection_class()

    class StampedService(NetworkFluidService):
        def connect(self, doc_id: str, mode: str = "write", from_seq: int = 0):
            return stamped(
                self.host, self.port, doc_id, self.tenant, "", mode, from_seq,
                push=self.push,
            )

    return StampedService


class Paced(Exception):
    """A throttle nack's retry-after, raised out of the client's nack
    loop before it changed anything."""

    def __init__(self, seconds: float):
        super().__init__(seconds)
        self.seconds = seconds


def _paced(seconds: float) -> None:
    raise Paced(seconds)


def _no_sleep(_seconds: float) -> None:
    return None


class Frame:
    """One op frame of a writer: when it was due, the clientSequenceNumber
    of its last op, when it went out and when it came back."""

    __slots__ = ("due", "csn", "sent", "ack", "regenerated")

    def __init__(self, due: float, csn: int, sent: float):
        self.due, self.csn, self.sent = due, csn, sent
        self.ack = None
        self.regenerated = False  # a throttle nack made the client resend it


class Writer:
    def __init__(self, rt, doc: str):
        self.rt, self.doc = rt, doc
        self.ch = rt.get_channel(CHANNEL)
        self.frames = []  # every Frame sent
        self.open = []  # those not yet acknowledged
        self.prepared = None  # due time of a frame edited and not yet sent
        self.nacked_until = 0.0
        self.nacks = 0


def percentile(values, q: float):
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, int(q * len(s)))]


class Child:
    def __init__(self, spec: dict):
        import numpy as np

        self.spec = spec
        self.np = np
        self.rng = np.random.default_rng([spec["seed"], 2, spec["index"]])
        service = make_service_class()(spec["host"], spec["port"])
        from fluidframework_tpu.models.shared_string import SharedString
        from fluidframework_tpu.runtime.container import ContainerRuntime

        self.reader_service = service
        self.writers = []
        for doc in spec["docs"]:
            for _ in range(spec["writers_per_doc"]):
                rt = ContainerRuntime(
                    service, doc, channels=(SharedString(CHANNEL),)
                )
                self.writers.append(Writer(rt, doc))
        self.commands: queue.Queue = queue.Queue()
        self.reads = []  # [due, latency or None]
        self.window = None  # (t0, t1)
        self.stop_reads = threading.Event()
        self.next_note = 0.0

    # -- editing ---------------------------------------------------------------

    def edit(self, w: Writer) -> None:
        """One frame's ``ops_per_frame`` edits against the writer's own
        view; ``send`` puts them on the frame wire."""
        s, rng = self.spec, self.rng
        live = len(w.ch.get_text())
        for _ in range(s["ops_per_frame"]):
            if live >= s["cut_at"]:
                start = int(rng.integers(0, s["cut_to"] + 1))
                w.ch.remove_range(start, start + live - s["cut_to"])
                live = s["cut_to"]
            elif live == 0 or rng.random() < s["insert_share"]:
                w.ch.insert_text(
                    int(rng.integers(0, live + 1)),
                    ALPHABET[int(rng.integers(0, 26))],
                )
                live += 1
            else:
                start = int(rng.integers(0, live))
                w.ch.remove_range(start, start + 1)
                live -= 1

    def take_in(self, w: Writer):
        """The writer takes in what has arrived for it. Returns None, or
        the time to come back at while the writer is throttled: a
        throttle nack met here is paced by the caller's schedule, not
        slept out on this thread, and after the wait the client's nack
        loop runs on. The client regenerates its whole pending tail then,
        under clientSequenceNumbers it uses again and maybe with fewer
        ops, so the frames in flight are acknowledged when the pending
        queue drains, not by their own numbers."""
        now = time.monotonic()
        if now < w.nacked_until:
            return w.nacked_until
        w.rt.throttle_sleep = _no_sleep if w.nacked_until else _paced
        try:
            w.rt.process_incoming()
        except Paced as p:
            w.nacks += 1
            w.nacked_until = now + p.seconds
            for f in w.open:
                f.regenerated = True
            return w.nacked_until
        w.nacked_until = 0.0
        self.settle(w)
        return None

    def prepare(self, w: Writer, due: float):
        """The writer's turn for the frame due at ``due``. Returns None
        when the frame is ready to send, or the time to come back at
        while the writer is throttled."""
        back_at = self.take_in(w)
        if back_at is None:
            self.edit(w)
            w.prepared = due
        return back_at

    def send(self, w: Writer) -> None:
        """The turn-end flush: the prepared frame goes on the wire."""
        w.rt.flush()
        frame = Frame(w.prepared, w.rt.client_seq, time.monotonic())
        w.prepared = None
        w.frames.append(frame)
        w.open.append(frame)

    def settle(self, w: Writer) -> None:
        """Match open frames with the stamps of the reader thread."""
        acked = w.rt.connection.acked_at
        still = []
        drained = not w.rt.pending
        for f in w.open:
            if f.regenerated:  # acknowledged when the pending queue drains
                if drained:
                    f.ack = time.monotonic()
                else:
                    still.append(f)
            elif f.csn in acked:
                f.ack = acked[f.csn]
            else:
                still.append(f)
        w.open = still

    # -- the reader --------------------------------------------------------------

    def read_loop(self, t_go: float) -> None:
        """One of the reader threads: they share the fixed-rate schedule,
        so a slow read delays only what is due on its own thread."""
        s, np = self.spec, self.np
        period = 1.0 / s["reads_per_s"]
        while not self.stop_reads.is_set():
            with self.read_lock:
                k = self.read_k
                self.read_k += 1
                doc = f"d{int(self.read_perm[int(np.searchsorted(self.read_cdf, self.read_rng.random()))])}"
            due = t_go + k * period
            if self.window is not None and due >= self.window[1]:
                return  # every read due inside the window has been asked
            delay = due - time.monotonic()
            if delay > 0 and self.stop_reads.wait(delay):
                return
            # The reply is kept with the instants between which it was
            # asked for and had arrived: the comparison holds it to them.
            # A 503 with Retry-After is the service's back-pressure on
            # reads (shed, or the socket refused): the client comes back
            # after it, as a writer does after a throttle nack, and the
            # read counts once, its latency from the due time. Anything
            # else is a failed read.
            rec = {"due": due, "doc": doc, "asked": None, "done": None,
                   "text": None, "shed": 0}
            give_up = None
            while True:
                rec["asked"] = time.monotonic()
                try:
                    rec["text"] = self.reader_service.get_channel_text(doc, CHANNEL)
                    rec["done"] = time.monotonic()
                    break
                except HTTPError as e:
                    print(json.dumps({"read_error": repr(e), "doc": doc,
                                      "late_s": time.monotonic() - due}),
                          file=sys.stderr, flush=True)
                    if e.code != 503:
                        break
                    rec["shed"] += 1
                    pause = float(e.headers.get("Retry-After") or 1.0)
                except (OSError, HTTPException) as e:
                    print(json.dumps({"read_error": repr(e), "doc": doc,
                                      "late_s": time.monotonic() - due}),
                          file=sys.stderr, flush=True)
                    break
                if give_up is None and self.window is not None:
                    give_up = self.window[1] + s["drain_seconds"]
                if give_up is not None and time.monotonic() + pause > give_up:
                    break
                if self.stop_reads.wait(pause):
                    break
            self.reads.append(rec)

    def start_readers(self, t_go: float) -> list:
        s, np = self.spec, self.np
        self.read_rng = np.random.default_rng([s["seed"], 3])
        n = s["resident_documents"]
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s["zipf_s"]
        self.read_cdf = np.cumsum(w) / np.sum(w)
        # Popularity follows the editing: the documents being written take
        # the hottest ranks (in the seed's order), the rest of the fleet
        # the ranks below, so a good share of the reads meets live writes.
        written = [int(d[1:]) for d in s["written_docs"]]
        rest = np.setdiff1d(np.arange(n), written)
        self.read_perm = np.concatenate(
            [self.read_rng.permutation(written), self.read_rng.permutation(rest)]
        )
        self.read_lock, self.read_k = threading.Lock(), 0
        threads = [
            threading.Thread(target=self.read_loop, args=(t_go,), daemon=True)
            for _ in range(READER_THREADS)
        ]
        for t in threads:
            t.start()
        return threads

    # -- the schedule --------------------------------------------------------------

    def arrivals(self, phase: int, t0: float):
        """(due, writer) of this child's frames from ``t0`` on: the
        aggregate process's fixed realisation, each instant dealt to one
        of all the writers by the seed. Every child walks the same stream
        and keeps its own."""
        s, np = self.spec, self.np
        gaps = np.random.default_rng([ARRIVALS, phase])
        deal = np.random.default_rng([s["seed"], 4, phase])
        mean, total, n = 1.0 / s["frames_per_s"], s["total_writers"], s["children"]
        t = t0
        while True:
            t += float(gaps.exponential(mean))
            g = int(deal.integers(total))
            if g % n == s["index"]:
                yield t, g // n

    def run(self) -> dict:
        threading.Thread(target=self._stdin, daemon=True).start()
        print(json.dumps({"ready": len(self.writers)}), flush=True)
        cmd = self.commands.get()
        assert cmd["cmd"] == "go", cmd
        self.t_go = t_go = cmd["at"]
        # The schedule: the stream's next frame, whose turn comes LEAD_S
        # ahead of its due time, and a heap of (when, tie, what, writer,
        # due) for the turns and sends that wait: a send at its due time,
        # a turn again once a throttle or the writer's last frame is out.
        stream, phase = self.arrivals(0, t_go), 0
        nxt = next(stream)
        heap, tie, end = [], 0, None
        readers = (
            self.start_readers(t_go) if self.spec["reads_per_s"] > 0 else []
        )
        while True:
            if end is None:
                try:
                    cmd = self.commands.get_nowait()
                    assert cmd["cmd"] == "window", cmd
                    self.window = (cmd["at"], cmd["at"] + cmd["seconds"])
                    end = self.window[1]
                except queue.Empty:
                    pass
            if phase == 0 and end is not None and nxt[0] >= self.window[0]:
                # The warm-up's stream ends where the window's starts.
                stream, phase = self.arrivals(1, self.window[0]), 1
                nxt = next(stream)
            if phase == 1 and nxt[0] >= end:
                nxt = (float("inf"), -1)  # the window's last frame is dealt
            if heap and heap[0][0] <= nxt[0] - LEAD_S:
                when, _, what, i, due = heap[0]
                waiting = True
            else:
                when, what, i, due = nxt[0] - LEAD_S, PREP, nxt[1], nxt[0]
                waiting = False
            if end is not None and when >= end and (
                not heap or when >= end + self.spec["drain_seconds"]
            ):
                # The window's last frame is out. A writer that a throttle
                # holds back past the window's end still has its frame
                # due: it is offered when the throttle lets it, and is
                # failed only if that never comes.
                break
            now = time.monotonic()
            if now >= self.next_note:
                self.note(now - t_go)
            if when > now:
                time.sleep(min(when - now, 0.05))
                continue
            if waiting:
                heapq.heappop(heap)
            else:
                nxt = next(stream)
            w = self.writers[i]
            tie += 1
            if what == PREP and phase == 0:
                # Warming up, a writer waits for its last frame to come
                # back before it sends the next: while the server's loop
                # builds its programs (ten seconds at a stretch) an open
                # loop would pile frames up behind it, the front door would
                # throttle them, and the clients' resubmission of several
                # frames in flight at once is where replicas were seen to
                # part (PERF.md section 7). The window itself is open loop.
                self.settle(w)
                if w.open:
                    continue
            if what == SEND:
                self.send(w)
            elif w.prepared is not None:
                # Its last frame is still to go out: the turn follows it.
                heapq.heappush(heap, (w.prepared, tie, PREP, i, due))
            else:
                back_at = self.prepare(w, due)
                if back_at is None:
                    heapq.heappush(heap, (due, tie, SEND, i, due))
                else:
                    heapq.heappush(heap, (back_at, tie, PREP, i, due))
        # Frames whose turn waited on a throttle to the last were due and
        # never sent.
        self.never_sent = [it[4] for it in heap if it[2] == PREP]
        for w in self.writers:
            if w.prepared is not None:
                self.send(w)
        # The readers end with the window's last read, or with the last
        # retry of one that the service shed.
        give_up = self.window[1] + self.spec["drain_seconds"] + 15.0
        for t in readers:
            t.join(max(0.0, give_up - time.monotonic()))
        self.stop_reads.set()
        return self.drain_and_report()

    def note(self, at: float) -> None:
        """Progress on stderr, every few seconds: what the writers sent,
        what came back, and whether their sockets still stand."""
        self.next_note = time.monotonic() + 5.0
        ws = self.writers
        print(json.dumps({
            "child": self.spec["index"], "at_s": round(at, 2),
            "frames_sent": sum(len(w.frames) for w in ws),
            "frames_open": sum(len(w.open) for w in ws),
            "pending_ops": sum(len(w.rt.pending) for w in ws),
            "connected": sum(bool(w.rt.connected) for w in ws),
            "sockets_closed": sum(bool(w.rt.connection.closed) for w in ws),
            "nacks": sum(w.nacks for w in ws), "reads": len(self.reads),
        }), file=sys.stderr, flush=True)

    def _stdin(self) -> None:
        for line in sys.stdin:
            if line.strip():
                self.commands.put(json.loads(line))

    def drain_and_report(self) -> dict:
        s = self.spec
        deadline = time.monotonic() + s["drain_seconds"]
        while time.monotonic() < deadline:
            busy = False
            for w in self.writers:
                if self.take_in(w) is not None:
                    busy = True  # a throttle nack of its last frames
                    continue
                busy = busy or bool(w.open) or bool(w.rt.pending)
            if not busy:
                break
            time.sleep(0.01)
        # Quiet: every writer takes in what the others sent last, until
        # the writers of each document stand at one sequence number.
        calm = 0
        while calm < 2 and time.monotonic() < deadline + 5.0:
            time.sleep(0.1)
            at: dict = {}
            for w in self.writers:
                self.take_in(w)
                at.setdefault(w.doc, set()).add(w.rt.ref_seq)
            calm = calm + 1 if all(len(v) == 1 for v in at.values()) else 0
        t0, t1 = self.window
        lat, late, failed, attempted, nacked = [], [], 0, 0, 0
        half = [[], []]
        for w in self.writers:
            for f in w.frames:
                if not (t0 <= f.due < t1):
                    continue
                attempted += 1
                nacked += f.regenerated
                late.append(1e3 * (f.sent - f.due))
                if f.ack is None:
                    failed += 1
                else:
                    lat.append(1e3 * (f.ack - f.due))
                    half[f.due >= (t0 + t1) / 2].append(1e3 * (f.ack - f.due))
        for due in self.never_sent:
            if t0 <= due < t1:
                attempted += 1
                failed += 1
        reads = [r for r in self.reads if t0 <= r["due"] < t1]
        # A read due in the window and never asked for (the readers were
        # all held up when it closed) was attempted and failed.
        reads_due = 0
        if s["reads_per_s"] > 0:
            period = 1.0 / s["reads_per_s"]
            reads_due = sum(
                t0 <= self.t_go + k * period < t1
                for k in range(int((t1 - self.t_go) / period) + 2)
            )
        writers = []
        for w in self.writers:
            conn = w.rt.connection
            writers.append({
                "doc": w.doc, "client": conn.client_id,
                "join_seq": conn.join_seq,
                # clientSequenceNumber -> when it came back sequenced
                "acked": sorted(conn.acked_at.items()),
                "text": w.ch.get_text(),
                "pending": len(w.rt.pending), "nacks": w.nacks,
            })
            w.rt.disconnect()
        return {
            "frames_attempted": attempted, "frames_failed": failed,
            "frames_regenerated_after_nack": nacked,
            "ack_ms": lat, "late_ms": late,
            "ack_p50_first_half_ms": percentile(half[0], 0.5),
            "ack_p50_second_half_ms": percentile(half[1], 0.5),
            "read_ms": [
                1e3 * (r["done"] - r["due"]) for r in reads
                if r["done"] is not None
            ],
            "reads_shed": sum(r["shed"] for r in reads),
            "reads_attempted": max(reads_due, len(reads)),
            "reads_failed": max(reads_due, len(reads))
            - sum(r["done"] is not None for r in reads),
            "reads": reads, "writers": writers,
        }


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    from fluidframework_tpu.utils import enable_compile_cache

    enable_compile_cache()
    t0 = time.monotonic()
    child = Child(spec)
    print(json.dumps({"connected_s": time.monotonic() - t0}), file=sys.stderr)
    report = child.run()
    print(json.dumps({"done": report}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
