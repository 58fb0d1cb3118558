"""A child of the ``ws_meeting`` traffic kind: this child's share of every
meeting document's websocket writers (writer k of a document lives in child
k mod children), and in child 0 the REST reader, in a process that never
touches the chip.

It is ``ws_child.Child`` (the same clients, edits, arrivals, acknowledgement
stamps, throttle pacing, reader and report) with what a meeting adds:

- every writer takes in what has arrived for it all the time, not only at
  its own turns: between two entries of the schedule the loop walks the
  writers round robin, one ``process_incoming`` at a time with a look at
  the clock after each, a round every ``poll_s``. A writer of a meeting
  sends once in six seconds and receives twenty ops a second, so this is
  where its replica follows the document and where its collab-window
  heartbeat (the client's own, ``ContainerRuntime``) finds its noops due;
- with every frame a writer sends ``signals_per_op`` signals a frame op;
- during the warm-up a seeded share of each document's writers drop their
  socket and rejoin (``drop_connection`` + ``reconnect``), a document's one
  after another in an order every child knows, none sooner than
  ``rejoin_gap_s`` after the last. A document of 120 writers has four
  writer slots to spare and a left slot is free again only when the MSN has
  passed its leave, so a rejoin also waits until the one before it is
  through and all but ``UNRECYCLED`` of the leaves so far are under the MSN,
  both as one of this child's writers of the document has read them off
  the stream: then a slot is free whatever the other children do. (A
  connect refused for now would be asked again after the pause the server
  names, by the driver's own patience; but a child that waits holds its
  writers' heartbeats back, and the comparison counts the refusal.)
- after the window's last frame no child can tell on its own that a
  document has come to rest, since the document's writers live in every
  child: it says ``sent`` once its own writers' frames have all come back,
  the parent reads each document's head off the server when every child
  has said so, and the child takes in up to that head before it reports
  its writers' texts (``settle_up``).
"""

from __future__ import annotations

import heapq
import json
import os
import queue
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.traffic import ws_child  # noqa: E402
from benchmark.traffic.ws_child import (  # noqa: E402
    LEAD_S, PREP, SEND, Writer, percentile,
)

REJOIN = 2  # a third thing an entry of the schedule can ask for
UNRECYCLED = 2  # leaves above the MSN a rejoin may find: spare slots less two


class Watch:
    """What one writer of a document has read off its stream since the
    schedule began: the joins, and the sequence numbers of the leaves."""

    def __init__(self, rt):
        from fluidframework_tpu.protocol.types import MessageType

        self.rt, self.joins, self.leaves = rt, 0, []
        self._join, self._leave = (
            MessageType.CLIENT_JOIN, MessageType.CLIENT_LEAVE,
        )
        rt.add_op_listener(self.saw)

    def saw(self, msg) -> None:
        if msg.type == self._join:
            self.joins += 1
        elif msg.type == self._leave:
            self.leaves.append(msg.sequence_number)

    def reset(self) -> None:
        self.joins, self.leaves = 0, []

    def clear_for(self, j: int) -> bool:
        """Whether the document's rejoin number ``j`` may go: the ``j``
        before it are through, and few enough of their leaves are still
        above the MSN for a writer slot to be free."""
        msn = self.rt.min_seq
        return (
            self.joins == j and len(self.leaves) == j
            and sum(seq > msn for seq in self.leaves) <= UNRECYCLED
        )


def make_service_class():
    stamped_service = ws_child.make_service_class()

    class MeetingService(stamped_service):
        """The stamped connections, dialled with the driver's patience: a
        join refused for want of a writer slot is asked again."""

        def connect(self, doc_id: str, mode: str = "write", from_seq: int = 0):
            dial = lambda: stamped_service.connect(self, doc_id, mode, from_seq)
            patiently = getattr(self, "_connect_patiently", None)
            return dial() if patiently is None else patiently(dial)

    return MeetingService


class Child(ws_child.Child):
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
                    service, doc, channels=(SharedString(ws_child.CHANNEL),)
                )
                self.writers.append(Writer(rt, doc))
        self.commands: queue.Queue = queue.Queue()
        self.reads = []
        self.window = None
        self.stop_reads = threading.Event()
        self.next_note = 0.0
        # The round-robin poll: where the round stands, when it began,
        # how long the rounds took.
        self.cursor, self.round_at = len(self.writers), 0.0
        self.round_ms: list = []
        self.polls = 0
        self.signals_sent = self.signals_received = self.rejoins = 0
        per = spec["writers_per_doc"]
        self.watches = [
            Watch(self.writers[d * per].rt) for d in range(len(spec["docs"]))
        ]

    # -- what a meeting adds ------------------------------------------------------

    def poll(self, w: Writer) -> None:
        """The writer takes in what has arrived; a frame edited and not
        yet sent waits for its send (taking in flushes)."""
        if w.prepared is None:
            self.take_in(w)
            self.polls += 1
        conn = w.rt.connection
        if conn.signals:
            self.signals_received += len(conn.signals)
            del conn.signals[:]

    def idle(self, until: float) -> None:
        """Until ``until``: poll rounds, a round every ``poll_s``; the
        loop sleeps what a round leaves."""
        ws, poll_s = self.writers, self.spec["poll_s"]
        while True:
            now = time.monotonic()
            if now >= until:
                return
            if self.cursor >= len(ws):
                nxt = self.round_at + poll_s
                if now < nxt:
                    time.sleep(min(until, nxt) - now)
                    continue
                if self.round_at:
                    self.round_ms.append(1e3 * (self.last_polled - self.round_at))
                self.cursor, self.round_at = 0, now
            self.poll(ws[self.cursor])
            self.cursor += 1
            self.last_polled = time.monotonic()

    def send(self, w: Writer) -> None:
        super().send(w)
        for _ in range(self.spec["signals_per_op"] * self.spec["ops_per_frame"]):
            try:
                w.rt.connection.submit_signal({"cursor": len(w.frames)})
                self.signals_sent += 1
            except OSError:
                break

    def rejoin(self, w: Writer, d: int, j: int) -> bool:
        """Rejoin number ``j`` of document ``d``: drop the socket and join
        again under a new client id. False while the document is not
        clear for it, or the writer has a frame out or edited: the turn
        comes back."""
        if not self.watches[d].clear_for(j):
            return False
        self.settle(w)
        if w.open or w.prepared is not None or w.rt.pending:
            return False
        if time.monotonic() < w.nacked_until:
            return False
        w.rt.drop_connection()
        w.rt.reconnect()
        self.rejoins += 1
        return True

    def settle_up(self) -> None:
        """After the window's last frame: take in until this child's own
        frames have all come back, say so, and then take in up to the
        head the parent names for each document, which it reads off the
        server once EVERY child has said so: by then every op of the
        window is sequenced at or under that head, whichever child sent
        it and however long the server's loop or a throttle held it.
        (Waiting for a quiet second and a half instead let a child report
        its texts while another child's last frames stood behind a stall
        of the server's loop across the window's end: such a run read
        ``client_text_differs_from_served`` with no op failed.)"""
        wait = self.spec["drain_seconds"]
        give_up = time.monotonic() + wait
        while time.monotonic() < give_up:
            for w in self.writers:
                self.poll(w)
            if not any(w.open or w.rt.pending for w in self.writers):
                break
            time.sleep(0.02)
        print(json.dumps({"sent": sum(len(w.frames) for w in self.writers)}),
              flush=True)
        heads, give_up = None, time.monotonic() + 2 * wait + 60.0
        while time.monotonic() < give_up:
            for w in self.writers:
                self.poll(w)
            if heads is None:
                try:
                    cmd = self.commands.get_nowait()
                    assert cmd["cmd"] == "finish", cmd
                    heads, give_up = cmd["heads"], time.monotonic() + wait
                except queue.Empty:
                    pass
            elif all(w.rt.ref_seq >= heads[w.doc] for w in self.writers):
                return
            time.sleep(0.02)

    def rejoin_schedule(self, t_go: float) -> list:
        """(when, index of the writer here, document, number in the
        document's order) of this child's rejoins: of every document the
        same seeded writers in the same order in every child, one every
        ``rejoin_gap_s`` at the soonest, each child keeping its own."""
        s, np = self.spec, self.np
        n, per = s["children"], s["writers_per_doc"]
        out = []
        for d, _doc in enumerate(s["docs"]):
            order = np.random.default_rng([s["seed"], 6, d]).permutation(
                s["meeting_writers"]
            )[: s["rejoins_per_doc"]]
            for j, k in enumerate(order.tolist()):
                if k % n == s["index"]:
                    when = t_go + s["rejoin_start_s"] + j * s["rejoin_gap_s"]
                    out.append((when, d * per + k // n, d, j))
        return out

    # -- the schedule (``ws_child.Child.run`` with the poll in its waits) ----------

    def run(self) -> dict:
        threading.Thread(target=self._stdin, daemon=True).start()
        print(json.dumps({"ready": len(self.writers)}), flush=True)
        cmd = self.commands.get()
        assert cmd["cmd"] == "go", cmd
        self.t_go = t_go = cmd["at"]
        stream, phase = self.arrivals(0, t_go), 0
        nxt = next(stream)
        heap, tie, end = [], 0, None
        for w in self.writers:  # what came before the schedule is read
            self.poll(w)
        for watch in self.watches:
            watch.reset()
        for when, i, d, j in self.rejoin_schedule(t_go):
            tie += 1
            heap.append((when, tie, REJOIN, i, (d, j)))
        heapq.heapify(heap)
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
                stream, phase = self.arrivals(1, self.window[0]), 1
                nxt = next(stream)
            if phase == 1 and nxt[0] >= end:
                nxt = (float("inf"), -1)
            if heap and heap[0][0] <= nxt[0] - LEAD_S:
                when, _, what, i, due = heap[0]
                waiting = True
            else:
                when, what, i, due = nxt[0] - LEAD_S, PREP, nxt[1], nxt[0]
                waiting = False
            if end is not None and when >= end and (
                not heap or when >= end + self.spec["drain_seconds"]
            ):
                break
            now = time.monotonic()
            if now >= self.next_note:
                self.note(now - t_go)
            if when > now:
                self.idle(min(when, now + 0.05))
                continue
            if waiting:
                heapq.heappop(heap)
            else:
                nxt = next(stream)
            w = self.writers[i]
            tie += 1
            if what == REJOIN:
                if not self.rejoin(w, *due):
                    heapq.heappush(heap, (now + 0.05, tie, REJOIN, i, due))
                continue
            if what == PREP and phase == 0:
                # Warming up, a writer waits for its last frame to come
                # back before it sends the next (``ws_child``).
                self.settle(w)
                if w.open:
                    continue
            if what == SEND:
                self.send(w)
            elif w.prepared is not None:
                heapq.heappush(heap, (w.prepared, tie, PREP, i, due))
            else:
                back_at = self.prepare(w, due)
                if back_at is None:
                    heapq.heappush(heap, (due, tie, SEND, i, due))
                else:
                    heapq.heappush(heap, (back_at, tie, PREP, i, due))
        self.never_sent = [it[4] for it in heap if it[2] == PREP]
        for w in self.writers:
            if w.prepared is not None:
                self.send(w)
        give_up = self.window[1] + self.spec["drain_seconds"] + 15.0
        for t in readers:
            t.join(max(0.0, give_up - time.monotonic()))
        self.stop_reads.set()
        self.settle_up()
        report = self.drain_and_report()
        report.update(
            signals_sent=self.signals_sent,
            signals_received=self.signals_received
            + sum(len(w.rt.connection.signals) for w in self.writers),
            rejoins=self.rejoins, polls=self.polls,
            poll_round_p95_ms=percentile(self.round_ms, 0.95) or 0.0,
            connect_retries=getattr(self.reader_service, "connect_retries", 0),
            heartbeat_noops=sum(
                getattr(w.rt, "heartbeat_noops", 0) for w in self.writers
            ),
        )
        return report


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
