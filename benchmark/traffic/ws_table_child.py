"""A child of the ``ws_table`` traffic kind: websocket writers of shared
tables and, in child 0, the REST reader of their grids, in a process that
never touches the chip.

It is ``ws_child.Child`` (the same sockets, arrivals, acknowledgement
stamps, throttle pacing, reader threads and report; a child holds whole
documents with all their writers) with real ``ContainerRuntime`` +
``SharedMatrix`` clients instead of ``SharedString`` ones:

- a frame is one user action, flushed as one batch: by the mix's weights
  set one cell (one op), insert one row and fill its cells (1 + columns
  ops), insert one column, remove one row, remove one column (one op
  each), at positions uniform in the writer's own view. Every writer keeps
  its table inside the mix's band (no insert at or over the upper edge of
  an axis, no remove at or under the lower one), so that neither axis of a
  document reaches the promotion mark of its 128-row tier;
- the reader asks for grids (``GET /documents/:id/channels/:cid`` answers
  a matrix channel with ``{"grid": ...}``) and keeps each reply with the
  instants between which it was asked for and had arrived;
- after the window's last frame it says ``sent`` once its own frames have
  all come back, and takes in up to the head the parent then names for
  each of its documents before it reports its writers' grids: no timer
  decides what is compared (PERF.md section 6, PR 36).
"""

from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time
from urllib.request import urlopen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.traffic import ws_child  # noqa: E402
from benchmark.traffic.ws_child import CHANNEL, Writer  # noqa: E402

ALPHABET = ws_child.ALPHABET
CELL, INSROW, INSCOL, REMROW, REMCOL = range(5)


def get_grid(host: str, port: int, doc: str, view: str = "") -> dict:
    """The REST channel read, as JSON: ``{"grid": ...}`` for a table."""
    q = f"?view={view}" if view else ""
    url = f"http://{host}:{port}/documents/{doc}/channels/{CHANNEL}{q}"
    with urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def make_service_class():
    stamped = ws_child.make_service_class()

    class TableService(stamped):
        """The stamped connections; the reader's entry replies grids."""

        def get_channel_text(self, doc_id: str, channel_id: str):
            return get_grid(self.host, self.port, doc_id)["grid"]

    return TableService


def make_table_class():
    from fluidframework_tpu.models.shared_matrix import SharedMatrix

    class Table(SharedMatrix):
        def get_text(self) -> str:  # ``ws_child`` reports a writer's text
            return ""

    return Table


class Child(ws_child.Child):
    def __init__(self, spec: dict):
        import numpy as np

        from fluidframework_tpu.runtime.container import ContainerRuntime

        self.spec = spec
        self.np = np
        self.rng = np.random.default_rng([spec["seed"], 2, spec["index"]])
        service = make_service_class()(spec["host"], spec["port"])
        table = make_table_class()
        self.reader_service = service
        self.writers = []
        for doc in spec["docs"]:
            for _ in range(spec["writers_per_doc"]):
                rt = ContainerRuntime(service, doc, channels=(table(CHANNEL),))
                self.writers.append(Writer(rt, doc))
        self.commands: queue.Queue = queue.Queue()
        self.reads = []
        self.window = None
        self.stop_reads = threading.Event()
        self.next_note = 0.0
        w = np.asarray(spec["action_weights"], np.float64)
        self.action_cdf = np.cumsum(w) / w.sum()
        self.actions = [0] * 5
        self.ops_sent = 0

    def value(self) -> str:
        n = int(self.rng.integers(1, 7))
        return "".join(ALPHABET[int(i)] for i in self.rng.integers(0, 26, n))

    def edit(self, w: Writer) -> None:
        """One user action against the writer's own view of the table."""
        s, rng, m = self.spec, self.rng, w.ch
        rows, cols = m.row_count, m.col_count
        act = int(self.np.searchsorted(self.action_cdf, rng.random()))
        # The band: a structural op that would leave it turns into the op
        # that leads back, so the table's size wanders inside.
        if act == INSROW and rows >= s["rows_max"]:
            act = REMROW
        elif act == REMROW and rows <= s["rows_min"]:
            act = INSROW
        elif act == INSCOL and cols >= s["cols_max"]:
            act = REMCOL
        elif act == REMCOL and cols <= s["cols_min"]:
            act = INSCOL
        if rows == 0 or cols == 0:
            act = INSROW if rows == 0 else INSCOL
        self.actions[act] += 1
        if act == CELL:
            m.set_cell(
                int(rng.integers(0, rows)), int(rng.integers(0, cols)),
                self.value(),
            )
            self.ops_sent += 1
        elif act == INSROW:
            at = int(rng.integers(0, rows + 1))
            m.insert_rows(at, 1)
            for c in range(cols):
                m.set_cell(at, c, self.value())
            self.ops_sent += 1 + cols
        elif act == INSCOL:
            m.insert_cols(int(rng.integers(0, cols + 1)), 1)
            self.ops_sent += 1
        elif act == REMROW:
            m.remove_rows(int(rng.integers(0, rows)), 1)
            self.ops_sent += 1
        else:
            m.remove_cols(int(rng.integers(0, cols)), 1)
            self.ops_sent += 1

    def settle_up(self) -> None:
        """After the window's last frame: take in until this child's own
        frames have all come back, say so, then take in up to the head the
        parent names for each document (read off the server once EVERY
        child has said so)."""
        wait = self.spec["drain_seconds"]
        give_up = time.monotonic() + wait
        while time.monotonic() < give_up:
            for w in self.writers:
                self.take_in(w)
            if not any(w.open or w.rt.pending for w in self.writers):
                break
            time.sleep(0.02)
        print(json.dumps({"sent": sum(len(w.frames) for w in self.writers)}),
              flush=True)
        heads, give_up = None, time.monotonic() + 2 * wait + 60.0
        while time.monotonic() < give_up:
            for w in self.writers:
                self.take_in(w)
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

    def drain_and_report(self) -> dict:
        self.settle_up()
        report = super().drain_and_report()
        for rec, w in zip(report["writers"], self.writers):
            rec["grid"] = w.ch.to_list()
            rec["handle_pulls"] = [w.ch._rows.pulls, w.ch._cols.pulls]
        report.update(actions=self.actions, ops_sent=self.ops_sent)
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
