"""Micro-benchmark of the delivery sweep, CPU only: the real server's
``_drain_all`` over loopback sockets that another process reads, in the
shapes of the websocket cells. Argument: the checkout to import (a
``git archive`` of the parent, or the change's). Prints one JSON line.

    python3 tools/sweep_micro.py <checkout>

- ``meeting``: 120 sessions of one document; a sweep carries one op and
  one signal to all of them;
- ``table``: 128 sessions, four to a document; a sweep carries one op, or
  a filled row's nine, to the four sockets of one document and passes
  the other 124;
- ``idle``: nothing queued, over 128 and over 1,024 sessions: the slope is
  what one idle session costs a sweep, the rest the sweep's fixed part.

Host only (JAX is held to the CPU, the service has no device backend):
how PERF.md's PR 45 times of a sweep and of an idle session were taken."""
import json
import os
import select
import socket
import statistics
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, sys.argv[1])
from fluidframework_tpu.protocol.types import DocumentMessage, MessageType  # noqa: E402
from fluidframework_tpu.service.network_server import (  # noqa: E402
    FluidNetworkServer,
    _Session,
)
from fluidframework_tpu.service.pipeline import PipelineFluidService  # noqa: E402


class Writer:  # what the transport's write comes to: one send a write
    def __init__(self, sock):
        self.sock, self.sends = sock, 0

    def write(self, data):
        self.sends += 1
        if self.sock is not None:
            self.sock.sendall(data)


def sockets(n):
    """``n`` server ends of loopback TCP connections, and the process
    that reads their other ends until they close."""
    lst = socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(n)
    pairs = []
    for _ in range(n):
        b = socket.create_connection(lst.getsockname())
        a, _ = lst.accept()
        a.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        pairs.append((a, b))
    lst.close()
    pid = os.fork()
    if pid == 0:
        for a, _ in pairs:
            a.close()
        live = {b.fileno(): b for _, b in pairs}
        while live:
            ready, _, _ = select.select(list(live.values()), [], [], 5.0)
            for b in ready:
                if not b.recv(1 << 16):
                    del live[b.fileno()]
                    b.close()
        os._exit(0)
    for _, b in pairs:
        b.close()
    return [a for a, _ in pairs], pid


def server(socks, per_doc):
    svc = PipelineFluidService(n_partitions=1, device_backend=False)
    srv = FluidNetworkServer(svc)
    for i, sock in enumerate(socks):
        s = _Session(Writer(sock))
        doc = f"doc-{i // per_doc}"
        s.conn, s.doc_id = svc.connect(doc), doc
        srv._sessions.append(s)
    srv._drain_all()
    return srv


def timed(srv, ops, signal, n=400):
    svc, conn = srv.service, srv._sessions[0].conn
    out = []
    csn = getattr(conn, "_micro_csn", 0)
    for _ in range(n):
        for _ in range(ops):
            csn += 1
            conn.submit(DocumentMessage(
                client_sequence_number=csn,
                reference_sequence_number=svc.doc_head(conn.doc_id),
                type=MessageType.OPERATION,
                contents={"address": "m", "contents": {
                    "type": "cell", "row": 3, "col": 5, "value": csn}},
            ))
        if signal:
            conn.submit_signal({"cursor": csn, "who": "writer"})
        svc.pump()
        t0 = time.perf_counter()
        srv._drain_all()
        out.append(time.perf_counter() - t0)
        if ops or signal:
            time.sleep(0.002)  # the reader catches up
    conn._micro_csn = csn
    return out


def shape(srv, ops, signal):
    timed(srv, ops, signal, 20)
    w0 = sum(s.writer.sends for s in srv._sessions)
    ts = timed(srv, ops, signal)
    writes = (sum(s.writer.sends for s in srv._sessions) - w0) / len(ts)
    q = statistics.quantiles(ts, n=4)
    return {
        "sweep_us_median": 1e6 * statistics.median(ts),
        "sweep_us_q1": 1e6 * q[0], "sweep_us_q3": 1e6 * q[2],
        "writes_a_sweep": writes,
    }


res = {"tree": sys.argv[1]}
socks, pid = sockets(120)
srv = server(socks, 120)
res["meeting op+signal"] = shape(srv, 1, 1)
res["meeting op"] = shape(srv, 1, 0)
for a in socks:
    a.close()
os.waitpid(pid, 0)
socks, pid = sockets(128)
srv = server(socks, 4)
res["table 1 op"] = shape(srv, 1, 0)
res["table 9 ops"] = shape(srv, 9, 0)
res["idle 128"] = shape(srv, 0, 0)
for a in socks:
    a.close()
os.waitpid(pid, 0)
big = server([None] * 1024, 4)
res["idle 1024"] = shape(big, 0, 0)
res["us_an_idle_session"] = (
    res["idle 1024"]["sweep_us_median"] - res["idle 128"]["sweep_us_median"]
) / (1024 - 128)
print(json.dumps(res))
