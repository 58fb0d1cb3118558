"""Front door + pipeline stages, the delivery sweep's writes
(``network_server._drain_all``, PR 45): messages the sweep wrote to op
sockets (``ops_delivered`` + ``frames_delivered`` + ``signals_delivered``
of the server, one count a socket) per write that carried them
(``socket_writes``: one ``_deliver`` a session a sweep, everything the
sweep found queued on the connection), window deltas: 1.0 where every
message is a ``send`` of its own, 2.0 where an op and its signal always
leave together, 9.0 for a filled table row found by one sweep. Also says
one ``socket_writes`` line with the writes, the messages and the sessions
the sweep passed over because they held nothing (``sessions_passed``). A
program without the count (the parent of PR 45) gives no keys, and the
reader reads nothing."""

from benchmark.layers.delivery_encode_share import WRITTEN as MESSAGES

SERVER = ("socket_writes", "sessions_passed") + MESSAGES


def snapshot(srv) -> dict:
    if any(not hasattr(srv, k) for k in SERVER):
        return {}
    return {f"writes.{k}": getattr(srv, k) for k in SERVER}


def read(ctx):
    w = ctx.window
    if any(f"writes.{k}" not in w for k in SERVER):
        return None
    writes = w["writes.socket_writes"]
    if writes <= 0:
        return None
    messages = sum(w[f"writes.{k}"] for k in MESSAGES)
    ctx.out.say(
        "socket_writes", writes=writes, messages=messages,
        sessions_passed=w["writes.sessions_passed"],
    )
    return messages / writes
