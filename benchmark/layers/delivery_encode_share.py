"""Front door + pipeline stages, the delivery sweep's encode-once cache
(``network_server._drain_all``, PR 37): encode passes of the sweep
(``delivery_encodes`` of the server: a sequenced message's JSON text, a
``SeqFrame``'s binary frame, a signal's text, each built at most once a
sweep per wire format) as a share of what the sweep wrote to op sockets
(``ops_delivered`` + ``frames_delivered`` + ``signals_delivered``, one
count a socket), in percent, window deltas: 100 at a fan-out of one, 25
where four sockets share a document, 0.83 in a meeting of 120. Also says
one ``delivery_encodes`` line with the counts. A program without the
count (the parent of PR 37) gives no keys, and the reader reads nothing."""

WRITTEN = ("ops_delivered", "frames_delivered", "signals_delivered")
SERVER = ("delivery_encodes",) + WRITTEN


def snapshot(srv) -> dict:
    if any(not hasattr(srv, k) for k in SERVER):
        return {}
    return {f"delivery.{k}": getattr(srv, k) for k in SERVER}


def read(ctx):
    w = ctx.window
    if any(f"delivery.{k}" not in w for k in SERVER):
        return None
    written = {k: w[f"delivery.{k}"] for k in WRITTEN}
    deliveries = sum(written.values())
    if deliveries <= 0:
        return None
    encodes = w["delivery.delivery_encodes"]
    ctx.out.say(
        "delivery_encodes", encodes=encodes, deliveries=deliveries, **written
    )
    return 100.0 * encodes / deliveries
