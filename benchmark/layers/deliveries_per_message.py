"""Front door + pipeline stages, the delivery sweep's fan-out: sequenced
messages written to op sockets (``frames_delivered`` + ``ops_delivered``
of the server) per message the window sequenced (the ticket loop's
``msn_lag_count``): the sockets a document's message goes out to, 120 in
a meeting document. Also says one ``deliveries`` line with the counts,
the signals taken in and written out among them."""

from benchmark.layers import meeting_counts

snapshot = meeting_counts.snapshot


def read(ctx):
    w = ctx.window
    n = meeting_counts.sequenced(w)
    if n is None:
        return None
    ops = w["meeting.socket.frames_delivered"] + w["meeting.socket.ops_delivered"]
    ctx.out.say(
        "deliveries", sequenced=n, frames=w["meeting.socket.frames_delivered"],
        ops=w["meeting.socket.ops_delivered"],
        signals_received=w["meeting.signals_received"],
        signals_queued=w["meeting.signals_delivered"],
        signals_written=w["meeting.socket.signals_delivered"],
    )
    return ops / n
