"""Front door + pipeline stages, stage deli, noop consolidation: noops
that took a sequence number (a client's immediate one or the server's
consolidated one) as a share of the messages the window sequenced, in
percent. Also says one ``noops`` line: the client noops deli took in
without sequencing them (the collab-window heartbeat's) beside the
sequenced ones."""

from benchmark.layers import meeting_counts

snapshot = meeting_counts.snapshot


def read(ctx):
    w = ctx.window
    n = meeting_counts.sequenced(w)
    if n is None:
        return None
    ctx.out.say(
        "noops", sequenced_messages=n, received=w["meeting.noops_received"],
        sequenced=w["meeting.noops_sequenced"],
    )
    return 100.0 * w["meeting.noops_sequenced"] / n
