"""Front door + pipeline stages, stage deli, the collab window: how far
the minimum sequence number trails the head at ticket time, in ops, mean
over the window's tickets (``msn_lag_sum`` / ``msn_lag_count``). What
the slowest writer's silence costs: tombstones of that many ops stay in
a document's rows."""

from benchmark.layers import meeting_counts

snapshot = meeting_counts.snapshot


def read(ctx):
    w = ctx.window
    n = meeting_counts.sequenced(w)
    if n is None:
        return None
    return w["meeting.msn_lag_sum"] / n
