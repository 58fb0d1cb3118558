"""What the readers of a many-writer document share: the pipeline's
always-on counts of the ticket loop and of signals
(``PipelineFluidService.stats()``: ``noops_received``, ``noops_sequenced``,
``msn_lag_sum``, ``msn_lag_count``, ``join_nacks_slots``,
``signals_received``, ``signals_delivered``) and the server's counts of
what the delivery sweep wrote to op sockets (``frames_delivered``,
``ops_delivered``, ``signals_delivered``), as numbers a window delta can
subtract. A program without them (the parent of the PR that added them)
gives no keys, and every reader of them reads nothing."""

STATS = (
    "noops_received", "noops_sequenced", "msn_lag_sum", "msn_lag_count",
    "join_nacks_slots", "signals_received", "signals_delivered",
)
SERVER = ("frames_delivered", "ops_delivered", "signals_delivered")


def snapshot(srv) -> dict:
    stats = getattr(srv.service, "stats", None)
    counts = stats() if stats is not None else {}
    if any(k not in counts for k in STATS) or any(
        not hasattr(srv, k) for k in SERVER
    ):
        return {}
    out = {f"meeting.{k}": counts[k] for k in STATS}
    out.update({f"meeting.socket.{k}": getattr(srv, k) for k in SERVER})
    return out


def sequenced(w: dict):
    """Messages the window sequenced: one ticket a message on the JSON
    wire, one a frame on the frame wire (a frame is one delivery a
    socket too). None without the counts, or with nothing sequenced."""
    n = w.get("meeting.msn_lag_count")
    return n if n else None
