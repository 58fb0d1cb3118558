"""Traffic kind ``bulk_ingest``: partition-consumer ingest from a standing
backlog (closed loop).

The path a deli host's Kafka consumer takes: batches of frames go through
``submit_frames_bulk`` on the server's loop, the next batch offered as soon
as the front door took the last one. Every parameter is data (the mix's
file): batch size, ops per frame, the Zipf constant, the insert share and
the text bounds.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness as H
from benchmark.reference.replay import replay


class State:
    pass


def setup(ctx) -> State:
    p, cfg = ctx.params, ctx.config
    st = State()
    st.srv, st.server_cfg = H.start_server(ctx.out, ctx.rehearsal)
    n = cfg["resident_documents"]
    rng = np.random.default_rng([ctx.seed, 1])
    st.rng = rng
    st.perm = rng.permutation(n)  # Zipf rank -> document
    st.cdf = H.zipf_cdf(n, p["zipf_s"])
    gen = H.EditGen(n, rng, p["insert_share"], p["cut_at"], p["cut_to"])
    st.feeder = H.BulkFeeder(st.srv, [f"d{i}" for i in range(n)], gen)
    st.feeder.load(
        cfg["assumed"]["ops_per_document_at_load"], p["load_chunk"],
        ctx.out.say,
    )
    H.settle(st.srv)
    return st


def _batch(st, p) -> list:
    sel = H.draw_distinct(st.rng, st.cdf, st.perm, p["frames_per_batch"])
    return st.feeder.build(sel, p["ops_per_frame"])


def warm(ctx, st) -> None:
    """The cell's own traffic until no program is built any more: past
    two compaction cadences with the AOT builds and JAX's compile count
    flat."""
    p = ctx.params
    flat, n = 0, 0
    while flat < p["warm_flat_batches"]:
        before = (H.counters(st.srv)["aot_builds"], ctx.meter.compiles)
        st.feeder.land(_batch(st, p))
        n += 1
        after = (H.counters(st.srv)["aot_builds"], ctx.meter.compiles)
        flat = flat + 1 if after == before else 0
        if n > p["warm_max_batches"]:
            raise RuntimeError("warm-up: programs are still being built")
    H.settle(st.srv)
    ctx.out.say("warm", batches=n)


def run(ctx, st, seconds: float, tracer) -> dict:
    """The window: offer batches back to back for ``seconds``, then wait
    until every op is applied on the device (inside the timed span)."""
    p, feeder = ctx.params, st.feeder
    ops0, re0, bc0 = feeder.ops_sent, feeder.reoffers, feeder.broadcast_ops
    trace_at, trace_s = p["trace_after_s"], p["trace_seconds"]
    feeder.annotate = tracer.annotate
    st.window_first_batch = len(feeder.batches)
    nxt = _batch(st, p)
    t0 = time.perf_counter()
    tracing = False
    landed = []  # (seconds into the window, ops the front door had taken)
    while True:
        now = time.perf_counter() - t0
        landed.append((now, feeder.ops_sent - ops0))
        if now >= seconds:
            break
        if tracer.on and not tracing and tracer.before is None and now >= trace_at:
            tracer.start()
            tracing = True
        if tracing and now >= trace_at + trace_s:
            tracer.stop()
            tracing = False
        fut = feeder.offer(nxt)
        cur = nxt
        nxt = _batch(st, p)  # 0.6 ms of work; no span: it waits for the GIL
        with tracer.annotate("bench.wait_front_door"):
            feeder.land(cur, fut)
    with tracer.annotate("bench.settle"):
        H.settle(st.srv)
    window_s = time.perf_counter() - t0
    if tracing:
        tracer.stop()
    feeder.unbuild()  # the batch built ahead was never offered
    ops = feeder.ops_sent - ops0
    return {
        "window_s": window_s, "attempted": ops, "failed": 0,
        "metrics": {"ops_per_s": ops / window_s},
        "notes": {
            "batches": ops // (p["frames_per_batch"] * p["ops_per_frame"]),
            "throttle_reoffers": feeder.reoffers - re0,
            "broadcast_ops": feeder.broadcast_ops - bc0,
            # the rate in each fifth of the window: a drift shows here
            "ops_per_s_by_fifth": _by_fifth(landed, seconds),
        },
    }


def _by_fifth(landed: list, seconds: float) -> list:
    rates, k = [], 0
    for n in range(1, 6):
        lo = landed[k]
        while k < len(landed) - 1 and landed[k][0] < n * seconds / 5:
            k += 1
        span = landed[k][0] - lo[0]
        rates.append((landed[k][1] - lo[1]) / span if span > 0 else None)
    return rates


def compared_documents(ctx, st) -> np.ndarray:
    """The documents compared, chosen once the window has closed: the
    hottest ranks, a larger draw by the traffic's own Zipf weights (the
    middle ranks that take most of the window's ops) and a cold tail,
    all from the seed."""
    p, n = ctx.params, len(st.perm)
    rng = np.random.default_rng([ctx.seed, 5])
    head = int(p["verify_head"])
    picked = dict.fromkeys(st.perm[:head].tolist())
    by_weight = H.draw_distinct(
        rng, st.cdf, st.perm, head + int(p["verify_weighted"])
    )
    for d in by_weight.tolist():
        if len(picked) < head + int(p["verify_weighted"]):
            picked.setdefault(d)
    rest = np.setdiff1d(st.perm, np.fromiter(picked, np.int64))
    tail = rng.choice(rest, min(int(p["verify_tail"]), len(rest)), replace=False)
    return np.concatenate([np.fromiter(picked, np.int64), tail])


def verify(ctx, st) -> list:
    """Every number compared, beside its limit."""
    svc, f = st.srv.service, st.feeder
    checks = []
    bad_text = bad_log = missing = caught = 0
    watch = compared_documents(ctx, st)
    n_docs = len(watch)
    in_window = set()
    for sel, *_ in f.batches[st.window_first_batch:]:
        in_window.update(sel.tolist())
    for i in watch.tolist():
        doc = f.doc_ids[i]
        served = H.served_text(st.srv, doc)
        got = H.log_and_replay(ctx, st.srv, doc)
        if got is None:
            bad_log += 1
            continue
        head, log, texts, acked = got
        from_log = texts[-1]
        sent = f.sent_ops(i)
        # The sent ops replayed on their own: the reference's answer
        # without anything the program made but the join's numbers.
        first = int(f.join_seq[i]) + 1
        want, _, _ = replay(sent, first + len(sent) - 1, first=first)
        logged = [op for op in log if op.contents is not None]
        same = len(logged) == len(sent) and all(
            a.contents == b.contents and a.seq == first + n
            and (a.client, a.csn, a.ref) == (b.client, b.csn, b.ref)
            for n, (a, b) in enumerate(zip(logged, sent))
        )
        bad_log += not same
        missing += sum((op.client, op.csn) not in acked for op in sent)
        if not (served == want == from_log):
            bad_text += 1
            ctx.out.say("text_mismatch", doc=doc, served=served[:64],
                        sent_replay=want[:64], log_replay=from_log[:64])
        if ctx.control:
            caught += H.control_caught(log, head, served)
    if ctx.control:
        ctx.out.say("control", what="replay with the last op withheld",
                    documents=n_docs, told_apart=caught, needed=1)
    stats = H.on_loop(st.srv, svc.device.stats)
    if stats["docs_with_errors"]:
        ctx.out.say("device_errors", **H.on_loop(st.srv, lambda: H.errored(svc, ctx.run_dir)))
    checks.append(("documents_compared", n_docs, None))
    checks.append(("documents_compared_edited_in_window",
                   sum(i in in_window for i in watch.tolist()), None))
    checks.append(("served_text_differs_from_replay", bad_text, 0))
    checks.append(("durable_log_differs_from_sent", bad_log, 0))
    checks.append(("acked_ops_missing_from_log", missing, 0))
    checks.append(("docs_with_errors", stats["docs_with_errors"], 0))
    checks.append(
        ("ops_sent_vs_broadcast", abs(f.ops_sent - f.broadcast_ops), 0)
    )
    checks.append(("ops_sent_vs_applied_on_device",
                   abs(f.ops_sent - stats["ops_applied"]), 0))
    return checks


def teardown(ctx, st) -> None:
    st.srv.stop()
