#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path still starts
on the chip.

One process, the entry points a user calls, real widths:

    python chip_smoke.py            # one chip: device, kernels, serve,
                                    # tiers, tree, scrape
    python chip_smoke.py --chips 4  # the mesh-sharded path and what it is
                                    # compared with, nothing else

Every phase prints one JSON line (sizes, seconds, compile seconds, cache
hits, counters) and raises on the first thing that is wrong, so a failed
phase ends the run with a traceback and no result line. The LAST stdout
line of a passing run is ``{"ok": true, "device": {...}}`` with the device
as JAX reports it. Without a TPU the ``device`` phase fails: nothing here
downgrades to the CPU.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
import urllib.request

import numpy as np

ALPHABET = "abcdefghijklmnopqrstuvwxyz"
CHANNEL = "s"
# Pallas kernels are compiled by Mosaic here, never interpreted. (A CPU
# rehearsal of this script's control flow flips it from a scratch driver.)
INTERPRET = False


def emit(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


class CompileMeter:
    """Counts what JAX's own monitoring reports: seconds spent obtaining
    executables (compiling, or reading the persistent cache), and the
    persistent cache's hits and misses."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def run_phase(meter: CompileMeter, name: str, fn, *args):
    s0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    out = fn(*args)  # a record, or (record, what later phases need)
    s1, h1, m1 = meter.snapshot()
    rec = out if isinstance(out, dict) else out[0]
    emit(
        name, **rec, seconds=round(time.perf_counter() - t0, 2),
        compile_seconds=round(s1 - s0, 2), cache_hits=h1 - h0,
        cache_misses=m1 - m0,
    )
    return out


# -- device -------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    import importlib.metadata as md

    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: device phase FAILED: JAX found {d.platform!r} "
            f"({d.device_kind}), not a TPU — this script never downgrades"
        )
    if len(devs) != chips:
        raise SystemExit(
            f"chip_smoke: device phase FAILED: {len(devs)} TPU device(s) "
            f"present, this run needs exactly {chips}"
        )

    def ver(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return None

    return {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs),
        "jax": jax.__version__, "jaxlib": ver("jaxlib"),
        "libtpu": ver("libtpu"),
    }


# -- kernels ------------------------------------------------------------------


def _fuzz_streams(seed: int, scripts: int, n_ops: int):
    """``scripts`` distinct err-free acked streams + the oracles that
    evolved with them."""
    from fluidframework_tpu.protocol.constants import NO_CLIENT
    from fluidframework_tpu.testing.fuzz import random_acked_stream
    from fluidframework_tpu.testing.oracle import OracleDoc

    payloads: dict = {}
    oracles = [OracleDoc(NO_CLIENT) for _ in range(scripts)]
    streams = np.stack([
        np.stack(random_acked_stream(
            np.random.default_rng(seed + d), n_ops, payloads, oracles[d],
            msn_lag=24, caught_up=True,
        ))
        for d in range(scripts)
    ]).astype(np.int32)
    return streams, oracles, payloads


def _packed_parity(tables, scalars, docs, oracles, payloads) -> int:
    """Texts of ``docs`` (device gather, one readback) vs their oracles;
    returns the mismatch count."""
    import jax.numpy as jnp

    from fluidframework_tpu.ops.pallas_kernel import unpack_state
    from fluidframework_tpu.ops.segment_state import SegmentState, materialize

    idx = jnp.asarray(np.asarray(docs, np.int32))
    st = unpack_state(tables[:, idx], scalars[idx])
    host = SegmentState(*[np.asarray(x) for x in st])
    bad = 0
    for j, d in enumerate(docs):
        one = SegmentState(*[x[j] for x in host])
        if materialize(one, payloads) != oracles[d % len(oracles)].text(
            payloads
        ):
            bad += 1
    return bad


def phase_kernels(seed: int, n_docs: int = 32768, top_cap: int = 32768) -> dict:
    """The three compiled kernels at 32,768 docs x 256 rows x K=64 —
    state equal to the pure-Python oracle — plus the fleet's top tier,
    and what this machine's dispatch and ``block_until_ready`` do."""
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops.pallas_compact import (
        apply_compact_packed,
        compact_packed,
    )
    from fluidframework_tpu.ops.pallas_kernel import (
        SC_ERR,
        apply_ops_packed,
        pack_state,
    )
    from fluidframework_tpu.ops.segment_state import make_batched_state
    from fluidframework_tpu.protocol.constants import NO_CLIENT

    cap, k, scripts = 256, 64, 8
    streams, oracles, payloads = _fuzz_streams(seed, scripts, 2 * k)
    reps = n_docs // scripts
    first = jax.device_put(np.tile(streams[:, :k], (reps, 1, 1)))
    second = jax.device_put(np.tile(streams[:, k:], (reps, 1, 1)))
    tables, scalars = pack_state(make_batched_state(n_docs, cap, NO_CLIENT))
    jax.block_until_ready((tables, scalars, first, second))

    def build(fn, *args):
        t0 = time.perf_counter()
        exe = fn.lower(*args, block_docs=32, interpret=INTERPRET).compile()
        return exe, round(time.perf_counter() - t0, 2)

    # Built twice from ONE call site (a Mosaic body carries its source
    # locations, so the line is part of the cache key), JAX's in-memory
    # caches dropped in between: the second has to come out of the
    # persistent cache.
    built = []
    for again in (False, True):
        if again:
            jax.clear_caches()
        hits0 = METER.hits
        built.append(build(apply_ops_packed, tables, scalars, first))
        cache_hit = METER.hits > hits0
    (apply_exe, apply_compile_s), (_, apply_rebuild_s) = built
    assert cache_hit, "kernels: the rebuilt AOT entry missed the persistent cache"

    # Is block_until_ready honest here? Enqueue, wait, then read a few
    # bytes: if the wait really waited, the read that follows is short.
    t0 = time.perf_counter()
    tables, scalars = apply_exe(tables, scalars, first)
    enqueue_s = time.perf_counter() - t0
    jax.block_until_ready((tables, scalars))
    waited_s = time.perf_counter() - t0
    np.asarray(scalars)  # 1 MB, no new program
    read_after_s = time.perf_counter() - t0 - waited_s
    bur_waits = enqueue_s < 0.5 * waited_s and read_after_s < 0.5 * waited_s

    compact_exe, compact_compile_s = build(compact_packed, tables, scalars)
    t0 = time.perf_counter()
    tables, scalars = compact_exe(tables, scalars)
    jax.block_until_ready((tables, scalars))
    compact_s = time.perf_counter() - t0

    fused_exe, fused_compile_s = build(
        apply_compact_packed, tables, scalars, second
    )
    t0 = time.perf_counter()
    tables, scalars = fused_exe(tables, scalars, second)
    jax.block_until_ready((tables, scalars))
    fused_s = time.perf_counter() - t0

    errs = int(jnp.sum(scalars[:, SC_ERR] != 0))
    rng = np.random.default_rng(seed)
    sample = sorted(
        set(range(scripts))
        | set(int(x) for x in rng.integers(0, n_docs, 16))
        | set(range(n_docs - scripts, n_docs))
    )
    mismatches = _packed_parity(tables, scalars, sample, oracles, payloads)
    # Every block computed what block 0 did (doc d replays stream d % 8).
    uniform = bool(jnp.all(
        tables.reshape(tables.shape[0], reps, scripts, cap)
        == tables[:, None, :scripts, :]
    ))
    assert errs == 0 and mismatches == 0 and uniform, (
        f"kernels: {errs} docs with errors, {mismatches}/{len(sample)} "
        f"sampled docs differ from the oracle, uniform={uniform}"
    )

    # The fleet's top tier: 8 docs of 32,768 rows, the block the rule
    # gives it (the tier needs most of VMEM; see pallas_kernel.doc_block).
    top_k = 8
    t_tables, t_scalars = pack_state(
        make_batched_state(scripts, top_cap, NO_CLIENT)
    )
    t_streams, t_oracles, t_payloads = _fuzz_streams(seed, scripts, top_k)
    t_ops = jax.device_put(t_streams)
    top_exe, top_compile_s = build(apply_ops_packed, t_tables, t_scalars, t_ops)
    t0 = time.perf_counter()
    t_tables, t_scalars = top_exe(t_tables, t_scalars, t_ops)
    jax.block_until_ready((t_tables, t_scalars))
    top_s = time.perf_counter() - t0
    top_bad = _packed_parity(
        t_tables, t_scalars, list(range(scripts)), t_oracles, t_payloads
    )
    top_errs = int(jnp.sum(t_scalars[:, SC_ERR] != 0))
    assert top_bad == 0 and top_errs == 0, (top_bad, top_errs)

    # Dispatch round trip: a trivial jitted call on fresh inputs.
    bump = jax.jit(lambda x: x + 1)
    x = jax.device_put(np.zeros(8, np.int32))
    bump(x).block_until_ready()
    rtt, rtt_read = [], []
    for _ in range(200):
        t0 = time.perf_counter()
        x = bump(x)
        x.block_until_ready()
        rtt.append(time.perf_counter() - t0)
    for _ in range(200):
        t0 = time.perf_counter()
        x = bump(x)
        np.asarray(x)
        rtt_read.append(time.perf_counter() - t0)

    return {
        "shape": f"{n_docs}x{cap}xK{k}", "pallas_interpret": INTERPRET,
        "docs_with_errors": errs, "oracle_sampled_docs": len(sample),
        "oracle_mismatches": mismatches, "all_blocks_uniform": uniform,
        "apply_compile_s": apply_compile_s,
        "apply_rebuild_s": apply_rebuild_s,
        "persistent_cache_hit_on_rebuild": cache_hit,
        "compact_compile_s": compact_compile_s,
        "fused_compile_s": fused_compile_s,
        "apply_enqueue_ms": round(1e3 * enqueue_s, 3),
        "apply_block_until_ready_ms": round(1e3 * waited_s, 3),
        "read_after_wait_ms": round(1e3 * read_after_s, 3),
        "block_until_ready_waits": bur_waits,
        "compact_ms": round(1e3 * compact_s, 3),
        "fused_apply_compact_ms": round(1e3 * fused_s, 3),
        "top_tier_shape": f"{scripts}x{top_cap}xK{top_k}",
        "top_tier_compile_s": top_compile_s,
        "top_tier_apply_ms": round(1e3 * top_s, 3),
        "top_tier_oracle_mismatches": top_bad,
        "dispatch_rtt_ms_median": round(1e3 * float(np.median(rtt)), 4),
        "dispatch_readback_rtt_ms_median": round(
            1e3 * float(np.median(rtt_read)), 4
        ),
    }


# -- serve --------------------------------------------------------------------


def on_loop(srv, fn, timeout: float = 900.0):
    """Run ``fn`` on the server's event loop: the service is single-
    threaded by design, and the loop is the thread that owns it."""

    async def run():
        return fn()

    return asyncio.run_coroutine_threadsafe(run(), srv._loop).result(timeout)


def _bulk_connect(svc, doc_ids):
    """One writer connection per document through the real join path
    (a sequenced ClientJoin via deli), batched: every join record lands
    on rawdeltas first, ONE pipeline drain sequences them all, then the
    tokens are matched up. ``svc.connect()`` pumps the whole pipeline per
    call, O(docs^2) stage sweeps at fleet scale."""
    import uuid

    from fluidframework_tpu.protocol.types import MessageType
    from fluidframework_tpu.service.lambdas import RAW_TOPIC
    from fluidframework_tpu.service.pipeline import PipelineConnection

    conns = {}
    for d in doc_ids:
        token = f"c-{uuid.uuid4().hex[:12]}"
        conn = PipelineConnection(svc, d, token)
        svc.rooms.setdefault(d, []).append(conn)
        svc.log.send(RAW_TOPIC, d, {"t": "join", "mode": "write",
                                    "token": token})
        conns[d] = conn
    svc.pump()
    for d, conn in conns.items():
        for msg in conn.take_inbox():
            if (
                msg.type == MessageType.CLIENT_JOIN
                and msg.contents.get("token") == conn.token
            ):
                conn.client_id = msg.contents["clientId"]
                conn.join_seq = msg.sequence_number
                conn.conn_no = msg.contents.get("connNo", 0)
        assert conn.client_id >= 0, d
    return conns


class Feeder:
    """Bulk frame traffic for a set of docs in lockstep, through the real
    join path and the bulk frame front door: every op inserts one
    character at position 0. Like a real client it honours the overload
    envelope: a frame nacked with THROTTLING is offered again after its
    retry-after. ``call`` runs a function on the thread that owns the
    service."""

    def __init__(self, call, svc, doc_ids):
        self.call, self.svc = call, svc
        self.doc_ids = list(doc_ids)
        conns = call(lambda: _bulk_connect(svc, self.doc_ids))
        self.conns = conns
        self.clients = [conns[d].client_id for d in self.doc_ids]
        n = len(self.doc_ids)
        self.heads = np.fromiter(
            (conns[d].join_seq for d in self.doc_ids), np.int64, n
        )
        self.connno = np.fromiter(
            (conns[d].conn_no for d in self.doc_ids), np.int64, n
        )
        self.sent = 0  # inserts per doc so far (lockstep)
        self.csn = np.zeros(n, np.int64)  # last clientSequenceNumber
        self.throttled = 0  # frames re-offered after a throttle nack

    def _send(self, rows_all, texts, sel) -> None:
        from fluidframework_tpu.protocol.opframe import OpFrame
        from fluidframework_tpu.protocol.types import NackErrorType

        docs, clients, conns = self.doc_ids, self.clients, self.conns

        def go(pending):
            self.svc.submit_frames_bulk(
                (docs[sel[j]], clients[sel[j]],
                 OpFrame(CHANNEL, rows_all[j], texts))
                for j in pending
            )
            again, wait = [], 0.0
            for j in pending:
                conn = conns[docs[sel[j]]]
                conn.inbox.clear()  # a real room's sockets drain
                for nack in conn.nacks:
                    assert nack.error_type == NackErrorType.THROTTLING, (
                        docs[sel[j]], nack
                    )
                    wait = max(wait, nack.retry_after_s)
                if conn.nacks:
                    conn.nacks.clear()
                    again.append(j)
            return again, wait

        pending = list(range(len(sel)))
        for _ in range(400):
            pending, wait = self.call(lambda: go(pending))
            if not pending:
                return
            self.throttled += len(pending)
            time.sleep(min(max(wait, 0.005), 0.5))
        raise AssertionError("feeder: frames still throttled after 400 offers")

    def inserts(self, k: int, sel=None) -> None:
        """One frame of ``k`` position-0 inserts to every doc of ``sel``
        (indices into doc_ids; default all). Docs move in lockstep, so a
        narrower ``sel`` must be the same set every time."""
        from fluidframework_tpu.protocol.constants import (
            F_ARG, F_LEN, F_REF, F_SEQ, F_TYPE, OP_INSERT, OP_WIDTH,
        )

        sel = np.arange(len(self.doc_ids)) if sel is None else np.asarray(sel)
        ar = np.arange(k, dtype=np.int64)
        rows = np.zeros((len(sel), k, OP_WIDTH), np.int32)
        rows[:, :, F_TYPE] = OP_INSERT
        rows[:, :, F_LEN] = 1
        rows[:, :, F_SEQ] = self.csn[sel, None] + 1 + ar[None, :]
        rows[:, :, F_REF] = self.heads[sel, None]
        # SharedString._MINT_STRIDE: content ids scope to the connection.
        rows[:, :, F_ARG] = (
            self.connno[sel, None] * (1 << 14) + self.sent + 1 + ar[None, :]
        )
        texts = tuple(ALPHABET[(self.sent + 1 + i) % 26] for i in range(k))
        self._send(rows, texts, sel)
        self.sent += k
        self.csn[sel] += k
        self.heads[sel] += k

    def remove(self, start: int, end: int, sel) -> None:
        from fluidframework_tpu.protocol.constants import (
            F_POS1, F_POS2, F_REF, F_SEQ, F_TYPE, OP_REMOVE, OP_WIDTH,
        )

        sel = np.asarray(sel)
        rows = np.zeros((len(sel), 1, OP_WIDTH), np.int32)
        rows[:, 0, F_TYPE] = OP_REMOVE
        rows[:, 0, F_POS1] = start
        rows[:, 0, F_POS2] = end
        rows[:, 0, F_SEQ] = self.csn[sel] + 1
        rows[:, 0, F_REF] = self.heads[sel]
        self._send(rows, (), sel)
        self.csn[sel] += 1
        self.heads[sel] += 1

    @staticmethod
    def expected(n_ops: int, first: int = 1) -> str:
        """Text after position-0 inserts number ``first``..``n_ops``."""
        return "".join(ALPHABET[o % 26] for o in range(n_ops, first - 1, -1))


def oracle_replay(svc, doc_id: str) -> str:
    """The channel's text by the plain reference: every sequenced op of
    the DURABLE log, lowered the way a client lowers it and applied to a
    pure-Python OracleDoc. Also asserts the log is gapless 1..head."""
    from fluidframework_tpu.models.shared_string import row_from_wire
    from fluidframework_tpu.protocol.constants import NO_CLIENT
    from fluidframework_tpu.protocol.types import MessageType
    from fluidframework_tpu.service.lambdas import stored_message
    from fluidframework_tpu.testing.oracle import OracleDoc

    head = svc.doc_head(doc_id)
    oracle, payloads = OracleDoc(NO_CLIENT), {}
    want = 1
    for lo, hi, obj in svc.log_entries(doc_id, 1, head):
        assert lo == want, f"{doc_id}: durable log gap at seq {want} (got {lo})"
        want = hi + 1
        msgs = obj.messages() if hasattr(obj, "messages") else [
            stored_message(obj)
        ]
        for m in msgs:
            if m.type != MessageType.OPERATION:
                continue
            env = m.contents
            if not isinstance(env, dict) or env.get("address") != CHANNEL:
                continue
            row = row_from_wire(
                env["contents"], seq=m.sequence_number,
                ref=m.reference_sequence_number, client=m.client_id,
                msn=m.minimum_sequence_number, payloads=payloads,
            )
            if row is not None:
                oracle.apply(row)
    assert want == head + 1, f"{doc_id}: log ends at {want - 1}, head {head}"
    return oracle.text(payloads)


def _drain_clients(runtimes, settled, timeout: float = 120.0) -> None:
    for rt in runtimes:
        rt.flush()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for rt in runtimes:
            rt.process_incoming()
        if settled():
            return
        time.sleep(0.02)
    raise AssertionError("serve: websocket clients did not converge")


def phase_serve(seed: int, target_docs: int):
    """The default-configured server, a fleet of resident documents loaded
    through the bulk frame front door, then real websocket clients."""
    from fluidframework_tpu.service import server_main

    cfg = server_main.load_config(
        env={}, overrides={"host": "127.0.0.1", "port": 0}
    )
    srv = server_main.build_server(cfg)
    srv.start()
    try:
        return _serve_body(srv, cfg, seed, target_docs), srv
    except BaseException:
        srv.stop()
        raise


def _serve_body(srv, cfg, seed: int, target_docs: int) -> dict:
    from fluidframework_tpu.drivers.network_driver import NetworkFluidService
    from fluidframework_tpu.models.shared_string import SharedString
    from fluidframework_tpu.parallel import aot
    from fluidframework_tpu.runtime.container import ContainerRuntime

    svc = srv.service
    # Load in slices so the server's loop is never held for minutes, and
    # so a slow host shows before the whole budget is gone: past the
    # 32,768-doc floor the load stops when its time budget is spent.
    floor, chunk, budget_s, k_load = 32768, min(8192, target_docs), 300.0, 2
    call = lambda fn: on_loop(srv, fn)
    feeders = []
    t0 = time.perf_counter()
    loaded = 0
    while loaded < target_docs:
        if loaded >= floor and time.perf_counter() - t0 > budget_s:
            emit("serve.cut", target_docs=target_docs, loaded_docs=loaded,
                 reason=f"load budget of {budget_s:.0f}s spent")
            break
        n = min(chunk, target_docs - loaded)
        f = Feeder(call, svc, [f"d{loaded + i}" for i in range(n)])
        f.inserts(k_load)
        feeders.append(f)
        loaded += n
    on_loop(srv, svc.flush_device)
    load_s = time.perf_counter() - t0

    # Steady state: more rounds on one slice; AOT builds must go flat.
    steady = feeders[0]
    sel = np.arange(512)
    for _ in range(10):  # past the compaction cadence at this pool shape
        steady.inserts(2, sel)
        on_loop(srv, svc.flush_device)
    builds_before = aot.stats()["builds"]
    for _ in range(4):
        steady.inserts(2, sel)
        on_loop(srv, svc.flush_device)
    builds_after = aot.stats()["builds"]
    assert builds_after == builds_before, (
        f"serve: AOT builds grew in steady state "
        f"({builds_before} -> {builds_after})"
    )

    # Real clients over the websocket: two on one doc concurrently, one
    # each on two more; >=2 ops per flush so the frame wire is taken.
    host, port = "127.0.0.1", srv.port

    def client(doc):
        return ContainerRuntime(
            NetworkFluidService(host, port), doc,
            channels=(SharedString(CHANNEL),),
        )

    a, b = client("c0"), client("c0")
    c, d = client("c1"), client("c2")
    texts = lambda *rts: [r.get_channel(CHANNEL).get_text() for r in rts]
    a.get_channel(CHANNEL).insert_text(0, "hello ")
    a.get_channel(CHANNEL).insert_text(6, "world")
    _drain_clients([a, b], lambda: texts(a, b) == ["hello world"] * 2)
    # Concurrent edits at both ends of c0, and the other docs meanwhile.
    a.get_channel(CHANNEL).insert_text(0, ">> ")
    a.get_channel(CHANNEL).insert_text(3, "[a] ")
    b.get_channel(CHANNEL).insert_text(11, " from b")
    b.get_channel(CHANNEL).remove_range(0, 1)
    c.get_channel(CHANNEL).insert_text(0, "chip")
    c.get_channel(CHANNEL).insert_text(4, " smoke")
    d.get_channel(CHANNEL).insert_text(0, "xy")
    d.get_channel(CHANNEL).insert_text(1, "-")
    _drain_clients(
        [a, b, c, d],
        lambda: texts(a)[0] == texts(b)[0] and "from b" in texts(a)[0]
        and "[a]" in texts(b)[0] and not any(
            r._has_unacked_local_state() for r in (a, b, c, d)
        ),
    )
    client_text = {
        "c0": texts(a)[0], "c1": texts(c)[0], "c2": texts(d)[0],
    }
    assert texts(b)[0] == client_text["c0"]
    assert client_text["c1"] == "chip smoke" and client_text["c2"] == "x-y"
    reader = NetworkFluidService(host, port)
    for doc, want in client_text.items():
        served = reader.get_channel_text(doc, CHANNEL)
        replay = on_loop(srv, lambda doc=doc: oracle_replay(svc, doc))
        assert served == want == replay, (
            f"serve: {doc}: device-served {served!r}, client {want!r}, "
            f"oracle replay {replay!r}"
        )
    frames_received = srv.frames_received
    for rt in (a, b, c, d):
        rt.disconnect()

    # Sampled fleet docs: device text == analytic == oracle replay.
    rng = np.random.default_rng(seed)
    sampled = 0
    for f in (feeders[0], feeders[-1]):
        for i in rng.integers(512, len(f.doc_ids), 8):
            doc = f.doc_ids[int(i)]
            got = on_loop(srv, lambda doc=doc: svc.device.text(doc, CHANNEL))
            replay = on_loop(srv, lambda doc=doc: oracle_replay(svc, doc))
            assert got == f.expected(k_load) == replay, (doc, got, replay)
            sampled += 1
    doc = steady.doc_ids[7]
    got = on_loop(srv, lambda: svc.device.text(doc, CHANNEL))
    assert got == steady.expected(steady.sent) == on_loop(
        srv, lambda: oracle_replay(svc, doc)
    ), (doc, got)

    stats = on_loop(srv, svc.device.stats)
    fleet = svc.device.fleet
    base = fleet.pools[cfg["device_capacity"]]
    assert stats["docs_with_errors"] == 0, stats
    assert fleet.kernel == "pallas", fleet.kernel
    assert svc.device.pump_dispatches > 0
    assert stats["channels"] == loaded + 3, stats
    lane_bytes = sum(
        int(np.prod(x.shape)) * 4
        for p in fleet.pools.values() for x in p.state
    )
    rec = {
        "resident_docs": loaded, "target_docs": target_docs,
        "ops_per_doc_at_load": k_load, "load_seconds": round(load_s, 2),
        "frames_reoffered_after_throttle": sum(f.throttled for f in feeders),
        "kernel": fleet.kernel, "docs_with_errors": stats["docs_with_errors"],
        "channels": stats["channels"], "ops_applied": stats["ops_applied"],
        "base_pool": f"{base.n_slots}x{base.capacity}",
        "fleet_lane_bytes": lane_bytes,
        "pump_dispatches": svc.device.pump_dispatches,
        "feed_triggers": dict(svc.device.feed_triggers),
        "aot": aot.stats(), "aot_builds_steady_delta": 0,
        "websocket_frames_received": frames_received,
        "client_docs": client_text,
        "device_eq_client_eq_oracle": True,
        "fleet_docs_oracle_checked": sampled + 1,
    }
    assert frames_received > 0, "serve: clients never took the frame wire"
    return rec


# -- tiers --------------------------------------------------------------------


def phase_tiers(srv, target: int = 2112, top: int = 4096) -> dict:
    """Grow 64 documents past 2,048 rows through the same service — the
    promotion walk up to the 4,096 tier — then cool some down (demotion)
    and put one to sleep and wake it."""
    svc = srv.service
    fleet = svc.device.fleet
    n_docs = 64
    call = lambda fn: on_loop(srv, fn)
    f = Feeder(call, svc, [f"t{i}" for i in range(n_docs)])
    while f.sent < target:
        f.inserts(64)
    on_loop(srv, svc.flush_device)

    def texts(idx):
        return on_loop(srv, lambda: [
            svc.device.text(f.doc_ids[i], CHANNEL) for i in idx
        ])

    want = f.expected(f.sent)
    got = texts(range(n_docs))
    bad = [f.doc_ids[i] for i, t in enumerate(got) if t != want]
    assert not bad, f"tiers: device text differs from expected on {bad[:4]}"
    for i in (0, n_docs - 1):
        assert on_loop(
            srv, lambda i=i: oracle_replay(svc, f.doc_ids[i])
        ) == want, f.doc_ids[i]
    caps = sorted({fleet.placement[svc.device._index[(d, CHANNEL)]][0]
                   for d in f.doc_ids})
    assert caps == [top], f"tiers: docs sit in tiers {caps}, expected {top}"
    migrations = fleet.migrations
    assert migrations >= n_docs * (top // 128).bit_length() - n_docs, migrations

    # Cool 8 docs down to 40 characters; the collab window then has to
    # pass the removal before zamboni reclaims the rows and the count
    # falls under the low-water mark.
    cold = np.arange(8)
    f.remove(40, f.sent, cold)
    grown = f.sent
    for _ in range(12):
        f.inserts(2)
        on_loop(srv, svc.flush_device)
    on_loop(srv, fleet.check_and_demote)
    on_loop(srv, svc.flush_device)
    assert fleet.demotions > 0, "tiers: nothing demoted"
    extra = f.sent - grown
    want_hot = f.expected(f.sent)
    want_cold = want_hot[: extra + 40]
    got = texts(range(n_docs))
    for i, t in enumerate(got):
        assert t == (want_cold if i < 8 else want_hot), f.doc_ids[i]
    assert on_loop(srv, lambda: oracle_replay(svc, f.doc_ids[0])) == want_cold
    cold_caps = sorted({
        fleet.placement[svc.device._index[(f.doc_ids[i], CHANNEL)]][0]
        for i in cold
    })

    # Hibernate -> wake: the doc goes clientless; with a resident budget
    # (max_resident — no server config key, so set on the manager) far
    # under the fleet, one sweep puts every clientless doc to sleep (this
    # one and the websocket clients' three); its next frame wakes it.
    sleeper = f.doc_ids[-1]
    rm = svc.device.residency

    def hibernate():
        svc.disconnect(sleeper, f.clients[-1])
        rm.max_resident = 1
        try:
            return svc.hibernate_sweep(max_docs=16)
        finally:
            rm.max_resident = 0

    slept = on_loop(srv, hibernate)
    assert sleeper in slept and rm.is_cold(sleeper), slept
    before = texts([n_docs - 1])[0]  # served from the cold record
    assert before == want_hot
    waker = Feeder(call, svc, [sleeper])  # a fresh connection's 2 inserts
    waker.inserts(2)
    on_loop(srv, svc.flush_device)
    after = texts([n_docs - 1])[0]
    assert not rm.is_cold(sleeper)
    assert after == waker.expected(2) + before, (after[:8], before[:8])
    assert on_loop(srv, lambda: oracle_replay(svc, sleeper)) == after
    stats = on_loop(srv, svc.device.stats)
    assert stats["docs_with_errors"] == 0, stats
    return {
        "docs": n_docs, "rows_per_doc": target, "tiers_walked": sorted(
            c for c in fleet.pools if c > 128
        ),
        "migrations": migrations, "demotions": fleet.demotions,
        "frames_reoffered_after_throttle": f.throttled,
        "demoted_docs_now_in_tiers": cold_caps,
        "hibernated": slept, "residency": rm.stats(),
        "docs_with_errors": stats["docs_with_errors"],
        "device_eq_expected_eq_oracle": True,
    }


# -- tree ---------------------------------------------------------------------


def _tree_stream(seed: int, n_commits: int, move_prob: float):
    """One document's concurrent wire stream: three sessions author on
    views that lag the log by up to 6 commits, inserts and deletes
    balanced so the view stays in one dense-size bucket (no recompile in
    mid-run), and ``move_prob`` of the commits a first-class move
    (mout/min marks)."""
    from fluidframework_tpu.tree import marks as M
    from fluidframework_tpu.tree.edit_manager import Commit, EditManager

    r = np.random.default_rng(seed)
    sessions = [EditManager(session=100 + s) for s in range(3)]
    processed = [0, 0, 0]
    log = []
    nid = 1
    for k in range(1, n_commits + 1):
        s = int(r.integers(0, 3))
        em = sessions[s]
        target = max(
            processed[s],
            max((c.seq for c in log if c.session == em.session), default=0),
            len(log) - 6,
        )
        for c in log[processed[s]: target]:
            em.add_sequenced(c)
        processed[s] = target
        view = em.local_view()
        if len(view) >= 4 and r.random() < move_prob:
            i0 = int(r.integers(0, len(view) - 1))
            cnt = int(r.integers(1, min(3, len(view) - i0) + 1))
            dest = int(r.integers(0, len(view) - cnt + 1))
            cells = view[i0: i0 + cnt]
            if dest <= i0:
                change = [M.skip(dest), M.move_in(0, cnt),
                          M.skip(i0 - dest), M.move_out(0, cells)]
            else:
                change = [M.skip(i0), M.move_out(0, cells),
                          M.skip(dest - i0), M.move_in(0, cnt)]
        else:
            change = []
            i = 0
            while i < len(view):
                roll = r.random()
                run = min(int(r.integers(1, 3)), len(view) - i)
                if roll < 0.45 and len(view) > 24:
                    change.append(M.delete(view[i: i + run]))
                else:
                    change.append(M.skip(run))
                i += run
            change.append(M.insert([
                ((100 + s) * 1000000 + nid + j, nid + j) for j in range(2)
            ]))
            nid += 2
        change = M.normalize(change)
        em.add_local(change)
        log.append(Commit(session=em.session, seq=k, ref=target, change=change))
    return log


def phase_tree(
    n_docs: int = 1024, n_commits: int = 64, scripts: int = 8,
    wave: int = 32, move_prob: float = 0.05,
) -> dict:
    """SharedTree through EditManager's device path: ``batch_ingest``
    gathers many documents' eligible prefixes into ONE
    ``batched_em_trunk_scan`` dispatch per wave. ``scripts`` distinct
    streams are tiled over the documents; every commit has to ride the
    device (moves too), and every distinct script has to end in the trunk
    state the per-commit host EditManager folds."""
    from fluidframework_tpu.tree import marks as M
    from fluidframework_tpu.tree.edit_manager import EditManager, batch_ingest

    streams = [
        _tree_stream(1000 + i, n_commits, move_prob) for i in range(scripts)
    ]
    host_ems = []
    for log in streams:
        em = EditManager(session=1)
        for c in log:
            em.add_sequenced(c)
        host_ems.append(em)

    n_docs = scripts * max(1, n_docs // scripts)
    ems = [EditManager(session=1) for _ in range(n_docs)]
    logs = [streams[d % scripts] for d in range(n_docs)]
    device_commits = total = waves = 0
    for w0 in range(0, n_commits, wave):
        # The collab floor trails the head by the authoring lag: commits
        # of the NEXT wave ref up to 6 back, and the server's min_seq only
        # passes states nothing will reference.
        items = []
        for em, log in zip(ems, logs):
            chunk = log[w0: w0 + wave]
            items.append((em, chunk, max(0, chunk[-1].seq - 8)))
        stats = batch_ingest(items)
        device_commits += stats["device_commits"]
        total += stats["device_commits"] + stats["host_commits"]
        waves += 1

    assert total == n_docs * n_commits, (total, n_docs, n_commits)
    assert device_commits == total, (
        f"tree: {total - device_commits} of {total} commits fell back to "
        "the host"
    )
    for d in range(scripts):
        assert ems[d].trunk_state == host_ems[d].trunk_state, (
            f"tree: device/host divergence on script {d}"
        )
    n_moves = sum(1 for log in streams for c in log if M.has_moves(c.change))
    return {
        "n_docs": n_docs, "commits_per_doc": n_commits, "waves": waves,
        "device_fraction": device_commits / total,
        "move_commit_fraction": round(n_moves / (scripts * n_commits), 3),
        "parity_with_host_engine": "ok",
    }


# -- scrape -------------------------------------------------------------------


def phase_scrape(srv) -> dict:
    out = {}
    for path in ("/metrics", "/debugz"):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=300
        ) as r:
            body = r.read()
            assert r.status == 200 and body, (path, r.status)
        out[path.strip("/") + "_bytes"] = len(body)
        if path == "/metrics":
            text = body.decode()
            assert "docs_with_errors" in text or "device" in text, text[:400]
            out["metric_lines"] = sum(
                1 for ln in text.splitlines() if ln and ln[0] != "#"
            )
    return out


# -- four chips ---------------------------------------------------------------


def phase_mesh(seed: int, n_docs: int = 8192) -> dict:
    """``PipelineFluidService(device_mesh=make_mesh(4))`` against the
    default single-device service on the same frames: lane states
    bit-equal, every pool lane a quarter per device; then one ShardedDoc
    against its single-device twin."""
    import jax

    from fluidframework_tpu.ops.segment_state import SegmentState
    from fluidframework_tpu.parallel.mesh import make_mesh
    from fluidframework_tpu.service.pipeline import PipelineFluidService

    assert len(jax.devices()) == 4
    mesh = make_mesh(4)
    k, rounds, hot = 8, 3, 64
    doc_ids = [f"m{i}" for i in range(n_docs)]

    def drive(svc):
        f = Feeder(lambda fn: fn(), svc, doc_ids)
        for _ in range(rounds):
            f.inserts(k)
        # A hot subset outgrows the base tier: promotion builds (and
        # grows) a second pool, which must come out sharded too.
        for _ in range(6):
            f.inserts(32, np.arange(hot))
        svc.flush_device()
        return svc

    meshed = drive(PipelineFluidService(device_mesh=mesh))
    default = drive(PipelineFluidService())
    fm, fd = meshed.device.fleet, default.device.fleet
    assert fm.kernel == fd.kernel == "pallas", (fm.kernel, fd.kernel)
    assert sorted(fm.pools) == sorted(fd.pools) and len(fm.pools) >= 2, (
        sorted(fm.pools), sorted(fd.pools)
    )
    placement = {}
    for cap, pm in fm.pools.items():
        pd = fd.pools[cap]
        assert pm.n_slots == pd.n_slots, (cap, pm.n_slots, pd.n_slots)
        for name, x, y in zip(SegmentState._fields, pm.state, pd.state):
            assert len(x.sharding.device_set) == 4, (cap, name, x.sharding)
            rows = sorted(s.data.shape[0] for s in x.addressable_shards)
            assert rows == [pm.n_slots // 4] * 4, (cap, name, rows)
            assert len({s.device for s in x.addressable_shards}) == 4
            assert len(y.sharding.device_set) == 1
            assert np.array_equal(np.asarray(x), np.asarray(y)), (
                f"mesh/default divergence: pool {cap} lane {name}"
            )
        placement[str(cap)] = f"{pm.n_slots} slots, {pm.n_slots // 4}/device"
    sm, sd = meshed.device.stats(), default.device.stats()
    assert sm["docs_with_errors"] == sd["docs_with_errors"] == 0, (sm, sd)
    assert sm["ops_applied"] == sd["ops_applied"]
    text = meshed.device.text(doc_ids[0], CHANNEL)
    assert text == default.device.text(doc_ids[0], CHANNEL) and len(text) == (
        rounds * k + 6 * 32
    )

    sharded = _sharded_doc_check(seed)
    return {
        "devices": 4, "docs": n_docs, "kernel": fm.kernel,
        "pools": placement, "lanes_bit_equal": True,
        "ops_applied": sm["ops_applied"], "migrations": fm.migrations,
        **sharded,
    }


def _sharded_doc_check(seed: int) -> dict:
    import jax
    from jax.sharding import Mesh

    from fluidframework_tpu.ops import encode as E
    from fluidframework_tpu.ops.merge_kernel import jit_apply_ops
    from fluidframework_tpu.ops.segment_state import (
        make_state, materialize, to_host,
    )
    from fluidframework_tpu.parallel.sharded_doc import ShardedDoc
    from fluidframework_tpu.protocol.constants import NO_CLIENT
    from fluidframework_tpu.testing.fuzz import random_acked_stream
    from fluidframework_tpu.testing.oracle import OracleDoc

    payloads: dict = {}
    n_rows, shard_cap = 96, 128
    rows = []
    for i in range(n_rows):
        payloads[100 + i] = ALPHABET[i % 26] * 3
        rows.append(E.insert(3 * i, 100 + i, 3, seq=i + 1, ref=i, client=0))
    base = jit_apply_ops(make_state(512, NO_CLIENT), np.stack(rows))
    doc = ShardedDoc(
        shard_cap=shard_cap, mesh=Mesh(np.array(jax.devices()), ("seg",))
    )
    assert doc.n_shards == 4
    doc.load_single(base)
    assert len(doc.state.kind.sharding.device_set) == 4
    assert (np.asarray(doc.state.count) > 0).all()
    track = OracleDoc(NO_CLIENT)
    h = to_host(base)
    for i in range(int(h.count)):
        track.apply(
            E.insert(3 * i, int(h.orig[i]), 3, seq=i + 1, ref=i, client=0)
        )
    ops = np.stack(random_acked_stream(
        np.random.default_rng(seed), 96, payloads, track, caught_up=True,
        seq0=n_rows + 1,
    )).astype(np.int32)
    doc.apply(ops)
    single = jit_apply_ops(base, ops)
    assert doc.err == 0
    rebalanced = doc.rebalance(trigger=0.0)
    got = materialize(doc.to_single(), payloads)
    assert got == materialize(single, payloads) == track.text(payloads)
    return {
        "sharded_doc_shards": doc.n_shards,
        "sharded_doc_rebalanced": bool(rebalanced),
        "sharded_doc_eq_single_eq_oracle": True,
    }


# -- main ---------------------------------------------------------------------

METER: CompileMeter


def main(argv=None) -> int:
    global METER
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    # First thing: the one compile cache (a directory named in
    # JAX_COMPILATION_CACHE_DIR wins; else <checkout>/.jax_cache).
    from fluidframework_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    METER = CompileMeter()
    dev = run_phase(METER, "device", phase_device, args.chips)
    from fluidframework_tpu.utils.native import native_status

    emit("setup", compile_cache_dir=cache_dir, native_loaded=native_status())

    if args.chips == 4:
        run_phase(METER, "mesh", phase_mesh, args.seed)
    else:
        run_phase(METER, "kernels", phase_kernels, args.seed)
        _, srv = run_phase(METER, "serve", phase_serve, args.seed, 100_000)
        try:
            run_phase(METER, "tiers", phase_tiers, srv)
            run_phase(METER, "tree", phase_tree)
            run_phase(METER, "scrape", phase_scrape, srv)
        finally:
            srv.stop()
    emit(
        "total", seconds=round(time.perf_counter() - t_start, 2),
        compile_seconds=round(METER.seconds, 2), cache_hits=METER.hits,
        cache_misses=METER.misses,
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev["platform"], "kind": dev["kind"],
            "count": dev["count"],
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
