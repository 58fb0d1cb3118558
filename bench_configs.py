"""BASELINE.md measurement configs 1-5 (BASELINE.json `configs`).

``bench.py`` is the driver's headline line (config 2: batched merge-op
apply). This harness runs the rest; each config prints one JSON line.

    python bench_configs.py           # all configs, CI-sized
    python bench_configs.py --full    # BASELINE-sized (TPU for 2/4/5)
    python bench_configs.py --config 3

Configs (BASELINE.md "Measurement configs to implement"):
1. Single SharedString doc: insert/remove ops replayed through the replay
   driver (CPU baseline; ref harness packages/drivers/replay-driver).
2. Batched merge-op apply across concurrent docs (delegates to bench.py).
3. SharedTree changeset rebase: docs x concurrent edits through the
   EditManager trunk (ref editManager.ts:142-281).
4. SharedMatrix axis merge across docs: permutation-vector op batches on
   the Pallas kernel (ref permutationvector.ts:151).
5. Deli+scribe end-to-end: many docs sequenced through the partitioned
   lambda pipeline, sequenced batches applied device-side (ref
   deli/lambda.ts:742) — the TpuDeliLambda shape.
"""

from __future__ import annotations

import argparse
import json
import time
import sys

import numpy as np


def _emit(**kv) -> dict:
    """Print one JSON record line and return it — callers embedding a
    config inside another artifact (bench.py's driver headline) reuse the
    returned dict."""
    print(json.dumps(kv))
    return kv


# ---------------------------------------------------------------------------


def config1_single_doc_replay(n_ops: int) -> None:
    """CPU baseline: one doc's op log replayed through the replay driver."""
    from fluidframework_tpu.drivers.replay_driver import ReplayDocumentService
    from fluidframework_tpu.models.shared_string import SharedString
    from fluidframework_tpu.runtime.container import ContainerRuntime
    from fluidframework_tpu.service.local_server import LocalFluidService

    rng = np.random.default_rng(0)
    svc = LocalFluidService()
    author = ContainerRuntime(svc, "doc", channels=(SharedString("text"),))
    s = author.get_channel("text")
    for i in range(n_ops):
        length = len(s.get_text())
        if length > 8 and rng.random() < 0.45:
            a = int(rng.integers(0, length - 2))
            s.remove_range(a, a + int(rng.integers(1, 3)))
        else:
            s.insert_text(int(rng.integers(0, length + 1)), "ab")
        if i % 16 == 0:
            author.flush()
            author.process_incoming()
    author.flush()
    author.process_incoming()

    replay = ReplayDocumentService(svc.get_deltas("doc"), doc_id="doc")
    t0 = time.perf_counter()
    reader = ContainerRuntime(replay, "doc", channels=(SharedString("text"),))
    reader.process_incoming()
    dt = time.perf_counter() - t0
    assert reader.get_channel("text").get_text() == s.get_text()
    total = len(svc.get_deltas("doc"))
    _emit(
        metric="single_doc_replay_ops_per_sec", value=round(total / dt),
        unit="ops/s", config=1, n_ops=total,
    )


def config2b_apply_latency(n_docs: int, k: int, steps: int, on_tpu: bool) -> None:
    """Latency mode for the apply path (BASELINE p99 target): small op
    batches per step, compaction amortized; reports per-step wall-time
    percentiles including the host readback."""
    import jax

    from bench import build_op_stream
    from fluidframework_tpu.ops.pallas_compact import apply_compact_packed
    from fluidframework_tpu.ops.pallas_kernel import (
        SC_ERR,
        apply_ops_packed,
        pack_state,
    )
    from fluidframework_tpu.ops.segment_state import make_batched_state
    from fluidframework_tpu.protocol.constants import NO_CLIENT

    rng = np.random.default_rng(0)
    ops = jax.device_put(build_op_stream(n_docs, k, rng))
    blk = 32 if on_tpu else 8
    tables, scalars = pack_state(make_batched_state(n_docs, 256, NO_CLIENT))
    # Warm BOTH kernels (plain apply and fused apply+compact) so no JIT
    # compile lands inside the timed loop.
    tables, scalars = apply_ops_packed(
        tables, scalars, ops, block_docs=blk, interpret=not on_tpu
    )
    tables, scalars = apply_compact_packed(
        tables, scalars, ops, block_docs=blk, interpret=not on_tpu
    )
    np.asarray(scalars[:, SC_ERR])

    times = []
    for i in range(steps):
        t0 = time.perf_counter()
        if i % 4 == 3:
            # Zamboni cadence: the FUSED apply+compact replaces what used
            # to be two dispatches — the p99 step (VERDICT r1 #10).
            tables, scalars = apply_compact_packed(
                tables, scalars, ops, block_docs=blk, interpret=not on_tpu
            )
        else:
            tables, scalars = apply_ops_packed(
                tables, scalars, ops, block_docs=blk, interpret=not on_tpu
            )
        np.asarray(scalars[:, SC_ERR])
        times.append(time.perf_counter() - t0)
    assert int(np.asarray(scalars[:, SC_ERR]).sum()) == 0
    arr = np.array(times) * 1e3
    fused_steps = arr[3::4]  # the zamboni-cadence (apply+compact) steps
    plain_steps = np.delete(arr, np.s_[3::4])

    def _med(x):  # empty slice (smoke runs) -> null, not NaN-invalid JSON
        return round(float(np.median(x)), 3) if len(x) else None

    _emit(
        metric="apply_step_latency_ms", value=round(float(np.median(arr)), 3),
        unit="ms", config="2b", p99_ms=round(float(np.percentile(arr, 99)), 3),
        apply_step_median_ms=_med(plain_steps),
        fused_zamboni_step_median_ms=_med(fused_steps),
        n_docs=n_docs, ops_per_doc=k,
        ops_per_sec=round(n_docs * k * len(times) / (arr.sum() / 1e3)),
    )


def config3_tree_rebase(n_docs: int, n_edits: int) -> None:
    """Concurrent-edit rebase through the EditManager trunk: real
    SharedTree clients editing without seeing each other until the flush,
    so every sequenced commit transports through the rebase path."""
    from fluidframework_tpu.runtime.container import ContainerRuntime
    from fluidframework_tpu.service.local_server import LocalFluidService
    from fluidframework_tpu.tree.shared_tree import SharedTree

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    total = 0
    for d in range(n_docs):
        svc = LocalFluidService()
        rts = [
            ContainerRuntime(svc, "t", channels=(SharedTree("tree"),))
            for _ in range(3)
        ]
        trees = [rt.get_channel("tree") for rt in rts]
        for i in range(n_edits):
            k = int(rng.integers(0, 3))
            t = trees[k]
            if len(t) > 2 and rng.random() < 0.3:
                t.delete_nodes(int(rng.integers(0, len(t) - 1)), 1)
            else:
                t.insert_nodes(int(rng.integers(0, len(t) + 1)), [i])
            total += 1
            if i % 4 == 0:  # concurrency window: flush every few edits
                rts[k].flush()
            if i % 8 == 0:
                for rt in rts:
                    rt.process_incoming()
        for rt in rts:
            rt.flush()
        busy = True
        while busy:
            busy = any(rt.process_incoming() for rt in rts)
        assert trees[0].get() == trees[1].get() == trees[2].get()
    dt = time.perf_counter() - t0
    _emit(
        metric="tree_rebase_edits_per_sec", value=round(total / dt),
        unit="edits/s", config=3, n_docs=n_docs, edits_per_doc=n_edits,
    )


def config3b_tree_rebase_device(
    n_docs: int, n_commits: int, scripts: int = 64
) -> None:
    """SharedTree trunk rebase ON DEVICE (VERDICT r1 #4): sequenced commit
    streams integrate through the dense-rebase trunk scan
    (tree/device_trunk.py) — the EditManager inner loop as a lax.scan with
    a W-deep concurrent window, vmapped across documents.

    Stream generation (host, untimed data prep) builds ``scripts`` distinct
    concurrent multi-session streams and tiles them across the doc batch;
    device timing is shape-dependent, not data-dependent, so tiling does
    not flatter the number. Parity vs the host rebase trunk is asserted on
    the distinct scripts. The CPU comparison point is the host fold over
    the same streams (marks.py rebase/apply — the reference EditManager
    algorithm without container overhead)."""
    import jax

    from fluidframework_tpu.ops import tree_kernel as TK
    from fluidframework_tpu.testing.tree_streams import (
        gen_streams,
        host_trunk,
        to_device_batch,
    )
    from fluidframework_tpu.tree.device_trunk import batched_trunk_scan

    Lc, Pc, W = 128, 32, 16
    scripts = min(scripts, n_docs)
    rng = np.random.default_rng(0)
    streams = gen_streams(
        rng, scripts, n_commits, n_sessions=3, W=W, Lc=Lc
    )
    base = to_device_batch(streams, Lc, Pc)
    reps = n_docs // scripts
    n_docs = scripts * reps
    # Stage the commit batch on device ONCE — a per-call host->device
    # re-transfer of the tiled arrays would be timed with the kernel.
    batch = type(base)(
        *[
            jax.device_put(np.tile(x, (reps,) + (1,) * (x.ndim - 1)))
            for x in base
        ]
    )
    doc_ids = jax.device_put(np.zeros((n_docs, Lc), np.int32))
    L0 = jax.device_put(np.zeros(n_docs, np.int32))

    # CPU baseline: the same trunk fold in pure Python.
    t0 = time.perf_counter()
    host_states = [host_trunk(s) for s in streams]
    cpu_rate = scripts * n_commits / (time.perf_counter() - t0)

    # Warmup / compile.
    out_ids, out_L, err = batched_trunk_scan(doc_ids, L0, batch, W)
    np.asarray(out_L)
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        out_ids, out_L, err = batched_trunk_scan(doc_ids, L0, batch, W)
        np.asarray(out_L)  # forces completion
    dt = time.perf_counter() - t0
    rate = n_docs * n_commits * iters / dt

    assert not np.asarray(err).any(), "ring-window overflow in config 3b"
    for d in range(scripts):  # parity across every distinct script
        got = TK.dense_to_doc(out_ids[d], out_L[d])
        assert got == host_states[d], f"device/host divergence on doc {d}"
    _emit(
        metric="tree_rebase_device_edits_per_sec", value=round(rate),
        unit="edits/s", config="3b", n_docs=n_docs, commits_per_doc=n_commits,
        window=W, scripts=scripts, parity="ok",
        cpu_trunk_edits_per_sec=round(cpu_rate),
        vs_cpu=round(rate / cpu_rate, 2),
    )


def config3c_em_kernel_concurrent(
    n_docs: int, n_commits: int, scripts: int = 16, wave: int = 32,
    move_prob: float = 0.0,
) -> dict:
    """The LINEAGE-AWARE EM kernel at scale (VERDICT r3 #4): concurrent
    multi-session commit streams integrate through the PRODUCTION
    EditManager ingest — ``edit_manager.batch_ingest`` aggregates many
    documents' eligible prefixes into ONE ``batched_em_trunk_scan``
    dispatch per wave — and the artifact reports edits/s plus the
    device-ridden fraction, against the same streams folded per-commit
    on the host (the reference ``editManager.ts:142-281`` inner loop).

    Unlike config 3b (the positional-rebase kernel on fully-sequential
    streams), these streams carry real concurrency: sessions author
    against lagged views (max_lag 6), so the kernel exercises the
    id-anchor/lineage algebra, and whatever the B-boundary keeps
    host-side is counted, not hidden. ``scripts`` distinct streams tile
    across the doc batch (device timing is shape-dependent); parity vs
    the per-commit host EditManager is asserted on every distinct
    script. Streams are delete-biased so views stay in one dense-size
    bucket (no mid-run recompiles — production keeps these shapes warm).

    ``move_prob`` > 0 mixes first-class move commits (mout/min marks)
    into the streams. Through r6 moves were OUTSIDE the dense device IR
    by contract and this variant measured the fallback tax (a move broke
    the wave's device prefix, sending it AND its wave remainder
    host-side — device_fraction ~0.0). Since r7 the encoder lowers
    mout/min into the EM kernel's move lane + same-cell attach runs, so
    move-bearing commits ride the device natively: the reported
    ``device_fraction`` is the r7 acceptance number (>= 0.9 at the 5%
    move mix), still parity-asserted per distinct script against the
    per-commit host EditManager."""
    from fluidframework_tpu.tree import marks as M
    from fluidframework_tpu.tree.edit_manager import (
        Commit,
        EditManager,
        batch_ingest,
    )

    rng = np.random.default_rng(0)

    def gen_stream(seed, n):
        """Authentic concurrent wire stream (sessions author on lagged
        views), insert/delete balanced so the view size stays bounded."""
        r = np.random.default_rng(seed)
        sessions = [EditManager(session=100 + s) for s in range(3)]
        processed = [0, 0, 0]
        log = []
        nid = [1]
        for k in range(1, n + 1):
            s = int(r.integers(0, 3))
            em = sessions[s]
            target = max(
                processed[s],
                max((c.seq for c in log if c.session == em.session),
                    default=0),
                len(log) - 6,
            )
            for c in log[processed[s]: target]:
                em.add_sequenced(c)
            processed[s] = target
            view = em.local_view()
            if move_prob and len(view) >= 4 and r.random() < move_prob:
                # A first-class move commit (host-path by contract).
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
                change = M.normalize(change)
                em.add_local(change)
                log.append(
                    Commit(session=em.session, seq=k, ref=target,
                           change=change)
                )
                continue
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
            cells = [
                ((100 + s) * 1000000 + nid[0] + j, nid[0] + j)
                for j in range(2)
            ]
            nid[0] += 2
            change.append(M.insert(cells))
            change = M.normalize(change)
            em.add_local(change)
            log.append(
                Commit(session=em.session, seq=k, ref=target, change=change)
            )
        return log

    streams = [gen_stream(1000 + i, n_commits) for i in range(scripts)]

    # Host baseline: the per-commit production fold on the distinct
    # scripts (device disabled via the min-batch gate).
    t0 = time.perf_counter()
    host_ems = []
    for log in streams:
        em = EditManager(session=1)
        for c in log:
            em.add_sequenced(c)
            em.host_commits += 1
        host_ems.append(em)
    cpu_rate = scripts * n_commits / (time.perf_counter() - t0)

    reps = max(1, n_docs // scripts)
    n_docs = scripts * reps
    ems = [EditManager(session=1) for _ in range(n_docs)]
    logs = [streams[d % scripts] for d in range(n_docs)]

    # Warmup wave on throwaway managers compiles the kernel shapes.
    warm = [EditManager(session=1) for _ in range(n_docs)]
    batch_ingest(
        [(em, list(log[:wave]), log[wave - 1].seq)
         for em, log in zip(warm, logs)]
    )

    t0 = time.perf_counter()
    device_commits = 0
    total = 0
    waves = 0
    for w0 in range(0, n_commits, wave):
        items = []
        for em, log in zip(ems, logs):
            chunk = log[w0: w0 + wave]
            # Collab floor trails the head by the authoring lag: commits
            # in the NEXT wave ref up to 6 back, and the server's min_seq
            # can only advance past states nothing will reference.
            items.append((em, chunk, max(0, chunk[-1].seq - 8)))
        stats = batch_ingest(items)
        device_commits += stats["device_commits"]
        total += stats["device_commits"] + stats["host_commits"]
        waves += 1
    dt = time.perf_counter() - t0
    rate = total / dt

    for d in range(scripts):  # parity across every distinct script
        assert ems[d].trunk_state == host_ems[d].trunk_state, (
            f"device/host divergence on script {d}"
        )
    extra = {}
    if move_prob:
        n_moves = sum(
            1 for log in streams for c in log if M.has_moves(c.change)
        )
        extra = {
            "move_prob": move_prob,
            "move_commit_fraction": round(
                n_moves / (scripts * n_commits), 3
            ),
        }
    return _emit(
        metric="em_kernel_concurrent_edits_per_sec", value=round(rate),
        unit="edits/s", config="3c-moves" if move_prob else "3c",
        n_docs=n_docs,
        commits_per_doc=n_commits, waves=waves, scripts=scripts,
        device_fraction=round(device_commits / max(total, 1), 3),
        parity="ok",
        cpu_em_edits_per_sec=round(cpu_rate),
        vs_cpu=round(rate / cpu_rate, 2),
        **extra,
    )


def config4_matrix_axis_merge(n_docs: int, k: int, on_tpu: bool) -> None:
    """Row/col insert + annotate batches on the Pallas kernel: each doc is
    two permutation vectors, so the batch is 2*n_docs kernel docs."""
    import jax

    from fluidframework_tpu.ops.pallas_compact import compact_packed
    from fluidframework_tpu.ops.pallas_kernel import (
        SC_ERR,
        apply_ops_packed,
        pack_state,
    )
    from fluidframework_tpu.ops.segment_state import make_batched_state
    from fluidframework_tpu.protocol.constants import NO_CLIENT, OP_WIDTH
    from fluidframework_tpu.ops import encode as E

    rng = np.random.default_rng(0)
    docs = 2 * n_docs  # row + col vector per matrix
    ops = np.zeros((docs, k, OP_WIDTH), np.int32)
    for d in range(min(docs, 16)):
        length = 0
        for i in range(k - 1):
            seq = i + 1
            roll = rng.random()
            if length > 6 and roll < 0.3:
                a = int(rng.integers(0, length - 2))
                ops[d, i] = E.remove(a, a + 2, seq=seq, ref=seq - 1,
                                     client=int(rng.integers(0, 8)))
                length -= 2
            elif length > 4 and roll < 0.5:
                a = int(rng.integers(0, length - 2))
                ops[d, i] = E.annotate(a, a + 2, 1 + i % 7, seq=seq,
                                       ref=seq - 1,
                                       client=int(rng.integers(0, 8)))
            else:
                ops[d, i] = E.insert(int(rng.integers(0, length + 1)),
                                     100 + i, 4, seq=seq, ref=seq - 1,
                                     client=int(rng.integers(0, 8)))
                length += 4
        # Close the script with a whole-doc remove + window advance so
        # compaction reclaims the table each round (steady state; same
        # pattern as bench.py's stream).
        ops[d, k - 1] = E.remove(0, length, seq=k, ref=k - 1, client=0, msn=k)
    for d in range(16, docs):
        ops[d] = ops[d % 16]
    jops = jax.device_put(ops)
    tables, scalars = pack_state(make_batched_state(docs, 256, NO_CLIENT))
    blk = 32 if on_tpu else 8
    tables, scalars = apply_ops_packed(
        tables, scalars, jops, block_docs=blk, interpret=not on_tpu
    )
    np.asarray(scalars[:, SC_ERR])
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        tables, scalars = apply_ops_packed(
            tables, scalars, jops, block_docs=blk, interpret=not on_tpu
        )
        tables, scalars = compact_packed(
            tables, scalars, interpret=not on_tpu
        )
        errs = int(np.asarray(scalars[:, SC_ERR]).sum())
    dt = time.perf_counter() - t0
    _emit(
        metric="matrix_axis_ops_per_sec", value=round(docs * k * iters / dt),
        unit="ops/s", config=4, n_matrices=n_docs, errs=errs,
    )


def config5_deli_scribe_e2e(n_docs: int, ops_per_doc: int, on_tpu: bool) -> dict:
    """End-to-end service shape THROUGH the product path (VERDICT r2 #1):
    this config drives :class:`~fluidframework_tpu.service.fleet_service.
    TpuFleetService` — native deli ticketing, fused Pallas apply, and the
    device scribe — via its public API only. Nothing here touches kernels
    or ticket loops directly; the numbers are the serving path.

    - EVERY document runs the real ticket loop per round (no tiling);
    - the scribe stage runs INSIDE the timed loop: logTail blobs for a
      rotating fleet slice plus device-state summaries (dirty-doc
      readback), with the readback cost measured and reported;
    - double-buffered boxcars: round r+1's host generation overlaps the
      device's round r (async dispatch; the err-lane readback barriers);
    - device-only step time measured separately on a pre-staged chain.
    """
    import jax

    from fluidframework_tpu.ops.pallas_compact import apply_compact_packed
    from fluidframework_tpu.protocol.constants import (
        F_ARG,
        F_CLIENT,
        F_LEN,
        F_MSN,
        F_POS1,
        F_POS2,
        F_REF,
        F_SEQ,
        F_TYPE,
        OP_INSERT,
        OP_REMOVE,
        OP_WIDTH,
    )
    from fluidframework_tpu.service.fleet_service import TpuFleetService

    rng = np.random.default_rng(0)
    rounds = 3
    blk = 32 if on_tpu else 8
    svc = TpuFleetService(
        n_docs, capacity=128, block_docs=blk, interpret=not on_tpu,
        compact_every=1,
    )
    svc.join_writer(0)
    host_backend = (
        "native-c++" if svc.fseq.native_available else "python"
    )
    lengths = np.zeros(n_docs, np.int64)
    cseqs = np.zeros(n_docs, np.int64)

    def generate_round():
        """Host content generation only — ticketing/stamping is the
        service's job (submit_round). Each round closes with a whole-doc
        remove + window advance so device tables stay bounded."""
        k = ops_per_doc
        rows = np.zeros((n_docs, k, OP_WIDTH), np.int32)
        intents = np.zeros((n_docs, k, 3), np.int32)
        start_seq = svc.fseq.doc_state[:, 0].astype(np.int64)
        for i in range(k):
            cseqs[:] += 1
            intents[:, i, 0] = 0  # writer slot
            intents[:, i, 1] = cseqs
            intents[:, i, 2] = start_seq + i  # caught-up perspective
            if i == k - 1:
                rows[:, i, F_TYPE] = OP_REMOVE
                rows[:, i, F_POS1] = 0
                rows[:, i, F_POS2] = lengths
                lengths[:] = 0
            else:
                roll = rng.random(n_docs)
                pos = rng.random(n_docs)
                rem = (lengths >= 6) & (roll < 0.4)
                a = (pos * np.maximum(lengths - 2, 1)).astype(np.int64)
                rows[:, i, F_TYPE] = np.where(rem, OP_REMOVE, OP_INSERT)
                rows[:, i, F_POS1] = np.where(
                    rem, a, (pos * (lengths + 1)).astype(np.int64)
                )
                rows[:, i, F_POS2] = np.where(rem, a + 2, 0)
                rows[:, i, F_ARG] = np.where(rem, 0, 10 + i)
                rows[:, i, F_LEN] = np.where(rem, 0, 3)
                lengths[:] += np.where(rem, -2, 3)
        return intents, rows

    def scribe_logtail(r: int, rows: np.ndarray) -> int:
        """LogTail persistence for the 1/rounds slice due this round
        (reference scribe/lambda.ts:304) into the service's store — one
        batched blob per round the way scriptorium bulk-inserts sequenced
        ops (``scriptorium/lambda.ts`` insertMany), not a write per doc."""
        sl = np.arange(r, n_docs, rounds)
        if sl.size == 0:
            return 0
        heads = svc.fseq.doc_state[sl, 0].astype(np.int64)
        head = json.dumps(
            {"round": r, "first_doc": int(sl[0]), "stride": rounds,
             "n": int(sl.size)}
        ).encode()
        svc.store.put_blob(
            head + b"\n" + heads.tobytes() + rows[sl].tobytes()
        )
        return int(sl.size)

    # Warmup compiles both kernels at the fleet shape via the service API,
    # then converges the scribe's adaptive lane set (three small sweeps age
    # out the never-occupied lanes) and warms the steady-state gather
    # shapes with one full-width sweep — production scribe cadence keeps
    # all of this warm; a bench that compiled mid-loop would charge XLA
    # compile time to the serving path.
    intents, rows = generate_round()
    err, stamped = svc.submit_round(intents, rows)
    assert not err.any(), "warmup tickets must stay on the fast path"
    for _ in range(3):
        svc.summarize_dirty(threshold=1, max_docs=min(256, n_docs))
    svc.summarize_dirty(threshold=1, max_docs=max(1, n_docs // rounds))
    assert int(svc.device_errors().sum()) == 0, (
        "warmup round must be clean — errs below count timed rounds only"
    )

    t0 = time.perf_counter()
    t_gen = 0.0  # host content generation
    t_ticket = 0.0  # native deli ticket loops (inside submit_round)
    t_scribe = 0.0  # logTail writes
    t_summary = 0.0  # device-scribe stage+finish host time
    sum_break: dict = {}  # per-stage scribe breakdown (summed over rounds)
    logtail_writes = 0
    summary_docs = 0
    summary_bytes = 0
    th = time.perf_counter()
    batch = generate_round()  # round 0's boxcar
    t_gen += time.perf_counter() - th
    def _account(pend) -> None:
        nonlocal summary_docs, summary_bytes
        nd, nb = pend.finish()
        summary_docs += nd
        summary_bytes += nb
        for k2, v in pend.breakdown.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                sum_break[k2] = sum_break.get(k2, 0.0) + v

    # Pipelined rounds, built around the link being full-duplex: round
    # r's apply is dispatched from a pre-staged upload; the sweep's slim
    # dirtiness scan starts streaming behind it; the host overlaps the
    # device with logTail writes, the next boxcar's generation, AND the
    # next round's ticket+upload (stage_round), so round r+1's H2D
    # streams WHILE round r's scribe gathers drain D2H. The err lane is
    # sticky, so the correctness barrier is one readback after the loop.
    max_sweep = max(1, n_docs // rounds)
    tok = svc.stage_round(*batch)
    t_ticket += svc.last_ticket_s
    for r in range(rounds):
        err, stamped = svc.commit_round(tok)
        assert not err.any(), "steady-state stream must stay on fast path"
        pend = svc.begin_summarize_dirty(threshold=1, max_docs=max_sweep)
        th = time.perf_counter()
        logtail_writes += scribe_logtail(r, stamped)
        t_scribe += time.perf_counter() - th
        if r + 1 < rounds:
            th = time.perf_counter()
            batch = generate_round()
            t_gen += time.perf_counter() - th
            tok = svc.stage_round(*batch)
            t_ticket += svc.last_ticket_s
        th = time.perf_counter()
        pend.stage()
        _account(pend)
        t_summary += time.perf_counter() - th
    errs = int(svc.device_errors().sum())  # the sticky-err barrier
    dt = time.perf_counter() - t0

    # Device step time, measured honestly: ONE fused apply+compact over a
    # freshly generated, freshly ticketed round, with the op wire
    # uploaded and DRAINED first — device_put is async on this transport,
    # so an undrained upload lands in whatever readback comes next and
    # can masquerade as 4x of device time (r3's step numbers mixed the
    # two).
    batch = generate_round()
    out, terr = svc.fseq.ticket_batch(batch[0])
    fresh = np.array(batch[1], np.int32)
    fresh[:, :, F_SEQ] = out[:, :, 0]
    fresh[:, :, F_REF] = batch[0][:, :, 2]
    fresh[:, :, F_MSN] = out[:, :, 1]
    fresh[:, :, F_CLIENT] = batch[0][:, :, 0]
    jops = svc._upload_round(fresh, out, terr)
    np.asarray(jops[:1, :1, :1])  # drain the upload + expand
    floor = []
    for _ in range(3):
        td = time.perf_counter()
        np.asarray(svc.scalars[:1, :1])
        floor.append(time.perf_counter() - td)
    floor_ms = min(floor) * 1e3
    td = time.perf_counter()
    svc.tables, svc.scalars = apply_compact_packed(
        svc.tables, svc.scalars, jops,
        block_docs=blk, interpret=not on_tpu,
    )
    np.asarray(svc.scalars[:1, :1])
    device_step_ms = (time.perf_counter() - td) * 1e3 - floor_ms

    total = n_docs * ops_per_doc * rounds
    return _emit(
        metric="deli_scribe_e2e_ops_per_sec", value=round(total / dt),
        unit="ops/s", config=5, n_docs=n_docs, host_docs=n_docs,
        service_path="TpuFleetService",
        # Per-stage wall breakdown (VERDICT r3 #1): gen is bench content
        # generation; ticket the native deli loop; scribe the batched
        # logTail writes; summary the device-scribe host time, itself
        # split in summary_stages (scan/dispatch/transfer/serialize/
        # store — transfer is the D2H wait AFTER overlap).
        stage_gen_s=round(t_gen, 3),
        stage_ticket_s=round(t_ticket, 3),
        stage_scribe_s=round(t_scribe, 3),
        stage_summary_s=round(t_summary, 3),
        summary_stages={
            k2: round(v, 1) for k2, v in sorted(sum_break.items())
        },
        host_tickets_per_sec=round(total / max(t_ticket, 1e-9)),
        host_backend=host_backend,
        logtail_writes=logtail_writes,
        summary_writes=summary_docs,
        summary_readback_ms=round(t_summary * 1e3, 1),
        summary_bytes_per_doc=round(summary_bytes / max(summary_docs, 1)),
        device_step_ms=round(device_step_ms, 3),
        readback_floor_ms=round(floor_ms, 1),
        wire16_rounds=svc.wire16_rounds, wire32_rounds=svc.wire32_rounds,
        errs=errs,
    )


def config6_big_docs(n_docs: int, target_rows: int, on_tpu: bool) -> None:
    """Throughput at REALISTIC document sizes (VERDICT r1 Weak #5): every
    round-1 bench ended rounds with a whole-doc remove, so steady-state
    tables held ≲64 tiny rows. Here documents GROW through the fleet's
    capacity lifecycle (pool promotion, zero drops) to ``target_rows``
    live rows each, then the timed phase measures apply+compact at that
    size with a balanced insert/remove mix. 16 distinct op scripts tiled
    across the fleet (device timing is shape-dependent, not
    data-dependent)."""
    from fluidframework_tpu.ops import encode as E
    from fluidframework_tpu.parallel.fleet import DocFleet
    from fluidframework_tpu.protocol.constants import OP_WIDTH

    rng = np.random.default_rng(0)
    scripts = min(16, n_docs)
    k = 32
    fleet = DocFleet(n_docs=n_docs, capacity=256, high_water=0.7)
    seqs = [0] * scripts
    lens = [0] * scripts

    def round_ops(grow: bool) -> np.ndarray:
        ops = np.zeros((n_docs, k, OP_WIDTH), np.int32)
        for d in range(scripts):
            for i in range(k):
                seqs[d] += 1
                remove = (
                    lens[d] > 8
                    and rng.random() < (0.05 if grow else 0.5)
                )
                if remove:
                    a = int(rng.integers(0, lens[d] - 4))
                    ops[d, i] = E.remove(
                        a, a + 4, seq=seqs[d], ref=seqs[d] - 1,
                        client=int(rng.integers(0, 8)),
                        msn=max(0, seqs[d] - 64),
                    )
                    lens[d] -= 4
                else:
                    ops[d, i] = E.insert(
                        int(rng.integers(0, lens[d] + 1)), 10 + seqs[d], 4,
                        seq=seqs[d], ref=seqs[d] - 1,
                        client=int(rng.integers(0, 8)),
                        msn=max(0, seqs[d] - 64),
                    )
                    lens[d] += 4
        for d in range(scripts, n_docs):
            ops[d] = ops[d % scripts]
        return ops

    # Growth phase (untimed): drive docs to the target size through the
    # promotion lifecycle.
    while True:
        fleet.apply(round_ops(grow=True))
        fleet.compact()
        fleet.check_and_migrate()
        counts = fleet.doc_counts(list(range(scripts)))
        if int(counts.min()) >= target_rows:
            break
    stats = fleet.stats()
    assert stats["docs_with_errors"] == 0, stats

    # Warmup to promotion quiescence: steady-state rounds until no doc
    # promotes (each new pool shape compiles once, outside the timed loop).
    for _ in range(12):
        fleet.apply(round_ops(grow=False))
        fleet.compact()
        if not fleet.check_and_migrate():
            break
    iters = 3
    t0 = time.perf_counter()
    t_routing = 0.0
    t_gen = 0.0
    for _ in range(iters):
        tg = time.perf_counter()
        ops = round_ops(grow=False)
        t_gen += time.perf_counter() - tg
        fleet.apply(ops)
        t_routing += fleet.last_routing_s
        fleet.compact()
        fleet.check_and_migrate()
    stats = fleet.stats()
    assert stats["docs_with_errors"] == 0, stats
    dt = time.perf_counter() - t0
    rows_now = stats["rows_in_use"] // n_docs
    _emit(
        metric="big_doc_ops_per_sec", value=round(n_docs * k * iters / dt),
        unit="ops/s", config=6, n_docs=n_docs,
        live_rows_per_doc=rows_now, capacity_tiers=stats["pools"],
        migrations=stats["migrations"], errs=stats["docs_with_errors"],
        routing_s=round(t_routing, 3), gen_s=round(t_gen, 3),
        routing_pct=round(100 * t_routing / dt, 1),
    )


def _bulk_connect(svc, doc_ids):
    """One writer connection per document through the REAL join path
    (sequenced ClientJoin via deli), but batched: all join records land
    on rawdeltas first, ONE pipeline drain sequences them all, then
    tokens match up — svc.connect()'s per-call full-pipeline pump is
    O(docs^2) stage sweeps at fleet scale."""
    import uuid as _uuid

    from fluidframework_tpu.protocol.types import MessageType
    from fluidframework_tpu.service.lambdas import RAW_TOPIC
    from fluidframework_tpu.service.pipeline import PipelineConnection

    conns = {}
    for d in doc_ids:
        token = f"c-{_uuid.uuid4().hex[:12]}"
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


def config7_pipeline_serving(
    n_docs: int, ops_per_doc: int, rounds: int, socket_docs: int,
    json_docs: int = 1024,
) -> None:
    """The PRODUCT pipeline path at fleet scale (VERDICT r3 do #3, r4 do
    #1): the path network clients actually ride — front-door ingest ->
    rawdeltas -> deli -> deltas -> TpuDeliLambda -> DeviceFleetBackend
    gathered staging -> DocFleet dispatch — measured at >=10k channels
    with every stage's wall attributed (reference: the per-document
    partition loop, ``lambdas-driver/src/document-router/
    documentLambda.ts:20`` + ``deli/lambda.ts:742``).

    Round 5: the PRIMARY wire is the batched binary op frame
    (``protocol/opframe.py``) — clients ship int32 kernel rows in planar
    frames, deli tickets each frame in one vectorized call, and the
    device stage stages rows with zero per-op Python. The per-op JSON
    wire (r4's 5.7k ops/s bottleneck) remains the compat path and is
    measured alongside at ``json_docs`` so the decode price stays an
    attributed number. A socket sub-measurement drives real websocket
    clients end-to-end at a smaller doc count (per-op socket cost is
    per-connection, so it scales out with listener processes, not with
    the fleet)."""
    from fluidframework_tpu.service.pipeline import PipelineFluidService

    # Round-sized boxcars: with the frame wire the decode is gone, so the
    # per-dispatch cost is the next stage up — one flush per round
    # (instead of 4096-row sub-boxcars) cuts ~48 dispatch enqueues to ~2.
    # Per-doc chunking inside flush still respects tier headroom.
    # checkpoint_every follows the reference's heuristic scale (<=500
    # messages between checkpoints, config.json:164-176) rather than the
    # test default of 10 — checkpoint serialization is real per-message
    # host cost on the serving path.
    svc = PipelineFluidService(
        n_partitions=8, device_max_batch=max(1 << 17, n_docs * ops_per_doc),
        checkpoint_every=500,
    )
    doc_ids = [f"d{i}" for i in range(n_docs)]
    conns = _bulk_connect(svc, doc_ids)
    rec = _config7_measure(
        svc, doc_ids, conns, ops_per_doc, rounds, wire="frame",
        metric="pipeline_serving_ops_per_sec",
    )
    # Compat wire at reduced scale: the decode price, attributed.
    jdocs = [f"j{i}" for i in range(min(json_docs, n_docs))]
    jsvc = PipelineFluidService(n_partitions=8, device_max_batch=4096)
    jconns = _bulk_connect(jsvc, jdocs)
    _config7_measure(
        jsvc, jdocs, jconns, ops_per_doc, max(1, rounds - 1), wire="json",
        metric="pipeline_serving_json_wire_ops_per_sec",
    )
    _config7_socket(socket_docs)
    return rec


def _config7_measure(
    svc, doc_ids, conns, ops_per_doc: int, rounds: int, wire: str,
    metric: str,
) -> dict:
    from fluidframework_tpu.protocol.constants import (
        F_ARG, F_LEN, F_REF, F_SEQ, F_TYPE, OP_INSERT, OP_WIDTH,
    )
    from fluidframework_tpu.protocol.opframe import OpFrame
    from fluidframework_tpu.protocol.types import DocumentMessage, MessageType
    from fluidframework_tpu.service.lambdas import RAW_TOPIC

    n_docs = len(doc_ids)
    stages = [
        ("deli", svc._deli),
        ("scribe", svc._scribe),
        ("scriptorium", svc._scriptorium),
        ("broadcaster", svc._broadcaster),
        ("signals", svc._signals),
        ("device_decode", svc._device_runner),
        ("foreman", svc._foreman),
    ]
    stage_s = {name: 0.0 for name, _r in stages}
    flush_staging_s = flush_dispatch_s = flush_routing_s = 0.0
    submit_s = 0.0
    cseq = {d: 0 for d in doc_ids}
    orig = {d: 0 for d in doc_ids}
    # Heads advance deterministically (each doc receives only its own
    # ops_per_doc ops per round) — svc.doc_head is an O(log) dict max.
    heads = {d: conns[d].join_seq for d in doc_ids}
    mint = 1 << 14  # SharedString._MINT_STRIDE: orig ids scope to conn_no

    alphabet = "abcdefghijklmnopqrstuvwxyz"
    base_rows = np.zeros((ops_per_doc, OP_WIDTH), np.int32)
    base_rows[:, F_TYPE] = OP_INSERT
    base_rows[:, F_LEN] = 1
    ar = np.arange(ops_per_doc, dtype=np.int32)

    # Frame rounds build as ONE [D, K, W] numpy pass (all docs progress in
    # lockstep, so the texts tuple is shared) and land on rawdeltas via
    # the bulk front door — the per-doc Python is one OpFrame wrap.
    clients_l = [conns[d].client_id for d in doc_ids]
    heads_a = np.fromiter(
        (conns[d].join_seq for d in doc_ids), np.int64, n_docs
    )
    connno_a = np.fromiter(
        (conns[d].conn_no for d in doc_ids), np.int64, n_docs
    )
    frame_round = [0]

    def send_frames(timed_round: bool) -> None:
        nonlocal heads_a
        o0 = frame_round[0] * ops_per_doc
        texts = tuple(
            alphabet[(o0 + 1 + i) % 26] for i in range(ops_per_doc)
        )
        rows_all = np.tile(base_rows, (n_docs, 1, 1))
        rows_all[:, :, F_SEQ] = o0 + 1 + ar[None, :]
        rows_all[:, :, F_REF] = heads_a[:, None]
        rows_all[:, :, F_ARG] = (
            connno_a[:, None] * mint + o0 + 1 + ar[None, :]
        )
        svc.submit_frames_bulk(
            (
                (d, clients_l[i], OpFrame("s", rows_all[i], texts))
                for i, d in enumerate(doc_ids)
            ),
            pump=False,
        )
        frame_round[0] += 1
        heads_a += ops_per_doc

    def send_json(timed_round: bool) -> None:
        for d in doc_ids:
            ref = heads[d]
            client = conns[d].client_id
            for _i in range(ops_per_doc):
                cseq[d] += 1
                orig[d] += 1
                svc.log.send(
                    RAW_TOPIC, d,
                    {"t": "op", "client": client,
                     "msg": DocumentMessage(
                         client_sequence_number=cseq[d],
                         reference_sequence_number=ref,
                         type=MessageType.OPERATION,
                         contents={"address": "s", "contents": {
                             "k": "ins", "pos": 0,
                             "text": alphabet[orig[d] % 26],
                             "orig": conns[d].conn_no * mint + orig[d],
                         }},
                     )},
                )
            heads[d] += ops_per_doc

    send = send_frames if wire == "frame" else send_json

    def run_round(r: int, timed: bool) -> None:
        nonlocal submit_s, flush_staging_s, flush_dispatch_s
        nonlocal flush_routing_s
        pre = dict(svc.device.flush_totals)
        t0 = time.perf_counter()
        send(timed)
        t1 = time.perf_counter()
        if timed:
            submit_s += t1 - t0
        while True:
            n = 0
            for name, runner in stages:
                if runner is None:
                    continue
                ts = time.perf_counter()
                n += runner.pump()
                if timed:
                    stage_s[name] += time.perf_counter() - ts
            if n == 0:
                break
        svc.flush_device()
        if timed:
            tot = svc.device.flush_totals
            flush_staging_s += tot["staging_s"] - pre["staging_s"]
            flush_dispatch_s += tot["dispatch_s"] - pre["dispatch_s"]
            # r16: the fleet-side routing wall left staging_s for its
            # own bucket (staging_s is now a pure derived view of the
            # profiler intervals) — report it so the flush breakdown
            # still sums to the flush wall across rounds.
            flush_routing_s += tot["routing_s"] - pre["routing_s"]
        # Broadcast delivery was already paid above; drop the inboxes so a
        # long run's memory stays bounded (a real room's sockets drain).
        for c in conns.values():
            c.inbox.clear()

    run_round(0, timed=False)  # warmup: compiles the flush shapes
    t0 = time.perf_counter()
    for r in range(1, rounds + 1):
        run_round(r, timed=True)
    # Barrier: the flush dispatches are async on TPU.
    for pool in svc.device.fleet.pools.values():
        pool.state.count.block_until_ready()
    wall = time.perf_counter() - t0

    total_ops = n_docs * ops_per_doc * rounds
    stats = svc.device.stats()
    assert stats["docs_with_errors"] == 0, stats
    assert stats["ops_applied"] == total_ops + n_docs * ops_per_doc, stats

    # The read path, sampled: text + summary straight from device state.
    sample = doc_ids[:: max(1, n_docs // 64)][:64]
    tr = time.perf_counter()
    for d in sample:
        want = "".join(
            chr(97 + (o % 26))
            for o in range((rounds + 1) * ops_per_doc, 0, -1)
        )
        assert svc.device.text(d, "s") == want, d
    t_text = time.perf_counter() - tr
    tr = time.perf_counter()
    for d in sample:
        s = svc.device.channel_summary(d, "s")
        assert s["count"] > 0
    t_summary = time.perf_counter() - tr

    pipeline_s = sum(stage_s.values())
    return _emit(
        metric=metric,
        value=round(total_ops / wall),
        unit="ops/s", config=7, wire=wire, n_docs=n_docs,
        ops_per_doc=ops_per_doc,
        rounds=rounds, channels=stats["channels"],
        submit_s=round(submit_s, 3),
        stage_s={k: round(v, 3) for k, v in stage_s.items()},
        pipeline_s=round(pipeline_s, 3),
        flush_staging_s=round(flush_staging_s, 4),
        flush_dispatch_s=round(flush_dispatch_s, 4),
        flush_routing_s=round(flush_routing_s, 4),
        read_text_ms_per_doc=round(1e3 * t_text / len(sample), 3),
        read_summary_ms_per_doc=round(1e3 * t_summary / len(sample), 3),
        errs=stats["docs_with_errors"],
    )


def _config7_socket(socket_docs: int) -> None:
    # -- socket ingest sub-measurement ---------------------------------------
    # The server keeps the accelerator; the CLIENTS run in a subprocess
    # pinned to the CPU through its environment before it imports JAX
    # (the realistic topology — client replicas are remote CPU processes;
    # and one process per chip: a child that reached for the chip its
    # parent holds would fail or hang).
    import os
    import subprocess
    import sys

    from fluidframework_tpu.service.network_server import FluidNetworkServer
    from fluidframework_tpu.service.pipeline import PipelineFluidService

    srv = FluidNetworkServer(
        service=PipelineFluidService(
            n_partitions=4, device_flush_min_rows=256
        )
    )
    srv.start()
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--socket-child",
             "127.0.0.1", str(srv.port), str(socket_docs), "8"],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        lines = [
            ln for ln in out.stdout.splitlines() if ln.startswith("{")
        ]
        assert lines, f"socket child failed: {out.stderr[-2000:]}"
        rec = json.loads(lines[-1])
        _emit(
            metric="socket_ingest_ops_per_sec", value=rec["ops_per_sec"],
            unit="ops/s", config=7, socket_docs=socket_docs,
            ops_per_doc=8, connect_s=rec["connect_s"],
            converge_s=rec["converge_s"],
        )
    finally:
        srv.stop()


def socket_child(host: str, port: int, n_docs: int, k: int) -> None:
    """Client half of config 7's socket measurement: runs in its own
    process, pinned to the CPU by the parent through JAX_PLATFORMS.
    Converged = every op ACKED over the socket (pending empty —
    optimistic local text proves nothing), then the device replica is
    read back over REST and checked."""
    from fluidframework_tpu.drivers.network_driver import NetworkFluidService
    from fluidframework_tpu.models.shared_string import SharedString
    from fluidframework_tpu.runtime.container import ContainerRuntime

    t0 = time.perf_counter()
    rts = []
    for i in range(n_docs):
        net = NetworkFluidService(host, port, push=True)
        rts.append(
            ContainerRuntime(net, f"s{i}", channels=(SharedString("s"),))
        )
    connect_s = time.perf_counter() - t0

    def burst() -> float:
        t0 = time.perf_counter()
        for rt in rts:
            ch = rt.get_channel("s")
            for j in range(k):
                ch.insert_text(0, chr(97 + j))
            rt.flush()
        deadline = time.perf_counter() + 600
        while time.perf_counter() < deadline:
            for rt in rts:
                rt.process_incoming()
            if all(not rt.pending for rt in rts):
                break
            time.sleep(0.005)
        assert all(not rt.pending for rt in rts), (
            "socket ingest did not converge"
        )
        return time.perf_counter() - t0

    # Warmup burst: the server's fleet pools grow through their slot
    # sizes here, so their one-time kernel compiles don't bill the
    # steady-state number (every other config warms the same way).
    burst()
    converge_s = burst()
    reader = NetworkFluidService(host, port)
    assert (
        reader.get_channel_text("s0", "s")
        == rts[0].get_channel("s").get_text()
    )
    for rt in rts:
        rt.disconnect()
    _emit(
        ops_per_sec=round(n_docs * k / converge_s),
        connect_s=round(connect_s, 2), converge_s=round(converge_s, 2),
    )


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--socket-child":
        socket_child(
            sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
            int(sys.argv[5]),
        )
        return
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, default=0, help="0 = all")
    ap.add_argument("--full", action="store_true",
                    help="BASELINE-sized runs")
    args = ap.parse_args()

    import bench
    from fluidframework_tpu.utils import enable_compile_cache

    enable_compile_cache()
    bench.require_tpu("bench_configs.py")
    on_tpu = True
    full = args.full

    if args.config in (0, 1):
        config1_single_doc_replay(10_000 if full else 1_000)
    if args.config in (0, 2):
        bench.main()
        config2b_apply_latency(
            n_docs=2048 if full else 64,
            k=16,
            steps=50 if full else 3,
            on_tpu=on_tpu,
        )
    if args.config in (0, 3):
        config3_tree_rebase(
            n_docs=1000 if full else 20, n_edits=1000 if full else 60
        )
        config3b_tree_rebase_device(
            n_docs=1024 if full else 32,
            n_commits=1000 if full else 24,
            scripts=64 if full else 8,
        )
        config3c_em_kernel_concurrent(
            n_docs=1024 if full else 8,
            n_commits=512 if full else 32,
            scripts=16 if full else 4,
            # Wave >> authoring lag: the per-wave ring-seed replay spans
            # only the lag window, so big waves amortize it toward zero.
            wave=128 if full else 16,
        )
        # Move-bearing workload at a realistic move rate: device-native
        # since r7 — device_fraction here is the acceptance number, not
        # a fallback tax.
        config3c_em_kernel_concurrent(
            n_docs=512 if full else 8,
            n_commits=256 if full else 32,
            scripts=8 if full else 4,
            wave=128 if full else 16,
            move_prob=0.05,
        )
    if args.config in (0, 4):
        config4_matrix_axis_merge(
            n_docs=10_000 if full else 16, k=64 if full else 16,
            on_tpu=on_tpu,
        )
    if args.config in (0, 5):
        config5_deli_scribe_e2e(
            n_docs=100_000 if full else 64,
            ops_per_doc=16 if full else 8,
            on_tpu=on_tpu,
        )
    if args.config in (0, 6):
        # >=10k docs so the lifecycle's HOST cost (routing gathers, count
        # readbacks, migration copies) is a measured number at fleet scale.
        # One promotion wave (256->512) at fleet scale; the deep
        # many-tier lifecycle is chip_smoke.py's tiers phase and the CI
        # shape every run. (A 128 start tier underflows
        # this generator: ~30 inserts/round plus splits can outgrow the
        # 0.3*128-row promotion headroom inside one boxcar.)
        config6_big_docs(
            n_docs=10_240 if full else 8,
            target_rows=320 if full else 256,
            on_tpu=on_tpu,
        )
    if args.config in (0, 7):
        # >=10k channels so the general-wire serving path (the one socket
        # clients ride) is measured at the scale VERDICT r3 Weak #3 asked
        # for, not the 8-doc test scale.
        config7_pipeline_serving(
            n_docs=12_288 if full else 48,
            ops_per_doc=8 if full else 4,
            rounds=2,
            socket_docs=96 if full else 8,
        )


if __name__ == "__main__":
    main()
