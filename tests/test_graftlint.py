"""graftlint (tools/graftlint): fixture-snippet unit tests per pass — at
least one true positive and one true negative each — plus the wire-drift
lock behavior (a mutated opframe codec must trip the fingerprint check)
and the repo-wide CI invariant (`--check` exits 0 with an empty
baseline)."""

import ast
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tools():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.graftlint import core  # noqa: F401
    from tools.graftlint.passes import (
        DeterminismPass,
        HostSyncPass,
        RecompileHazardPass,
        wire_drift,
    )

    return core, HostSyncPass, RecompileHazardPass, DeterminismPass, wire_drift


def _run_pass(pass_cls, snippet, tmp_path, relpath="fluidframework_tpu/x.py"):
    """Run one pass over a fixture snippet; returns surviving findings
    (pragma suppression applied, baseline not)."""
    core = _tools()[0]
    abspath = tmp_path / "snippet.py"
    abspath.write_text(textwrap.dedent(snippet))
    src = core.ModuleSource.load(str(tmp_path), "snippet.py")
    src.path = relpath  # scopes are resolved by the runner, not the pass
    p = pass_cls()
    return [
        f for f, node in p.run(src) if not src.suppressed(f, node)
    ]


# -- host-sync -----------------------------------------------------------------


def test_host_sync_flags_asarray_on_device_attr(tmp_path):
    _, HostSync, *_ = _tools()
    findings = _run_pass(
        HostSync,
        """
        import numpy as np

        def stats(pool):
            return np.asarray(pool.state.err)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "device→host" in findings[0].message


def test_host_sync_flags_scalarize_of_jitted_result(tmp_path):
    _, HostSync, *_ = _tools()
    findings = _run_pass(
        HostSync,
        """
        import jax
        import numpy as np

        @jax.jit
        def _scan(s):
            return s.sum()

        def probe(pool):
            dev = _scan(pool.state)
            return int(dev)
        """,
        tmp_path,
    )
    assert [f.message.split("(")[0] for f in findings] == ["int"]


def test_host_sync_true_negatives(tmp_path):
    _, HostSync, *_ = _tools()
    findings = _run_pass(
        HostSync,
        """
        import numpy as np

        def host_only(rows):
            # host numpy staging is NOT a readback
            buf = np.asarray(rows, np.int64)
            n = int(buf.max())
            # .shape metadata is host-resident even on device arrays
            def shapes(pool):
                return int(pool.state.shape[0])
            # np.asarray result is host: downstream int() is clean
            host = np.asarray(pool_state_like(), np.int32)
            return n, int(host[0])

        def pool_state_like():
            return [1, 2, 3]
        """,
        tmp_path,
    )
    assert findings == []


def test_host_sync_pragma_suppresses_with_reason(tmp_path):
    _, HostSync, *_ = _tools()
    findings = _run_pass(
        HostSync,
        """
        import numpy as np

        def stats(pool):
            return np.asarray(pool.state.err)  # graftlint: readback(explicit stats barrier)
        """,
        tmp_path,
    )
    assert findings == []


def test_host_sync_pragma_without_reason_does_not_suppress(tmp_path):
    core, HostSync, *_ = _tools()
    abspath = tmp_path / "snippet.py"
    abspath.write_text(
        "import numpy as np\n"
        "def stats(pool):\n"
        "    return np.asarray(pool.state.err)  # graftlint: readback\n"
    )
    src = core.ModuleSource.load(str(tmp_path), "snippet.py")
    survivors = [
        f for f, node in HostSync().run(src) if not src.suppressed(f, node)
    ]
    assert len(survivors) == 1  # reasonless pragma suppresses nothing
    pragma_errors = core.pragma_findings(src)
    assert len(pragma_errors) == 1
    assert "no reason" in pragma_errors[0].message


def test_host_sync_telemetry_slice_readback_pragma(tmp_path):
    """The r9 telemetry-lane shape: a jitted per-shard reduction whose
    single batched result is read back once per /metrics scrape. The
    np.asarray IS a device→host transfer — flagged bare, suppressed by
    the reasoned one-readback-per-scrape pragma."""
    _, HostSync, *_ = _tools()
    snippet = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def _pool_telemetry(state, n_shards):
        return state.count.reshape(n_shards, -1).sum(axis=1)

    def telemetry_slice(pool, n_shards):
        dev = _pool_telemetry(pool.state, n_shards)
        return np.asarray(dev){pragma}
    """
    bare = _run_pass(HostSync, snippet.format(pragma=""), tmp_path)
    assert len(bare) == 1 and "device→host" in bare[0].message
    annotated = _run_pass(
        HostSync,
        snippet.format(
            pragma="  # graftlint: readback(the ONE batched telemetry"
            " readback per /metrics scrape)"
        ),
        tmp_path,
    )
    assert annotated == []


def test_host_sync_pump_scan_consume_readback_pragma(tmp_path):
    """The r10 pump's ONLY legal readback: consuming the one-boxcar-
    stale health scan. The np.asarray over the jitted scan result IS a
    device→host transfer — flagged bare, suppressed by the reasoned
    one-readback-per-round pragma the production pump carries."""
    _, HostSync, *_ = _tools()
    snippet = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def _pool_scan(state):
        return jnp.stack([state.count, state.err])

    def pump_round(pool, staged_rows):
        dev = _pool_scan(pool.state)  # begin_scan: async, no transfer
        host = np.asarray(dev){pragma}
        return host
    """
    bare = _run_pass(HostSync, snippet.format(pragma=""), tmp_path)
    assert len(bare) == 1 and "device→host" in bare[0].message
    annotated = _run_pass(
        HostSync,
        snippet.format(
            pragma="  # graftlint: readback(the pump's one-boxcar-stale"
            " health scan — the only device→host transfer per round)"
        ),
        tmp_path,
    )
    assert annotated == []


def test_host_sync_ticker_scan_prefetch_readback_pragma(tmp_path):
    """The r12 deadline ticker's off-loop prefetch shape: the blocking
    half of the scan consume (np.array over the token's device arrays)
    moved off the event loop. It is the SAME one-boxcar-stale transfer
    the pump would run inline — the ticker performs ZERO new readbacks —
    so the np.array is flagged bare and suppressed only by the reasoned
    pragma the production ``scan_transfer`` carries."""
    _, HostSync, *_ = _tools()
    snippet = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def _pool_scan(state):
        return jnp.stack([state.count, state.err])

    def tick_prefetch(pool):
        # the deadline ticker's off-loop half: transfer the in-flight
        # scan token's device snapshot (run_in_executor), so the on-loop
        # feed consumes it without blocking
        dev = _pool_scan(pool.state)  # the token's async snapshot
        return np.array(dev){pragma}
    """
    bare = _run_pass(HostSync, snippet.format(pragma=""), tmp_path)
    assert len(bare) == 1 and "device→host" in bare[0].message
    annotated = _run_pass(
        HostSync,
        snippet.format(
            pragma="  # graftlint: readback(the pump's one-boxcar-stale"
            " health scan, run off-loop by the deadline ticker — the"
            " same single transfer per round, zero new readbacks)"
        ),
        tmp_path,
    )
    assert annotated == []


# -- recompile-hazard ----------------------------------------------------------


def test_recompile_flags_jit_in_loop(tmp_path):
    _, _, Recompile, *_ = _tools()
    findings = _run_pass(
        Recompile,
        """
        import jax

        for blk in (8, 16):
            step = jax.jit(lambda s: s)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "inside a loop" in findings[0].message


def test_recompile_flags_per_call_construction(tmp_path):
    _, _, Recompile, *_ = _tools()
    findings = _run_pass(
        Recompile,
        """
        import jax

        def make_step(mesh):
            return jax.jit(lambda s: s)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "per call" in findings[0].message


def test_recompile_allows_cached_and_module_level(tmp_path):
    _, _, Recompile, *_ = _tools()
    findings = _run_pass(
        Recompile,
        """
        import functools
        import jax

        _step = jax.jit(lambda s: s)  # module level: compiled once

        @functools.lru_cache(maxsize=None)
        def make_step(mesh):
            return jax.jit(lambda s: s)  # cached builder

        @jax.jit
        def entry(tables):
            return pl.pallas_call(kernel)(tables)  # under the jit cache
        """,
        tmp_path,
    )
    assert findings == []


def test_recompile_flags_traced_branch_not_static(tmp_path):
    _, _, Recompile, *_ = _tools()
    findings = _run_pass(
        Recompile,
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(1,))
        def f(x, flag):
            if flag:          # static: fine
                x = x + 1
            if x.shape[0] > 2:  # shape: fine
                x = x * 2
            if x:             # traced: flagged
                x = x - 1
            return x
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "traced value" in findings[0].message
    assert "'x'" in findings[0].message or " x " in findings[0].message


def test_recompile_flags_aot_entry_built_per_flush(tmp_path):
    """TP: an AOT entry lowered+compiled inside the per-flush dispatch
    function rebuilds the executable every flush — the exact hazard the
    parallel/aot.py shape-bucket cache exists to prevent."""
    _, _, Recompile, *_ = _tools()
    findings = _run_pass(
        Recompile,
        """
        import jax

        def dispatch(state, rows, slots):
            exe = jax.jit(lambda s, r, i: s).lower(
                state, rows, slots
            ).compile()
            return exe(state, rows, slots)
        """,
        tmp_path,
    )
    assert len(findings) == 2  # the jit ctor AND the lower().compile()
    assert all("per call" in f.message for f in findings)


def test_recompile_aot_shape_bucket_cache_is_accepted(tmp_path):
    """TN/pragma: the production AOT pattern — lru_cache jitted builders
    plus a dict-probe entry cache whose build branch carries the reasoned
    recompile pragma (parallel/aot.py) — survives the pass clean, pinning
    that entries are built once per shape bucket, never per flush."""
    _, _, Recompile, *_ = _tools()
    findings = _run_pass(
        Recompile,
        """
        import functools
        import jax

        _ENTRIES = {}

        @functools.lru_cache(maxsize=None)
        def _fused_step(n_slots):
            return jax.jit(lambda s, r, i: s, donate_argnums=(0,))

        def call(key, build, *args):
            exe = _ENTRIES.get(key)
            if exe is None:
                # graftlint: recompile(built ONCE per shape-bucket key — the dict probe above IS the cache)
                exe = _ENTRIES[key] = build().lower(*args).compile()
            return exe(*args)
        """,
        tmp_path,
    )
    assert findings == []


# -- determinism ---------------------------------------------------------------


def test_determinism_flags_set_iteration(tmp_path):
    *_, Determinism, _ = _tools()
    findings = _run_pass(
        Determinism,
        """
        def routes(bindings, pending):
            ids = set(bindings) | set(pending)
            return {k: [] for k in ids}
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "no deterministic order" in findings[0].message


def test_determinism_flags_id_keyed_set_and_sort(tmp_path):
    *_, Determinism, _ = _tools()
    findings = _run_pass(
        Determinism,
        """
        def f(ops):
            bad = {id(op) for op in ops}
            ops.sort(key=lambda o: id(o))
            return bad
        """,
        tmp_path,
    )
    assert sorted(
        ("id()-keyed" in f.message, "sort keyed" in f.message)
        for f in findings
    ) == [(False, True), (True, False)]


def test_determinism_true_negatives(tmp_path):
    *_, Determinism, _ = _tools()
    findings = _run_pass(
        Determinism,
        """
        def g(bindings, pending):
            ids = set(bindings) | set(pending)
            ordered = sorted(ids)          # total order: fine
            n = len(ids)                   # order-free fold: fine
            hot = min(ids)                 # value-based: fine
            for k in ordered:              # iterating the sorted list
                n += k
            members = set(bindings)
            members.discard(0)             # membership only: fine
            return n, hot
        """,
        tmp_path,
    )
    assert findings == []


# -- wire-drift ----------------------------------------------------------------


def _opframe_text():
    with open(
        os.path.join(REPO, "fluidframework_tpu/protocol/opframe.py")
    ) as f:
        return f.read()


def test_wire_fingerprint_stable_under_formatting():
    *_, wd = _tools()
    text = _opframe_text()
    fp1 = wd.fingerprint_source(text)
    # whitespace/comment churn must NOT drift the fingerprint
    fp2 = wd.fingerprint_source("# a comment\n" + text + "\n\n# tail\n")
    assert wd.digest(fp1) == wd.digest(fp2)


def test_wire_fingerprint_trips_on_codec_field_change():
    *_, wd = _tools()
    text = _opframe_text()
    fp0 = wd.fingerprint_source(text)
    # 1) magic constant change
    mutated = text.replace("0x4F463152", "0x4F463153", 1)
    assert wd.digest(wd.fingerprint_source(mutated)) != wd.digest(fp0)
    # 2) struct layout change (a reordered/retyped pack string)
    assert "<iiiii" in text
    mutated = text.replace("<iiiii", "<iiiiq", 1)
    assert wd.digest(wd.fingerprint_source(mutated)) != wd.digest(fp0)


def test_wire_drift_gate_end_to_end(tmp_path):
    """A codec edit without --regen-fingerprints fails; regen (with its
    version bump) clears it."""
    core, *_, wd = _tools()
    from tools.graftlint import config
    from tools.graftlint.passes import WireDriftPass

    # fixture repo: one codec module + a lock generated from it
    rel = config.CODEC_MODULES[1]  # protocol/opframe.py
    mod_dir = tmp_path / os.path.dirname(rel)
    mod_dir.mkdir(parents=True)
    (tmp_path / "api-report").mkdir()
    (mod_dir / os.path.basename(rel)).write_text(_opframe_text())

    orig_root = config.REPO_ROOT
    config.REPO_ROOT = str(tmp_path)
    try:
        wd.regenerate(str(tmp_path))
        lock = wd.load_lock(str(tmp_path))
        assert lock["modules"][rel]["version"] == 1

        src = core.ModuleSource.load(str(tmp_path), rel)
        assert list(WireDriftPass().run(src)) == []  # clean

        # mutate the codec: drift must be reported
        mutated = _opframe_text().replace("0x4F463152", "0x4F463154", 1)
        (mod_dir / os.path.basename(rel)).write_text(mutated)
        src = core.ModuleSource.load(str(tmp_path), rel)
        findings = [f for f, _ in WireDriftPass().run(src)]
        assert len(findings) == 1
        assert "fingerprint drift" in findings[0].message
        assert "_RAW_MAGIC" in findings[0].message

        # accept: regen bumps the version and the check turns clean
        changed = wd.regenerate(str(tmp_path))
        assert rel in changed
        lock = wd.load_lock(str(tmp_path))
        assert lock["modules"][rel]["version"] == 2
        src = core.ModuleSource.load(str(tmp_path), rel)
        assert list(WireDriftPass().run(src)) == []
    finally:
        config.REPO_ROOT = orig_root


def test_committed_lock_matches_tree():
    """The committed wire_fingerprints.json must describe the current
    codec sources (the mechanical half of the compat-matrix gate)."""
    *_, wd = _tools()
    from tools.graftlint import config

    lock = wd.load_lock(REPO)["modules"]
    assert set(lock) == set(config.CODEC_MODULES)
    for rel, entry in lock.items():
        with open(os.path.join(REPO, rel)) as f:
            fp = wd.fingerprint_source(f.read(), rel)
        assert wd.digest(fp) == entry["digest"], (
            f"{rel} drifted from the committed fingerprint — run "
            "python -m tools.graftlint --regen-fingerprints in the same "
            "change that moves the wire format"
        )


# -- fault-site ----------------------------------------------------------------


def _fault_site_pass():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.graftlint.passes import FaultSitePass

    return FaultSitePass


def test_fault_site_flags_unknown_site(tmp_path):
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("not.a.site")
        def append(log, frame):
            log.append(frame)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "unknown injection site" in findings[0].message


def test_fault_site_flags_non_literal_name(tmp_path):
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing import faults

        SITE = "store.append"

        @faults.inject_fault(SITE)
        def append(log, frame):
            log.append(frame)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "string literal" in findings[0].message


def test_fault_site_accepts_documented_vocabulary(tmp_path):
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("store.append")
        def append(log, frame):
            log.append(frame)

        @inject_fault("pump.dispatch")
        def dispatch(fleet, docs, rows):
            fleet.dispatch_staged(docs, rows)

        @inject_fault("pump.feed")
        def feed(backend):
            backend.pump_stage()
            return backend.pump_dispatch()

        @inject_fault("admission.decide")
        def decide(ctl, tenant, doc, n):
            return ctl.check(tenant, doc, n)

        @inject_fault("shed.tier")
        def evaluate(ctl, pressure):
            return ctl.tier_for(pressure)
        """,
        tmp_path,
    )
    assert findings == []


def test_fault_site_flags_unregistered_feed_site(tmp_path):
    """The r12 regression shape: a continuous-feed boundary added to a
    production module without declaring it in the vocabulary (e.g. a
    second ticker trigger named off-vocabulary) must fail lint — the
    deadline tick's recovery contract (rows stay buffered, next tick
    re-fires) only exists if the site is documented."""
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("pump.feed_tick")
        def feed_tick(backend):
            return backend.pump_dispatch()
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "unknown injection site" in findings[0].message
    assert "pump.feed_tick" in findings[0].message


def test_fault_site_flags_unregistered_overload_site(tmp_path):
    """The r13 regression shape: an overload boundary added to a
    production module without declaring it in the vocabulary (e.g. a
    second admission check named off-vocabulary) must fail lint — the
    fail-closed contract (op nacked, never silently admitted) only
    exists if the site is documented."""
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("admission.precheck")
        def precheck(ctl, tenant, doc):
            return ctl.check(tenant, doc, 1)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "unknown injection site" in findings[0].message
    assert "admission.precheck" in findings[0].message


def test_fault_site_accepts_read_tier_sites(tmp_path):
    """The r15 read-tier sites — the batched snapshot gather and the
    encode-once fan-out write — are documented vocabulary: production
    boundaries decorated with them pass lint."""
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("read.gather")
        def gather(backend, idxs):
            return backend.fleet.doc_states_start(idxs)

        @inject_fault("push.fanout")
        def push_write(server, session, data):
            session.writer.write(data)
        """,
        tmp_path,
    )
    assert findings == []


def test_fault_site_flags_unregistered_read_site(tmp_path):
    """The r15 regression shape: a read-path boundary added to a
    production module without declaring it in the vocabulary (e.g. a
    second gather named off-vocabulary) must fail lint — the fallback
    contract (per-doc host gathers, counted) only exists if the site is
    documented."""
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("read.batch")
        def batch(backend, keys):
            return backend.doc_states(keys)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "unknown injection site" in findings[0].message
    assert "read.batch" in findings[0].message


def test_fault_site_flags_unregistered_recovery(tmp_path):
    """A vocabulary entry whose recovery kind is not documented is a
    production site nobody catches — a lint failure, not a latent
    surprise."""
    from tools.graftlint.passes import fault_site

    vocab_dir = tmp_path / "fluidframework_tpu" / "testing"
    vocab_dir.mkdir(parents=True)
    (vocab_dir / "faults.py").write_text(
        'SITES = {"store.append": "wishful-thinking"}\n'
        'RECOVERY_KINDS = frozenset({"retry", "fallback"})\n'
    )
    p = fault_site.FaultSitePass()
    p.scope(str(tmp_path))  # pins the fixture root for vocabulary lookup
    src_dir = tmp_path / "mod"
    src_dir.mkdir()
    (src_dir / "m.py").write_text(
        "from fluidframework_tpu.testing.faults import inject_fault\n\n"
        '@inject_fault("store.append")\n'
        "def append(log, frame):\n"
        "    log.append(frame)\n"
    )
    core = _tools()[0]
    src = core.ModuleSource.load(str(tmp_path), "mod/m.py")
    findings = [f for f, _node in p.run(src)]
    assert len(findings) == 1
    assert "no registered recovery policy" in findings[0].message


def test_fault_vocabulary_is_fully_registered():
    """The REAL vocabulary: every production site maps to a documented
    recovery kind, and every site the service decorates is declared."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.graftlint import config as glconfig
    from tools.graftlint.passes import fault_site

    sites, kinds = fault_site._parse_vocabulary(
        os.path.join(REPO, glconfig.FAULT_VOCAB_MODULE)
    )
    assert sites, "vocabulary must not be empty"
    for site, recovery in sites.items():
        assert recovery in kinds, (site, recovery)
    # The parsed (static) vocabulary matches the runtime one.
    from fluidframework_tpu.testing import faults as runtime_faults

    assert sites == runtime_faults.SITES
    assert kinds == set(runtime_faults.RECOVERY_KINDS)


# -- baseline + CI invariant ---------------------------------------------------


def test_baseline_is_committed_empty():
    with open(os.path.join(REPO, "tools/graftlint/baseline.json")) as f:
        assert json.load(f) == []


def test_repo_is_graftlint_clean():
    """The CI gate: `python -m tools.graftlint --check` exits 0 on the
    merged tree (every surviving readback carries a reasoned pragma)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--check"],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_stale_baseline_entry_is_reported(tmp_path):
    core, *_ = _tools()
    baseline = [
        {"rule": "host-sync", "path": "gone.py", "source_line": "x = 1"}
    ]
    survivors, stale = core.apply_baseline([], baseline)
    assert survivors == []
    assert stale == baseline


# -- review-hardening regressions ----------------------------------------------


def test_determinism_flags_set_consumer_in_for_header(tmp_path):
    """`for k in list(ids):` hides the set inside a call in the loop
    header — the consumer check must still see it."""
    *_, Determinism, _ = _tools()
    findings = _run_pass(
        Determinism,
        """
        def f(ids_in):
            ids = set(ids_in)
            out = []
            for k in list(ids):
                out.append(k)
            for j, k in enumerate(ids, 1):
                out.append((j, k))
            return out
        """,
        tmp_path,
    )
    assert len(findings) == 2
    assert all("set" in f.message for f in findings)


def test_baseline_entries_suppress_one_occurrence_each():
    """A copy-pasted duplicate of a baselined line is a NEW finding."""
    core = _tools()[0]
    f = dict(rule="host-sync", path="a.py", col=1,
             message="m", source_line="x = np.asarray(pool.state.err)")
    findings = [
        core.Finding(line=10, **f),
        core.Finding(line=20, **f),
    ]
    baseline = [findings[0].baseline_key()]
    survivors, stale = core.apply_baseline(findings, baseline)
    assert len(survivors) == 1 and survivors[0].line == 20
    assert stale == []


def test_scope_files_matches_outside_package(tmp_path):
    """Scope globs are repo-root-relative: a pattern outside
    fluidframework_tpu/ must match files, not silently cover nothing."""
    core = _tools()[0]
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "x.py").write_text("a = 1\n")
    (tmp_path / "fluidframework_tpu").mkdir()
    (tmp_path / "fluidframework_tpu" / "y.py").write_text("b = 2\n")
    got = core.scope_files(
        str(tmp_path), ("tools/*.py", "fluidframework_tpu/*.py")
    )
    assert got == ["fluidframework_tpu/y.py", "tools/x.py"]


# -- r14 flight-recorder fixtures ----------------------------------------------


def test_fault_site_accepts_journal_dump_site(tmp_path):
    """The r14 flight-recorder dump boundary: ``journal.dump`` is in the
    documented vocabulary (recovery: a failed dump is counted and
    absorbed — the journal is best-effort), so a production module
    carrying the site passes lint."""
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("journal.dump")
        def write_dump(path, payload):
            with open(path, "w", encoding="utf-8") as f:
                f.write(payload)
        """,
        tmp_path,
    )
    assert findings == []


def test_fault_site_flags_unregistered_journal_site(tmp_path):
    """The r14 regression shape: a second journal boundary (e.g. an
    upload site) added off-vocabulary must fail lint — the absorb
    contract only exists if the site is documented."""
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("journal.upload")
        def upload_dump(path):
            return path
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "unknown injection site" in findings[0].message


def test_host_sync_flags_journal_producer_bare_transfer(tmp_path):
    """The flight recorder's zero-readback contract: the journal
    consumes HOST state only — the existing one-boxcar-stale scan and
    /metrics scrape data. A journal producer that runs its OWN
    device→host transfer to enrich an event is a new readback on the
    serving path; the fixture proves the host-sync pass fails it bare
    (and there is deliberately no blessed pragma shape for it: the fix
    is to consume already-transferred data, not to annotate)."""
    _, HostSync, *_ = _tools()
    findings = _run_pass(
        HostSync,
        """
        import numpy as np

        def journal_device_err(pool, journal):
            # WRONG: pulls the err lane synchronously just to journal it
            err = np.asarray(pool.state.err)
            journal.record("device.err", err_docs=int((err != 0).sum()))
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "device→host" in findings[0].message


# -- r16 serving-profiler fixtures ---------------------------------------------


def test_fault_site_accepts_profiler_arm_site(tmp_path):
    """The r16 profiler capture-arm boundary: ``profiler.arm`` is in the
    documented vocabulary (recovery: a failed arm is counted and
    absorbed — arm() returns False and /profilez 503s; the serving path
    never sees it), so a production module carrying the site passes
    lint."""
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("profiler.arm")
        def arm_window(duration_ms):
            return duration_ms
        """,
        tmp_path,
    )
    assert findings == []


def test_fault_site_flags_unregistered_profiler_site(tmp_path):
    """The r16 regression shape: a second profiler boundary (e.g. a
    capture-export site) added off-vocabulary must fail lint — the
    absorb contract only exists if the site is documented."""
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("profiler.capture")
        def export_window(path):
            return path
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "unknown injection site" in findings[0].message


# -- r17 loop-blocking ---------------------------------------------------------


def _loop_blocking_pass():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.graftlint.passes import LoopBlockingPass

    return LoopBlockingPass


def test_loop_blocking_flags_sleep_and_transfer_in_coroutine(tmp_path):
    """TP: a time.sleep directly in a coroutine and a device readback in
    a sync helper the coroutine calls — both reachable from the loop,
    both flagged, the transitive path named in the message."""
    findings = _run_pass(
        _loop_blocking_pass(),
        """
        import time
        import numpy as np

        class S:
            async def ticker(self):
                time.sleep(0.01)
                self._step()

            def _step(self):
                return np.asarray(self.pool.state.err)
        """,
        tmp_path,
    )
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 2, msgs
    assert any("time.sleep" in m for m in msgs)
    assert any(
        "device→host" in m and "ticker -> _step" in m for m in msgs
    )


def test_loop_blocking_off_loop_split_is_clean(tmp_path):
    """TN: the sanctioned pattern — the blocking transfer half runs via
    run_in_executor (the scan_transfer split); the off-loop helper's own
    np.asarray is NOT on-loop reachable."""
    findings = _run_pass(
        _loop_blocking_pass(),
        """
        import asyncio
        import numpy as np

        class S:
            async def tick(self, dev_backend):
                token = dev_backend.prefetch()
                loop = asyncio.get_running_loop()
                host = await loop.run_in_executor(
                    None, self.scan_transfer, token
                )
                return host

            @staticmethod
            def scan_transfer(token):
                return np.asarray(token.dev)
        """,
        tmp_path,
    )
    assert findings == []


def test_loop_blocking_flags_direct_off_loop_helper_call(tmp_path):
    """TP: calling a declared off-loop half synchronously from a
    coroutine defeats the split — flagged by name."""
    findings = _run_pass(
        _loop_blocking_pass(),
        """
        class S:
            async def tick(self):
                return self.scan_transfer(self._token)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "off-loop helper scan_transfer()" in findings[0].message


def test_loop_blocking_loop_entry_roots_apply(tmp_path):
    """The cross-module on-loop contract: device_backend's ``flush`` is
    a configured LOOP_ENTRY root — a blocking op inside it is flagged
    with no async def in sight (network_server's loop calls it)."""
    findings = _run_pass(
        _loop_blocking_pass(),
        """
        import time

        class Backend:
            def flush(self):
                time.sleep(0.001)
        """,
        tmp_path,
        relpath="fluidframework_tpu/service/device_backend.py",
    )
    assert len(findings) == 1
    assert "time.sleep" in findings[0].message


def test_loop_blocking_onloop_pragma_suppresses_with_reason(tmp_path):
    snippet = """
    import numpy as np

    class S:
        async def drain(self):
            {pragma}
            err = np.asarray(self.pool.state.err)
            return err
    """
    bare = _run_pass(
        _loop_blocking_pass(), snippet.format(pragma="pass"), tmp_path
    )
    assert len(bare) == 1
    annotated = _run_pass(
        _loop_blocking_pass(),
        snippet.format(
            pragma="# graftlint: onloop(quiescence barrier — runs only "
            "after ingest went quiet)"
        ),
        tmp_path,
    )
    assert annotated == []


def test_loop_blocking_unbounded_lock_acquire(tmp_path):
    """TP: a bare .acquire() on a lock parks the loop behind any
    producer thread; TN: a timeout-bounded acquire."""
    findings = _run_pass(
        _loop_blocking_pass(),
        """
        class S:
            async def handle(self):
                self._lock.acquire()
                try:
                    return 1
                finally:
                    self._lock.release()

            async def bounded(self):
                return self._lock.acquire(timeout=0.1)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "unbounded Lock.acquire" in findings[0].message


# -- r17 lock-order ------------------------------------------------------------


def _lock_order_pass():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.graftlint.passes import LockOrderPass

    return LockOrderPass


def _run_lock_order(snippet, tmp_path, relpath="fluidframework_tpu/service/x.py"):
    core = _tools()[0]
    abspath = tmp_path / "snippet.py"
    abspath.write_text(textwrap.dedent(snippet))
    src = core.ModuleSource.load(str(tmp_path), "snippet.py")
    src.path = relpath
    p = _lock_order_pass()()
    run_findings = [
        f for f, node in p.run(src) if not src.suppressed(f, node)
    ]
    return run_findings, p.finalize()


def test_lock_order_cycle_detected(tmp_path):
    """TP: two code paths taking the same two locks in opposite order —
    the classic deadlock — is a cycle in the aggregated graph."""
    run_f, cycles = _run_lock_order(
        """
        class A:
            def f(self):
                with self._lock:
                    with self._ring_lock:
                        pass

            def g(self):
                with self._ring_lock:
                    with self._lock:
                        pass
        """,
        tmp_path,
    )
    assert run_f == []
    assert len(cycles) == 1
    assert "lock-order cycle" in cycles[0].message
    assert "A._lock" in cycles[0].message
    assert "A._ring_lock" in cycles[0].message


def test_lock_order_consistent_order_is_clean(tmp_path):
    """TN: the same nesting everywhere is an ordered pair — edges, but
    no cycle."""
    run_f, cycles = _run_lock_order(
        """
        class A:
            def f(self):
                with self._lock:
                    with self._ring_lock:
                        pass

            def g(self):
                with self._lock:
                    with self._ring_lock:
                        pass
        """,
        tmp_path,
    )
    assert run_f == []
    assert cycles == []


def test_lock_order_interprocedural_cycle(tmp_path):
    """The cycle hides behind a call: f holds L and calls helper (which
    takes M); g nests the other way. Still detected via the per-function
    acquire closures."""
    run_f, cycles = _run_lock_order(
        """
        class A:
            def f(self):
                with self._lock:
                    self._helper()

            def _helper(self):
                with self._ring_lock:
                    pass

            def g(self):
                with self._ring_lock:
                    with self._lock:
                        pass
        """,
        tmp_path,
    )
    assert len(cycles) == 1


def test_lock_order_gc_callback_taking_lock_fails(tmp_path):
    """TP: the exact r16 deadlock shape — a gc.callbacks hook that
    acquires a lock (directly or via a metric inc) fails lint."""
    run_f, _ = _run_lock_order(
        """
        import gc

        def _cb(phase, info):
            with _LOCK:
                pass

        gc.callbacks.append(_cb)
        """,
        tmp_path,
        relpath="fluidframework_tpu/telemetry/x.py",
    )
    assert len(run_f) == 1
    assert "must be lock-free by contract" in run_f[0].message

    run_f2, _ = _run_lock_order(
        """
        import gc

        def _cb(phase, info):
            pause_counter().inc(gen="0")

        gc.callbacks.append(_cb)
        """,
        tmp_path,
        relpath="fluidframework_tpu/telemetry/x.py",
    )
    assert len(run_f2) == 1
    assert "_Metric._lock" in run_f2[0].message


def test_lock_order_buffering_gc_callback_is_clean(tmp_path):
    """TN: the production contract — the callback only appends to a
    plain list (GIL-atomic) and normal code drains it."""
    run_f, cycles = _run_lock_order(
        """
        import gc
        import time

        _PENDING = []

        def _cb(phase, info):
            _PENDING.append((time.perf_counter(), info.get("generation")))

        gc.callbacks.append(_cb)
        """,
        tmp_path,
        relpath="fluidframework_tpu/telemetry/x.py",
    )
    assert run_f == [] and cycles == []


def test_lock_order_render_path_nested_hold_fails(tmp_path):
    """TP: a render path acquiring a second lock while holding one —
    the shape the r16 hardening removed (snapshot under the lock,
    render outside it)."""
    run_f, _ = _run_lock_order(
        """
        class MetricsRegistry:
            def render(self):
                with self._lock:
                    for m in self._metrics.values():
                        with m._lock:
                            pass
        """,
        tmp_path,
        relpath="fluidframework_tpu/telemetry/metrics.py",
    )
    assert len(run_f) == 1
    assert "ONE lock at a time" in run_f[0].message


def test_lock_order_self_deadlock(tmp_path):
    run_f, _ = _run_lock_order(
        """
        class A:
            def f(self):
                with self._lock:
                    with self._lock:
                        pass
        """,
        tmp_path,
    )
    assert len(run_f) == 1
    assert "self-deadlock" in run_f[0].message


def test_lock_order_pragma_suppresses_with_reason(tmp_path):
    run_f, _ = _run_lock_order(
        """
        class MetricsRegistry:
            def render(self):
                with self._lock:
                    # graftlint: lockorder(m is registry-private: no other path holds m._lock without the registry lock)
                    with self._m._lock:
                        pass
        """,
        tmp_path,
        relpath="fluidframework_tpu/telemetry/metrics.py",
    )
    assert run_f == []


# -- r17 vocab-drift ------------------------------------------------------------


def _vocab_pass():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.graftlint.passes import VocabDriftPass

    return VocabDriftPass


def test_vocab_drift_flags_undeclared_journal_kind(tmp_path):
    findings = _run_pass(
        _vocab_pass(),
        """
        from fluidframework_tpu.telemetry import journal

        def submit(doc):
            journal.record("frame.submitted", doc=doc)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "undeclared journal event kind 'frame.submitted'" in (
        findings[0].message
    )


def test_vocab_drift_accepts_declared_kinds_and_conditional(tmp_path):
    """TN: declared kinds pass, including the two-literal conditional
    shape the admission path uses."""
    findings = _run_pass(
        _vocab_pass(),
        """
        from fluidframework_tpu.telemetry import journal, profiler

        def submit(doc, admitted):
            journal.record("frame.submit", doc=doc)
            journal.record(
                "admission.admit" if admitted else "admission.deny",
                doc=doc,
            )
            profiler.record("host_stage", 0.0, 1.0)
        """,
        tmp_path,
    )
    assert findings == []


def test_vocab_drift_flags_undeclared_profiler_lane(tmp_path):
    findings = _run_pass(
        _vocab_pass(),
        """
        from fluidframework_tpu.telemetry import profiler

        def step(t0, t1):
            profiler.record("device_wait", t0, t1)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "undeclared profiler lane 'device_wait'" in findings[0].message


@pytest.mark.parametrize(
    "lane,flagged", [("device_stage", False), ("device_wait", True)]
)
def test_vocab_drift_checks_the_lane_of_a_span(tmp_path, lane, flagged):
    """``profiler.span("<lane>")`` is a producer site like
    ``profiler.record``: its lane must be declared, and counts as used."""
    findings = _run_pass(
        _vocab_pass(),
        f"""
        from fluidframework_tpu.telemetry import profiler

        def sweep(runner):
            with profiler.span("{lane}"):
                return runner.pump()
        """,
        tmp_path,
    )
    assert [f.message for f in findings if "profiler lane" in f.message] == (
        [f"undeclared profiler lane '{lane}' — declare it in "
         "telemetry/profiler.py LANES (unknown names raise at runtime, "
         "but only when the branch runs)"] if flagged else []
    )


def test_vocab_drift_flags_non_literal_kind(tmp_path):
    findings = _run_pass(
        _vocab_pass(),
        """
        from fluidframework_tpu.telemetry import journal

        def submit(kind, doc):
            journal.record(kind, doc=doc)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "string literal" in findings[0].message


def test_vocab_drift_flags_unknown_stage_literal(tmp_path):
    findings = _run_pass(
        _vocab_pass(),
        """
        from fluidframework_tpu.telemetry import tracing

        def handle(traces):
            tracing.stamp(traces, "alfredo", "start")
            tracing.stamp(traces, "alfred", "end")
            tracing.stamp(traces, tracing.STAGE_DELI, "start")
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "'alfredo'" in findings[0].message


def test_vocab_drift_family_checks(tmp_path):
    """Undeclared family, kind mismatch, and non-literal name all fail;
    a declared registration passes."""
    findings = _run_pass(
        _vocab_pass(),
        """
        from fluidframework_tpu.telemetry import metrics

        def register(reg, name):
            ok = reg.counter("retry_attempts_total", "x", ("site",))
            bad_name = reg.counter("my_new_total", "x")
            bad_kind = reg.gauge("retry_attempts_total", "x")
            non_literal = reg.counter(name, "x")
            return ok, bad_name, bad_kind, non_literal
        """,
        tmp_path,
    )
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 3, msgs
    assert any("undeclared Prometheus family 'my_new_total'" in m for m in msgs)
    assert any("one family, one kind" in m for m in msgs)
    assert any("must be a string literal" in m for m in msgs)


def test_vocab_drift_dead_fault_site(tmp_path):
    """The DEAD direction: a site declared in the vocabulary that no
    production boundary decorates fails via finalize()."""
    core = _tools()[0]
    vocab_dir = tmp_path / "fluidframework_tpu" / "testing"
    vocab_dir.mkdir(parents=True)
    (vocab_dir / "faults.py").write_text(
        'SITES = {"store.append": "retry", "store.ghost": "retry"}\n'
        'RECOVERY_KINDS = frozenset({"retry"})\n'
    )
    mod_dir = tmp_path / "fluidframework_tpu" / "service"
    mod_dir.mkdir(parents=True)
    (mod_dir / "m.py").write_text(textwrap.dedent(
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("store.append")
        def append(log, frame):
            log.append(frame)
        """
    ))
    p = _vocab_pass()()
    p.scope(str(tmp_path))
    src = core.ModuleSource.load(
        str(tmp_path), "fluidframework_tpu/service/m.py"
    )
    run_findings = list(p.run(src))
    assert run_findings == []
    dead = [
        f for f in p.finalize()
        if "dead fault site" in f.message
    ]
    assert len(dead) == 1
    assert "'store.ghost'" in dead[0].message


def test_vocab_drift_repo_vocabularies_have_no_dead_entries():
    """The real repo: run the pass over its whole scope; finalize must
    find nothing dead (every site/kind/lane/stage/family has a live
    producer) — the CI invariant behind the empty baseline."""
    core = _tools()[0]
    p = _vocab_pass()()
    findings = []
    for rel in p.scope(REPO):
        src = core.ModuleSource.load(REPO, rel)
        findings.extend(f for f, _n in p.run(src))
    findings.extend(p.finalize())
    assert findings == [], [f.render() for f in findings]


# -- r17 stale pragmas + output formats ----------------------------------------


def test_stale_pragma_reported_and_live_pragma_kept(tmp_path):
    """A reasoned pragma whose finding no longer fires is itself a
    finding; a pragma still suppressing something is not."""
    core = _tools()[0]
    pkg = tmp_path / "fluidframework_tpu" / "parallel"
    pkg.mkdir(parents=True)
    (pkg / "fleet.py").write_text(textwrap.dedent(
        """
        import numpy as np

        def live(pool):
            return np.asarray(pool.state.err)  # graftlint: readback(explicit health pull)

        def stale(rows):
            return np.asarray(rows)  # graftlint: readback(this suppresses nothing)
        """
    ))
    findings, _ = core.run(
        str(tmp_path), passes=["host-sync"], use_baseline=False
    )
    assert [f.rule for f in findings] == ["stale-pragma"], [
        f.render() for f in findings
    ]
    assert findings[0].line == 8


def test_stale_pragma_not_reported_when_pass_not_selected(tmp_path):
    """A pragma is only stale when its OWN pass looked: running just the
    determinism pass must not call host-sync pragmas stale."""
    core = _tools()[0]
    pkg = tmp_path / "fluidframework_tpu" / "tree"
    pkg.mkdir(parents=True)
    (pkg / "m.py").write_text(
        "import numpy as np\n"
        "def f(rows):\n"
        "    return np.asarray(rows)  # graftlint: readback(unrelated)\n"
    )
    findings, _ = core.run(
        str(tmp_path), passes=["determinism"], use_baseline=False
    )
    assert findings == []


def test_repo_has_no_stale_pragmas():
    """The sweep satellite: the merged tree's reasoned-exception set is
    fully live (explicit --stale-pragmas mode exits 0)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--check",
         "--stale-pragmas"],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_json_output_shape():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--check",
         "--format=json", "--timings"],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["tool"] == "graftlint"
    assert doc["findings"] == []
    assert doc["stale_baseline_entries"] == []
    assert set(doc["pass_seconds"]) == {
        "host-sync", "recompile-hazard", "determinism", "fault-site",
        "wire-drift", "loop-blocking", "lock-order", "vocab-drift",
    }


def test_sarif_output_shape(tmp_path):
    """SARIF renders findings with ruleId + physical location (drive it
    through a fixture repo so there IS a finding)."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.graftlint.__main__ import _as_sarif

    core = _tools()[0]
    f = core.Finding(
        rule="loop-blocking", path="fluidframework_tpu/service/x.py",
        line=12, col=3, message="time.sleep blocks the event loop",
    )
    doc = _as_sarif([f])
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    res = run["results"][0]
    assert res["ruleId"] == "loop-blocking"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "fluidframework_tpu/service/x.py"
    assert loc["region"]["startLine"] == 12


def test_all_eight_passes_registered():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.graftlint.passes import ALL_PASSES

    assert [p.id for p in ALL_PASSES] == [
        "host-sync", "recompile-hazard", "determinism", "fault-site",
        "wire-drift", "loop-blocking", "lock-order", "vocab-drift",
    ]


def test_host_sync_flags_profiler_producer_bare_transfer(tmp_path):
    """The profiler's zero-readback contract: producers record HOST
    perf_counter timestamps only — device_step closes on the pump's
    EXISTING one-boxcar-stale scan. A producer that runs its own
    device→host transfer to 'time the device more precisely' is a new
    readback on the serving path; the fixture proves the host-sync pass
    fails it bare (no blessed pragma shape: the fix is to close on the
    existing scan, not to annotate)."""
    _, HostSync, *_ = _tools()
    findings = _run_pass(
        HostSync,
        """
        import numpy as np
        import time

        def profile_device_step(pool, profiler, t0):
            # WRONG: barriers the device just to close a timing lane
            np.asarray(pool.state.count)
            profiler.record("device_step", t0, time.perf_counter())
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "device→host" in findings[0].message


# -- r19 residency fixtures ----------------------------------------------------


def test_fault_site_accepts_residency_sites(tmp_path):
    """The r19 residency commit boundaries — ``doc.hibernate`` (the
    summarize→pointer walk already ran; this evicts the slots) and
    ``doc.wake`` (restore the cold states and unpark pending ops) —
    are documented vocabulary: production boundaries decorated with
    them pass lint."""
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("doc.hibernate")
        def hibernate_commit(backend, doc_id, idxs, states):
            return backend.fleet.evict_docs(idxs, states)

        @inject_fault("doc.wake")
        def wake_commit(backend, doc_id):
            for key, (state, head) in backend.cold_records(doc_id):
                backend.fleet.restore_doc(key, state)
        """,
        tmp_path,
    )
    assert findings == []


def test_fault_site_flags_unregistered_residency_site(tmp_path):
    """The r19 regression shape: a residency boundary added to a
    production module without declaring it in the vocabulary (e.g. a
    ``doc.freeze`` eviction variant) must fail lint — the
    stay-resident/retry contracts only exist if the site is
    documented."""
    findings = _run_pass(
        _fault_site_pass(),
        """
        from fluidframework_tpu.testing.faults import inject_fault

        @inject_fault("doc.freeze")
        def freeze(backend, doc_id):
            return backend.hibernate_doc(doc_id)
        """,
        tmp_path,
    )
    assert len(findings) == 1
    assert "unknown injection site" in findings[0].message
    assert "doc.freeze" in findings[0].message
