"""Per-pass scope and heuristics configuration.

graftlint is deliberately repo-native: the scopes below name THIS
codebase's device paths, merge paths, and codec modules, and the taint
heuristics name its device-state attribute idioms. Generic linters stop
where type information ends; a repo-native one gets to encode what the
repo already promises in its docstrings (``pool.state`` lives on device,
``fluidframework_tpu.ops`` functions return device values, ...).

All paths are repo-root-relative POSIX globs.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# Device-path modules (the host-sync + recompile-hazard scope): code that
# sits between the service front door and the kernels, where an
# unannotated device→host transfer is a serving-latency bug.
DEVICE_PATH_SCOPE = (
    "fluidframework_tpu/ops/*.py",
    "fluidframework_tpu/tree/device_*.py",
    "fluidframework_tpu/parallel/*.py",
    "fluidframework_tpu/service/device_backend.py",
)

# Merge/sequencing modules (the determinism scope): code every replica
# runs over the sequenced stream — any iteration-order dependence here is
# the bug class that breaks the identical-replica guarantee.
MERGE_PATH_SCOPE = (
    "fluidframework_tpu/tree/*.py",
    "fluidframework_tpu/ops/*.py",
    "fluidframework_tpu/service/sequencer.py",
    "fluidframework_tpu/service/pipeline.py",
    "fluidframework_tpu/runtime/*.py",
    "fluidframework_tpu/models/*.py",
)

# Codec modules (the wire-drift scope): every accreting format ROADMAP
# names — kernel-row field layout, op frames, log values, binary
# snapshots, the tree move wire, and the scribe lane layout.
CODEC_MODULES = (
    "fluidframework_tpu/protocol/constants.py",
    "fluidframework_tpu/protocol/opframe.py",
    "fluidframework_tpu/service/codec.py",
    "fluidframework_tpu/drivers/binary_snapshot.py",
    "fluidframework_tpu/tree/marks.py",
    "fluidframework_tpu/ops/segment_state.py",
)

# Attribute names that denote device-resident state in this codebase
# (``pool.state``, ``self.tables``, ``svc._scalars``, ...). An attribute
# access whose terminal name is in this set taints the expression as a
# device value for the host-sync pass.
DEVICE_ATTRS = frozenset(
    {"state", "tables", "scalars", "_tables", "_scalars", "_scan"}
)

# Imports from these module prefixes are assumed to RETURN device values
# (the kernel entry points: apply_ops_packed, unpack_state, ...).
KERNEL_MODULE_PREFIXES = ("fluidframework_tpu.ops",)

# Functions whose PARAMETERS carry device values by contract: the
# off-loop transfer halves (scan_transfer/read_transfer/
# doc_states_transfer/_telemetry_readback) receive immutable concrete
# device arrays precisely so an async server can run the blocking
# np.asarray off the serving thread. Local taint cannot see through a
# parameter, so the contract is declared here — their readbacks are
# flagged (and pragma-audited) instead of silently under-flagged.
DEVICE_PARAM_FNS = frozenset(
    {
        "scan_transfer",
        "read_transfer",
        "doc_states_transfer",
        "_telemetry_readback",
    }
)

# Fault-injection scope (the fault-site pass): every package module may
# carry ``@inject_fault`` boundaries; the testing/ package (which DEFINES
# the vocabulary) is excluded by the pass itself. Note fnmatch's ``*``
# crosses ``/``, so one glob covers the whole package.
FAULT_SITE_SCOPE = ("fluidframework_tpu/*.py",)
FAULT_VOCAB_MODULE = "fluidframework_tpu/testing/faults.py"

# -- loop-blocking (r17) -------------------------------------------------------

# The asyncio serving tier: modules whose code runs ON the socket event
# loop. network_server owns the loop; the pipeline pump sweep, the
# device backend's feed/flush surface, and the lambda handlers all
# execute inside it (the per-partition single-sequencer discipline the
# reference enforces by convention in its deli/alfred lambdas).
# store_server is thread-per-connection today but is scoped so any
# future async surface is covered from its first commit.
LOOP_SCOPE = (
    "fluidframework_tpu/service/network_server.py",
    "fluidframework_tpu/service/pipeline.py",
    "fluidframework_tpu/service/device_backend.py",
    "fluidframework_tpu/service/store_server.py",
    "fluidframework_tpu/service/lambdas.py",
)

# Cross-module on-loop entry points: functions the event loop calls
# into from ANOTHER module (so the per-module call graph cannot see the
# async caller). network_server's loop invokes the pipeline service
# surface and the device backend's pump/feed/read surface directly; the
# lambda handlers run inside the pipeline pump sweep. Keyed by
# repo-relative path, values are function/method names treated as
# on-loop roots for that module.
LOOP_ENTRY = {
    "fluidframework_tpu/service/pipeline.py": (
        "pump", "connect", "disconnect", "submit", "submit_frame",
        "submit_frames_bulk", "submit_signal", "doc_head", "ops_range",
        "log_entries", "get_deltas", "latest_summary_pointer",
        "flush_device", "_nack_device_errors", "device_text",
        "device_summary", "take_inbox", "take_inbox_raw",
    ),
    "fluidframework_tpu/service/device_backend.py": (
        "enqueue", "enqueue_frame", "enqueue_matrix", "flush", "needs_flush",
        "needs_scan_drain", "prefetch_scan", "scan_prefetched",
        "collect_now", "pump_feed", "pump_feed_counted",
        "pump_feed_absorbed", "pump_stage", "pump_dispatch", "pressure",
        "read_start", "read_finish", "publish_metrics", "has_channel",
        "take_errors", "text_from_state", "summary_from_state",
        "grid_from_state", "tables_due", "sweep_tables", "dirty_channels",
    ),
    "fluidframework_tpu/service/lambdas.py": (
        "handler", "handler_batch", "_handle", "_handle_frame", "_emit",
        "pump",
    ),
}

# Sanctioned off-loop halves: blocking by DESIGN, invoked only via
# run_in_executor (the scan_transfer/read_transfer splits and the
# /metrics telemetry readback). They are never treated as on-loop
# reachable — but a DIRECT call to one from an on-loop function is
# itself a finding (the split exists precisely so the blocking half
# never runs inline).
OFF_LOOP_HELPERS = frozenset(
    {"scan_transfer", "read_transfer", "_telemetry_readback"}
)

# -- lock-order (r17) ----------------------------------------------------------

# Lock-discipline scope: every module holding a lock another thread can
# contend on — the telemetry rings/registries (scraped from request
# threads) and the service tier (store node request threads, the
# drainer, admission from ticker + submit paths).
LOCK_SCOPE = (
    "fluidframework_tpu/telemetry/*.py",
    "fluidframework_tpu/service/*.py",
)

# Attribute/name suffixes recognized as locks in ``with`` statements and
# ``.acquire()`` calls.
LOCK_NAMES = ("lock", "_lock")

# Render paths: snapshot/exposition functions served to scrape threads.
# Contract (the r16 hardening pattern): snapshot under ONE lock, render
# outside it — acquiring a second lock while holding one in a render
# path is the nested-hold shape that deadlocked /metrics in r16.
RENDER_PATHS = {
    "fluidframework_tpu/telemetry/metrics.py": (
        "render", "snapshot", "samples", "stage_span_summary",
    ),
    "fluidframework_tpu/telemetry/journal.py": ("render", "snapshot"),
    "fluidframework_tpu/telemetry/profiler.py": (
        "render", "chrome_trace", "summarize", "snapshot",
    ),
}

# Calls that acquire a known lock in ANOTHER module (the per-module
# graph cannot see through them): metric observations take the
# per-metric lock, registry registration takes the registry lock, and
# the journal/profiler record paths take their ring locks. Used both
# for cross-module lock-order edges and for the gc-callback /
# signal-handler lock-free contract.
KNOWN_LOCK_CALLS = {
    # method name -> lock id it acquires
    "inc": "telemetry/metrics._Metric._lock",
    "observe": "telemetry/metrics._Metric._lock",
    "counter": "telemetry/metrics.MetricsRegistry._lock",
    "gauge": "telemetry/metrics.MetricsRegistry._lock",
    "histogram": "telemetry/metrics.MetricsRegistry._lock",
}
# record() receivers -> ring lock (journal.record / JOURNAL.record /
# profiler.record / PROFILER.record).
RECORD_LOCKS = {
    "journal": "telemetry/journal.Journal._lock",
    "JOURNAL": "telemetry/journal.Journal._lock",
    "profiler": "telemetry/profiler.Profiler._lock",
    "PROFILER": "telemetry/profiler.Profiler._lock",
}

# -- vocab-drift (r17) ---------------------------------------------------------

# Observability-vocabulary scope: every package module (including
# testing/ — faults.py legitimately journals ``fault.injected``). The
# declared vocabularies live in the modules below; a string used as a
# site/kind/lane/stage/family in scope must appear in its vocabulary,
# and every vocabulary entry must be used (dead entries fail lint).
VOCAB_SCOPE = ("fluidframework_tpu/*.py",)
JOURNAL_VOCAB_MODULE = "fluidframework_tpu/telemetry/journal.py"
PROFILER_VOCAB_MODULE = "fluidframework_tpu/telemetry/profiler.py"
TRACING_VOCAB_MODULE = "fluidframework_tpu/telemetry/tracing.py"
METRICS_VOCAB_MODULE = "fluidframework_tpu/telemetry/metrics.py"

# Vocabulary entries that are DERIVED (synthesized by read surfaces,
# never recorded by a producer) — exempt from the dead-entry check.
DERIVED_LANES = frozenset({"loop_other"})

# Committed artifacts.
WIRE_LOCK_FILE = "api-report/wire_fingerprints.json"
BASELINE_FILE = "tools/graftlint/baseline.json"
