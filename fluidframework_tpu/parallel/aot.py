"""AOT donated-entry cache: compile once per static shape bucket.

An ``.lower().compile()`` entry with ``donate_argnums`` skips tracing,
the jit cache lookup, AND the defensive copy on every hot call. Every hot
device entry on the serving path — the pump's busy-set step and compact
(``parallel/fleet.py``), the mesh ``shard_map`` step
(``parallel/mesh.py``) — is lowered and compiled ONCE per static shape
bucket and then served from a dict probe, so steady-state serving pays
zero per-flush tracing or cache-miss cost.

Keys are explicit shape-bucket tuples (callers already pow2-bucket their
batch dims, so the entry set stays logarithmic in fleet size); values are
jax ``Compiled`` executables. ``stats()`` exposes build/call counters so
tests can pin the steady-state contract: after warmup, flushes NEVER
build (``builds`` stays flat while ``calls`` grows) — one entry per shape
bucket, never one per flush.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Tuple

from fluidframework_tpu.telemetry import profiler

_ENTRIES: Dict[Tuple, Any] = {}
_LOCK = threading.Lock()
_BUILDS = 0
_BUILD_S = 0.0
_CALLS = 0


def call(key: Tuple, build: Callable[[], Any], *args, **static_kwargs):
    """Dispatch ``args`` through the AOT executable cached under ``key``.

    On a miss, ``build()`` returns the jitted callable (callers keep
    those in module-level/lru_cache builders — the repo's recompile
    rule), which is lowered against the concrete ``args`` (plus any
    static keyword args) and compiled once; the compiled entry is then
    invoked with the dynamic ``args`` only. Donation declared on the
    jitted callable carries through to the executable, so the hot call
    updates buffers in place with no defensive copy.
    """
    global _BUILDS, _BUILD_S, _CALLS
    exe = _ENTRIES.get(key)
    if exe is None:
        with _LOCK:
            exe = _ENTRIES.get(key)
            if exe is None:
                with profiler.span("aot_build") as built:
                    # graftlint: recompile(built ONCE per shape-bucket key — the dict probe above IS the cache; a steady-state flush never reaches this branch, and the entry-count/build counters are test-pinned)
                    exe = _ENTRIES[key] = (
                        build().lower(*args, **static_kwargs).compile()
                    )
                _BUILDS += 1
                _BUILD_S += built.t1 - built.t0
    _CALLS += 1
    return exe(*args)


def stats() -> Dict[str, float]:
    """Monotone counters: ``entries`` (live cache size), ``builds``
    (executables compiled — one per shape bucket ever seen), ``build_s``
    (seconds those lowerings and compiles took, the ``aot_build`` lane's
    own floats), ``calls`` (dispatches served). The zero-per-flush-
    tracing contract is ``builds`` flat while ``calls`` grows."""
    return {
        "entries": len(_ENTRIES), "builds": _BUILDS, "build_s": _BUILD_S,
        "calls": _CALLS,
    }


def clear() -> None:
    """Drop every entry (test isolation; production never calls this —
    entries are valid for the life of the process)."""
    global _BUILDS, _BUILD_S, _CALLS
    with _LOCK:
        _ENTRIES.clear()
        _BUILDS = 0
        _BUILD_S = 0.0
        _CALLS = 0
