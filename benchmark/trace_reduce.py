"""From a profiler trace to numbers: device busy and idle, operation time
by name, and the longest idle gaps with what the host was doing in them.

The reduction works on a plain list of events, ``(plane, line, name,
start_ns, duration_ns)``, so that it can be checked on a small recorded
trace (``tests/``). ``load_xplane`` makes that list from the ``.xplane.pb``
the JAX profiler writes, with nothing but JAX.

Kernels and jitted steps of the program carry no stable names yet
(``jax.named_scope`` is the `tracing` issue's work), so the reduction
matches the names the trace shows now and the harness prints them.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Iterable, List, NamedTuple


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int


# Lines of a device plane that hold one event per operation run there.
OP_LINES = ("XLA Ops",)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
ATTRIBUTED_GAPS = 256  # the longest idle gaps are named one by one
# Host spans that say nothing about what the host was doing.
_DULL_HOST = re.compile(r"^(\$|Thread|process_name|thread_name)")


def short_name(name: str) -> str:
    """A device operation's own name: the profiler gives the whole HLO
    instruction (``%apply_ops_packed.1 = (s32[...]) custom-call(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_xplane(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime,
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                events.append(Event(
                    plane.name, line.name, short_name(ev.name),
                    int(ev.start_ns), int(ev.duration_ns),
                ))
    return events


def load_json(path: str) -> List[Event]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def dump_json(events: Iterable[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def union(intervals: Iterable[tuple]) -> List[tuple]:
    """Merged, sorted [start, end) intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: List[Event]) -> dict:
    """Seconds by operation name on one line, each event's own time: an
    operation that encloses others (a loop, a call) is charged only what
    its children do not cover."""
    total: dict = {}
    stack: list = []  # [end, name, self_ns]
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        end = ev.start_ns + ev.dur_ns
        while stack and stack[-1][0] <= ev.start_ns:
            _, name, own = stack.pop()
            total[name] = total.get(name, 0) + own
        if stack:
            stack[-1][2] -= min(ev.dur_ns, stack[-1][0] - ev.start_ns)
        stack.append([end, ev.name, ev.dur_ns])
    for _, name, own in stack:
        total[name] = total.get(name, 0) + own
    return {k: v / 1e9 for k, v in total.items()}


def reduce(events: List[Event], top: int = 10) -> dict:
    """``busy_s`` (mean over the device planes of the union of their
    operations' intervals), ``window_s`` (first to last event of the
    trace), ``ops`` (seconds by name, own time, summed over devices),
    ``device_ops`` and ``idle_gaps`` (the ``top`` of each)."""
    dev_planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    spans = [e for e in events if e.dur_ns > 0]
    if not spans:
        raise ValueError("the trace holds no event with a duration")
    t_lo = min(e.start_ns for e in spans)
    t_hi = max(e.start_ns + e.dur_ns for e in spans)
    ops: dict = {}
    counts: dict = {}
    busy_ns = 0
    busy_by_plane = {}
    for plane in dev_planes:
        on = [e for e in events
              if e.plane == plane and e.line in OP_LINES and e.dur_ns > 0]
        merged = union((e.start_ns, e.start_ns + e.dur_ns) for e in on)
        busy_by_plane[plane] = merged
        busy_ns += sum(e - s for s, e in merged)
        for name, s in self_times(on).items():
            ops[name] = ops.get(name, 0.0) + s
        for e in on:
            counts[e.name] = counts.get(e.name, 0) + 1
    n = max(1, len(dev_planes))
    host = [
        e for e in spans
        if not DEVICE_PLANE.match(e.plane) and not _DULL_HOST.match(e.name)
    ]
    gaps: dict = {}
    if dev_planes:
        merged = busy_by_plane[dev_planes[0]]
        edges = [t_lo] + [t for s, e in merged for t in (s, e)] + [t_hi]
        idle = sorted(
            ((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a),
            reverse=True,
        )
        # Name the longest gaps one by one; the many short ones together.
        for length, a, b in idle[:ATTRIBUTED_GAPS]:
            what = _host_activity(host, a, b)
            gaps[what] = gaps.get(what, 0) + length
        rest = sum(length for length, _, _ in idle[ATTRIBUTED_GAPS:])
        if rest:
            gaps["shorter-gaps-not-attributed"] = rest
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (t_hi - t_lo) / 1e9,
        "devices": len(dev_planes),
        "ops": ops,
        "op_counts": counts,
        "device_ops": [
            [k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [k, v / 1e9]
            for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        ],
    }


def _host_activity(host: List[Event], a: int, b: int) -> str:
    """The host span that covers most of the gap [a, b); the benchmark's
    own annotations (``bench.*``) yield to anything more specific inside
    them."""
    best, best_cover = "host:nothing-traced", 0
    for e in host:
        cover = min(b, e.start_ns + e.dur_ns) - max(a, e.start_ns)
        if cover <= 0:
            continue
        # Prefer the tightest span that still covers: weigh cover by how
        # little of the span lies outside the gap.
        score = cover * cover / max(e.dur_ns, 1)
        if score > best_cover:
            best, best_cover = e.name, score
    return best
