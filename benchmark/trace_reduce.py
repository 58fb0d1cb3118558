"""From a profiler trace to numbers: device busy and idle, operation time
by name, and the longest idle gaps split among the host spans that cover
them.

The reduction works on a plain list of events, ``(plane, line, name,
start_ns, duration_ns, thread)``, so that it can be checked on a small
recorded trace (``tests/``). ``load_xplane`` makes that list from the
``.xplane.pb`` the JAX profiler writes, with nothing but JAX.

``thread`` tells the lines of one plane apart: the profiler names every
Python thread ``python3``, so the server's loop, the benchmark's main
thread and the executor threads share a line name. It is the line's index
within its plane (``ProfileData`` gives a line no id), and None in a
recording made before it was kept: lines of one name are then one thread.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Iterable, List, NamedTuple, Optional


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int
    thread: Optional[int] = None


# Lines of a device plane that hold one event per operation run there.
OP_LINES = ("XLA Ops",)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
ATTRIBUTED_GAPS = 256  # the longest idle gaps are split among host spans
NOTHING = "host:nothing-traced"  # an idle instant no host span covers
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
        for index, line in enumerate(plane.lines):
            for ev in line.events:
                events.append(Event(
                    plane.name, line.name, short_name(ev.name),
                    int(ev.start_ns), int(ev.duration_ns), index,
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


def loop_thread(host: Iterable[Event]):
    """``(plane, line, thread)`` of the host thread that carries the most
    of the program's ``fluid.*`` spans, which is the server's loop; None
    where the trace holds no span of the program."""
    seen: dict = {}
    for e in host:
        if e.name.startswith("fluid."):
            key = (e.plane, e.line, e.thread)
            seen[key] = seen.get(key, 0) + 1
    return max(seen, key=seen.get) if seen else None


def innermost(spans: Iterable[Event]) -> List[tuple]:
    """Sorted, disjoint ``(start, end, name)``: at each instant the span
    that started last among those that cover it. On one thread, where
    spans nest, that is the innermost one, so an enclosing span (a
    ``bench.*`` annotation, a stage around a device feed) is left only
    what nothing inside it covers."""
    out: List[tuple] = []
    stack: list = []  # (end, name) of the spans begun by ``t``, the last on top
    t = 0
    starts = sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns))
    for e in starts + [None]:  # None: run the open spans out
        upto = e.start_ns if e else max((end for end, _ in stack), default=t)
        while stack and t < upto:
            end, name = stack[-1]
            if end <= upto:
                stack.pop()
            if min(end, upto) > t:
                out.append((t, min(end, upto), name))
                t = min(end, upto)
        t = upto
        if e:
            stack.append((e.start_ns + e.dur_ns, e.name))
    return out


def charge(gaps: List[tuple], segments: List[tuple], out: dict) -> List[tuple]:
    """Adds to ``out``, by segment name, the nanoseconds of ``gaps`` that
    the segments cover, and returns the parts of the gaps that none
    covers. Both lists sorted and disjoint."""
    rest, k = [], 0
    for a, b in gaps:
        while k < len(segments) and segments[k][1] <= a:
            k += 1
        t, j = a, k
        while j < len(segments) and segments[j][0] < b:
            lo, hi, name = segments[j]
            lo, hi = max(lo, a), min(hi, b)
            if lo > t:
                rest.append((t, lo))
            out[name] = out.get(name, 0) + hi - lo
            t, j = hi, j + 1
        if t < b:
            rest.append((t, b))
    return rest


def split_gaps(host: List[Event], gaps: Iterable[tuple]) -> dict:
    """Nanoseconds of the idle ``gaps`` by what the host was doing: each
    instant goes to the innermost span of the server's loop thread that
    covers it; where that thread is in no span, to the span of any other
    host thread that started last; where no thread is in a span, to
    ``host:nothing-traced``. So a span of another thread that merely
    overlaps a gap (an executor's wait for a transfer, the feeder's wait
    for the front door) takes only what the loop leaves."""
    where = loop_thread(host)
    loop = [e for e in host if (e.plane, e.line, e.thread) == where]
    others = [e for e in host if (e.plane, e.line, e.thread) != where]
    out: dict = {}
    rest = charge(sorted(gaps), innermost(loop), out)
    rest = charge(rest, innermost(others), out)
    nothing = sum(b - a for a, b in rest)
    if nothing:
        out[NOTHING] = nothing
    return out


def reduce(events: List[Event], top: int = 10) -> dict:
    """``busy_s`` (mean over the device planes of the union of their
    operations' intervals), ``window_s`` (first to last event of the
    trace), ``ops`` (seconds by name, own time, summed over devices),
    ``idle_by_span`` (seconds of the first device's longest idle gaps by
    host span, ``split_gaps``; the shorter gaps together under one name),
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
        # Split the longest gaps; the many short ones go together.
        gaps = split_gaps(host, [(a, b) for _, a, b in idle[:ATTRIBUTED_GAPS]])
        rest = sum(length for length, _, _ in idle[ATTRIBUTED_GAPS:])
        if rest:
            gaps["shorter-gaps-not-attributed"] = rest
    by_span = sorted(gaps.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (t_hi - t_lo) / 1e9,
        "devices": len(dev_planes),
        "ops": ops,
        "op_counts": counts,
        "device_ops": [
            [k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_by_span": [[k, v / 1e9] for k, v in by_span],
        "idle_gaps": [[k, v / 1e9] for k, v in by_span[:top]],
    }
