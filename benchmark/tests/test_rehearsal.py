"""CPU rehearsal of the harness: both traffic kinds end to end on tiny
workload files that ``BENCHMARK.json`` does not list (they are found by
name, which is the point), the shape of the result line, and the
comparison shown to fail: a served text altered, the answer broken where
the program produces it, the REST read entry replying stale or cut texts
inside the window, and the controls (one sequenced op withheld, a reply
one acknowledged op stale).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Nothing here is a time: every line says it ran on the CPU.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
KINDS = ["rehearsal-ingest", "rehearsal-ws"]

DRIVER = """
import sys
sys.path.insert(0, {root!r})
{patch}
from benchmark import run
sys.exit(run.main({argv!r}))
"""

# One document's text altered between the program's answer and the
# comparison, where each kind takes that answer.
ALTER = """
from benchmark import harness
_orig, _seen = harness.{entry}, []
def _altered(srv, doc):
    text = _orig(srv, doc)
    return text + '!' if not _seen and not _seen.append(doc) else text
harness.{entry} = _altered
"""

BREAK_ANSWER = """
from fluidframework_tpu.service.device_backend import DeviceFleetBackend
_orig = DeviceFleetBackend.text_from_state
DeviceFleetBackend.text_from_state = lambda self, key, state: _orig(self, key, state)[1:]
"""

# The REST read entry alone, underneath the timed reads: the device and the
# in-process read stay sound. ``cut`` drops a reply's last character;
# ``cached`` replies what it replied first (no flush, no gather again),
# which is right for a document nobody edits and stale behind live writes.
BREAK_REST = """
import json
from fluidframework_tpu.service.network_server import FluidNetworkServer
_orig, _first = FluidNetworkServer._channel_read, {{}}
async def _broken(self, doc_id, channel_id, view):
    if {cached} and doc_id in _first:
        return _first[doc_id]
    status, payload = await _orig(self, doc_id, channel_id, view)
    if not {cached}:
        reply = json.loads(payload)
        reply["text"] = reply["text"][:-1]
        payload = json.dumps(reply).encode()
    return _first.setdefault(doc_id, (status, payload))
FluidNetworkServer._channel_read = _broken
"""


# The overload envelope sheds every ninth REST read with a 503 and a
# Retry-After, as it does for an instant after the server's loop stalled.
SHED_READS = """
from fluidframework_tpu.service.admission import OverloadController
_calls = []
def _shed(self):
    _calls.append(1)
    return len(_calls) % 9 == 0
OverloadController.shed_reads = _shed
"""


# From the window on, the front door denies every fourth op frame with a
# throttle nack, as it does when the admission budgets tighten behind a
# stall: through the window, at its end and while the writers drain.
THROTTLE_WRITES = """
from benchmark import harness
from fluidframework_tpu.service import admission
_on, _calls = [], []
_say, _decide = harness.Out.say, admission.AdmissionController.decide
def _say_and_mark(self, event, **kv):
    if event == "warm":
        _on.append(1)
    return _say(self, event, **kv)
def _denying(self, tenant, doc_id, n_ops=1, tier=admission.Tier.NORMAL):
    _calls.append(1)
    if _on and len(_calls) % 4 == 0:
        return admission.AdmissionDecision(False, 300.0, "tenant_budget")
    return _decide(self, tenant, doc_id, n_ops, tier)
harness.Out.say = _say_and_mark
admission.AdmissionController.decide = _denying
"""


def _argv(workload, seed, trace=0, *extra):
    return ["--workload", workload, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace), *extra]


def _run(code_or_argv, timeout=900):
    if isinstance(code_or_argv, list):
        cmd = [sys.executable, "benchmark/run.py", *code_or_argv]
    else:
        cmd = [sys.executable, "-c", code_or_argv]
    proc = subprocess.run(
        cmd, cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=timeout
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


@pytest.mark.parametrize("workload", KINDS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(workload, trace):
    proc, lines = _run(_argv(workload, 2147483777 + trace, trace))
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["attempted"] > 0, "\n".join(
        ln[:400] for ln in lines if '"compared"' in ln or "mismatch" in ln
    )
    assert last["device"]["platform"] == "cpu"  # and says so
    cell = json.load(open(os.path.join(ROOT, "benchmark/workloads", workload + ".json")))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = {
        group: {m["name"] for m in bench[group]
                if cell["as_cell"] in m.get("workloads", [cell["as_cell"]])}
        for group in ("end_to_end", "per_layer")
    }
    if trace:
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert last["metrics"] and set(last["metrics"]) <= names["per_layer"]
        assert "breakdown" in last
    else:
        assert set(last["metrics"]) == names["end_to_end"]
    events = {}
    for ln in lines[:-1]:  # every earlier line names where it ran
        rec = json.loads(ln)
        assert rec["platform"] == "cpu" and "device_kind" in rec and "device_count" in rec
        events[rec.get("event")] = rec
    if workload == "rehearsal-ws":
        # One boxcar of every shape the writers can fill goes through
        # before they start, so the window builds no step program.
        warm = events["warm_boxcars"]
        assert warm["shapes"] == [[1, 2], [2, 2], [4, 2], [2, 9]]
        assert warm["dispatches"] == 4 and warm["aot_builds"] >= 4
        assert events["window"]["aot_keys_built_in_window"] == []


def _compared(lines):
    return {
        rec["what"]: rec for rec in map(json.loads, lines[:-1])
        if rec.get("event") == "compared"
    }


@pytest.mark.parametrize(
    "workload,entry",
    [("rehearsal-ingest", "served_text"), ("rehearsal-ws", "rest_text")],
)
def test_altered_served_text_is_not_correct(workload, entry):
    code = DRIVER.format(
        root=ROOT, patch=ALTER.format(entry=entry), argv=_argv(workload, 11)
    )
    proc, lines = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(lines[-1])["correct"] is False
    assert _compared(lines)["served_text_differs_from_replay"]["value"] == 1


def test_answer_broken_where_it_is_produced_is_not_correct():
    code = DRIVER.format(
        root=ROOT, patch=BREAK_ANSWER, argv=_argv("rehearsal-ingest", 12)
    )
    proc, lines = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(lines[-1])["correct"] is False


@pytest.mark.parametrize("cached", [False, True])
def test_rest_replies_broken_inside_the_window_are_not_correct(cached):
    """What the timed reads were replied is what is compared: a reply cut
    short, or one served from a cache behind live writes, is caught among
    the window's own reads."""
    code = DRIVER.format(
        root=ROOT, patch=BREAK_REST.format(cached=cached),
        argv=_argv("rehearsal-ws", 13 + cached),
    )
    proc, lines = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(lines[-1])["correct"] is False
    compared = _compared(lines)
    assert compared["read_replies_of_written_documents"]["value"] > 0
    assert compared["read_replies_differ_from_replay"]["value"] > 0


def test_shed_reads_are_offered_again_and_do_not_fail():
    """A 503 with Retry-After is back-pressure, not a failure: the reader
    comes back after it, the read counts once, its latency runs from the
    due time, and its reply is compared like any other."""
    code = DRIVER.format(
        root=ROOT, patch=SHED_READS, argv=_argv("rehearsal-ws", 4000000007)
    )
    proc, lines = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    window = [json.loads(ln) for ln in lines if '"event": "window"' in ln][0]
    assert window["reads_reoffered_after_503"] > 0
    assert window["read_samples"] == last["attempted"] - 2 * window["frames"]


@pytest.mark.parametrize("seed", [4000000009, 77])
def test_throttled_frames_are_offered_again_and_do_not_fail(seed):
    """A throttle nack is back-pressure too: the client regenerates the
    frames in flight, whenever the nack is met (at a turn, past the
    window's end, or while the writers drain), and they count as
    acknowledged when its pending queue has drained."""
    code = DRIVER.format(
        root=ROOT, patch=THROTTLE_WRITES, argv=_argv("rehearsal-ws", seed)
    )
    proc, lines = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    window = [json.loads(ln) for ln in lines if '"event": "window"' in ln][0]
    assert window["frames_regenerated_after_nack"] > 0
    assert window["ack_samples"] == window["frames"]


@pytest.mark.parametrize("seed", [21, 2147483701, 3000000019])
@pytest.mark.parametrize("workload", KINDS)
def test_control_is_told_apart(workload, seed):
    proc, lines = _run(_argv(workload, seed, 0, "--control", "1"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    control = [json.loads(ln) for ln in lines if '"control"' in ln]
    # All but one at most: the last op of a document with several writers
    # can be a remove of what a concurrent remove already took.
    assert control and control[0]["told_apart"] >= control[0]["documents"] - 1
    for c in control[1:]:  # the ws kind's second control, over the reads
        assert c["told_apart"] >= c["needed"]


def test_listed_workload_refuses_the_cpu():
    listed = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]
    proc, lines = _run(_argv(listed[0]["name"], 1))
    assert proc.returncode != 0
    assert not any('"correct"' in ln for ln in lines)
