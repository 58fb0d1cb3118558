"""The collector's policy while a server serves (``service/gc_policy.py``):
installed by ``FluidNetworkServer.start()``, undone by ``stop()``, nesting
across servers; cycles are still reclaimed; a run of full boxcars sees no
generation-2 pass and few young ones; what was frozen alive is still freed
by reference count. Counts only: nothing here is a time.
"""

import asyncio
import gc
import time
import weakref

import pytest

from fluidframework_tpu.protocol.opframe import OpFrame
from fluidframework_tpu.service import gc_policy, residency
from fluidframework_tpu.service.network_server import FluidNetworkServer
from fluidframework_tpu.service.pipeline import PipelineFluidService

MINT = 1 << 14  # shared_string._MINT_STRIDE (content-id scoping)
FRAMES, OPS = 128, 4  # one default 512-row boxcar


@pytest.fixture(autouse=True)
def collector_as_python_starts_it():
    """Each case starts from CPython's own policy, whatever servers
    earlier tests of this process left running, and puts back what it
    found."""
    found = (gc_policy._servers, gc_policy._found, gc.get_threshold())
    gc_policy._servers, gc_policy._found = 0, None
    gc.unfreeze()
    gc.set_threshold(700, 10, 10)
    yield
    gc.unfreeze()
    gc_policy._servers, gc_policy._found = found[:2]
    gc.set_threshold(*found[2])


def on_loop(srv, fn):
    async def run():
        return fn()

    return asyncio.run_coroutine_threadsafe(run(), srv._loop)


def wait_for(cond, seconds: float = 5.0) -> bool:
    # Polls in 10 ms steps and returns at once when the condition holds
    # (a few ticks); the deadline is for a loop thread starved by xdist.
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def a_pass_froze() -> bool:
    # A full collection alone leaves a few hundred objects of the
    # interpreter's own in the permanent generation; a pass, the test's.
    return gc.get_freeze_count() > gc_policy.IDLE_QUANTUM


def churn() -> list:
    """Enough live containers for a pass at ANY tick: the idle test reads
    the process's lane totals, and another test's server may be crossing
    seams in this process."""
    return [[] for _ in range(gc_policy.BUSY_QUANTUM + 1000)]


class Cell:
    """Something that can sit in a reference cycle and be watched."""


def dead_cycle() -> weakref.ref:
    a, b = Cell(), Cell()
    a.other, b.other = b, a
    return weakref.ref(a)


def test_a_tick_passes_at_its_quantum_and_idle_ticks_sooner_and_in_full():
    passes = []

    def probe(phase, info):
        if phase == "stop":
            passes.append(info["generation"])

    gc.collect()
    gc_policy.acquire()
    gc.callbacks.append(probe)
    try:
        hold = [[] for _ in range(gc_policy.IDLE_QUANTUM + 1000)]
        gc_policy.tick(idle=False)
        assert passes == [] and not a_pass_froze()
        gc_policy.tick(idle=True)
        assert passes == [2] and a_pass_froze()
        gone = dead_cycle()
        hold += churn()
        gc_policy.tick(idle=False)
        assert passes == [2, 1] and gone() is None
        gc_policy.tick(idle=True)  # nothing allocated since: no pass
        assert passes == [2, 1]
    finally:
        gc.callbacks.remove(probe)
        gc_policy.release()
    assert gc.get_freeze_count() == 0 and gc.get_threshold() == (700, 10, 10)
    del hold


def test_start_installs_the_policy_and_stop_undoes_it():
    srv = FluidNetworkServer()
    srv.start()
    try:
        assert gc.get_threshold() == gc_policy.THRESHOLDS
        assert gc.isenabled()
    finally:
        srv.stop()
    assert gc.get_threshold() == (700, 10, 10)
    assert gc.get_freeze_count() == 0
    assert gc_policy._servers == 0


def test_two_servers_nest_and_the_last_restores():
    gc.set_threshold(800, 11, 12)  # what the embedder had set
    first, second = FluidNetworkServer(), FluidNetworkServer()
    first.start()
    try:
        second.start()
        try:
            hold = churn()
            assert wait_for(a_pass_froze), "no pass"
        finally:
            second.stop()
        assert gc.get_threshold() == gc_policy.THRESHOLDS
        assert a_pass_froze(), "the other server still serves"
    finally:
        first.stop()
    assert gc.get_threshold() == (800, 11, 12)
    assert gc.get_freeze_count() == 0
    del hold


def test_a_cycle_made_after_the_freeze_is_reclaimed_at_idle_ticks():
    srv = FluidNetworkServer()
    srv.start()
    try:
        hold = churn()
        assert wait_for(a_pass_froze), "no pass"
        ticks = srv.lag_ticks
        gone = dead_cycle()
        assert gone() is not None, "a cycle does not die by count"
        hold += churn()
        assert wait_for(lambda: gone() is None), "the cycle was never swept"
        assert srv.lag_ticks - ticks <= 40
    finally:
        srv.stop()
    del hold


def boxcar(conns, turn: int) -> list:
    items = []
    for conn in conns:
        c0 = 1 + turn * OPS
        frame = OpFrame.build(
            "s", ["ins"] * OPS, [0] * OPS,
            [conn.conn_no * MINT + c0 + j for j in range(OPS)], ["x"] * OPS,
            csn0=c0, ref=conn.join_seq + turn * OPS,
        )
        items.append((conn.doc_id, conn.client_id, frame))
    return items


def test_full_boxcars_see_no_old_pass_and_few_young_ones():
    n_boxcars = 8
    svc = PipelineFluidService(n_partitions=2)
    srv = FluidNetworkServer(service=svc)
    srv.start()
    passes = {0: 0, 1: 0, 2: 0}

    def probe(phase, info):
        if phase == "stop":
            passes[info["generation"]] += 1

    try:
        conns = on_loop(
            srv, lambda: [svc.connect(f"d{i}") for i in range(FRAMES)]
        ).result(120)
        # The step program of this boxcar's shape is built by the first.
        on_loop(srv, lambda: svc.submit_frames_bulk(boxcar(conns, 0))).result(300)
        batches = [boxcar(conns, turn) for turn in range(1, n_boxcars + 1)]
        applied = svc.device.ops_applied
        gc.callbacks.append(probe)
        try:
            # All queued at once: the loop never runs out of frames.
            futures = [
                on_loop(srv, lambda b=b: svc.submit_frames_bulk(b))
                for b in batches
            ]
            for fut in futures:
                fut.result(300)
        finally:
            gc.callbacks.remove(probe)
        on_loop(srv, svc.flush_device).result(60)
        assert svc.device.ops_applied - applied == n_boxcars * FRAMES * OPS
        assert svc.device.stats()["docs_with_errors"] == 0
    finally:
        srv.stop()
    assert passes[2] == 0, passes
    assert passes[0] + passes[1] <= n_boxcars // 4, passes


def test_a_document_hibernated_after_the_freeze_frees_what_it_held():
    svc = PipelineFluidService(n_partitions=2)
    srv = FluidNetworkServer(service=svc)
    srv.start()
    try:
        def join_and_write():
            conn = svc.connect("sleepy")
            conn.submit_frame(OpFrame.build(
                "s", ["ins"], [0], [conn.conn_no * MINT + 1], ["z"],
                csn0=1, ref=conn.join_seq,
            ))
            svc.flush_device()
            return conn

        conn = on_loop(srv, join_and_write).result(300)
        entry = svc._deli_doc("sleepy").sequencer.clients[conn.client_id]
        held = weakref.ref(conn), weakref.ref(entry)
        del entry
        hold = churn()
        assert wait_for(a_pass_froze), "no pass"

        def leave_and_hibernate():
            conn.disconnect()
            svc.pump()
            for _ in range(12):
                if "sleepy" in svc.hibernate_sweep():
                    return True
            return False

        assert on_loop(srv, leave_and_hibernate).result(60)
        assert svc.device.residency.state("sleepy") == residency.COLD
        # Frozen alive, freed by count: no collection is asked for.
        del conn
        assert held[0]() is None and held[1]() is None
    finally:
        srv.stop()
    del hold
