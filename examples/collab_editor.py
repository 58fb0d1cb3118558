"""Example: a collaborative text editor session over real sockets.

The reference's canonical demo shape (examples/): N editors share a
SharedString + a SharedMap of cursors; edits merge through the ordering
service; everyone converges. Run:

    python examples/collab_editor.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from fluidframework_tpu.drivers.network_driver import NetworkFluidService
from fluidframework_tpu.models.shared_map import SharedMap
from fluidframework_tpu.models.shared_string import SharedString
from fluidframework_tpu.runtime.container import ContainerRuntime
from fluidframework_tpu.service.network_server import FluidNetworkServer


def drain(runtimes, timeout=60.0):
    """Flush, then poll to quiescence with a deadline. Socket delivery is
    asynchronous: require half a second of continuous silence before
    declaring settled — a short quiet streak misfires on loaded machines
    while a message is still in flight."""
    import time

    for rt in runtimes:
        rt.flush()
    deadline = time.monotonic() + timeout
    quiet = 0
    while quiet < 25 and time.monotonic() < deadline:
        if any(rt.process_incoming() for rt in runtimes):
            quiet = 0
        else:
            quiet += 1
            time.sleep(0.02)


def main() -> None:
    server = FluidNetworkServer()
    server.start()
    try:
        def editor():
            svc = NetworkFluidService("127.0.0.1", server.port)
            return ContainerRuntime(
                svc, "shared-doc",
                channels=(SharedString("text"), SharedMap("cursors")),
            )

        alice, bob = editor(), editor()
        alice.get_channel("text").insert_text(0, "Hello world")
        drain([alice, bob])

        # Concurrent edits at both ends.
        bob.get_channel("text").insert_text(11, " from Bob")
        alice.get_channel("text").insert_text(0, ">> ")
        alice.get_channel("cursors").set("alice", 3)
        bob.get_channel("cursors").set("bob", 20)
        drain([alice, bob])

        ta = alice.get_channel("text").get_text()
        tb = bob.get_channel("text").get_text()
        assert ta == tb, (ta, tb)
        print(f"converged text: {ta!r}")
        print(
            "cursors:",
            {k: alice.get_channel("cursors").get(k) for k in ("alice", "bob")},
        )
        alice.disconnect()
        bob.disconnect()
    finally:
        server.stop()


if __name__ == "__main__":
    main()
