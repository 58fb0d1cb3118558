"""Example: presence via signals (reference examples/apps/presence-tracker).

Presence is transient — it rides SIGNALS, never the sequenced op stream,
so joining/leaving and cursor blinks cost no document history. Run:

    python examples/presence_tracker.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from fluidframework_tpu.models.shared_map import SharedMap
from fluidframework_tpu.runtime.container import ContainerRuntime
from fluidframework_tpu.service.local_server import LocalFluidService


def main() -> None:
    svc = LocalFluidService()
    users = {
        name: ContainerRuntime(svc, "room", channels=(SharedMap("state"),))
        for name in ("ann", "ben", "cam")
    }

    # Everyone announces presence on the signal channel.
    for name, rt in users.items():
        rt.connection.submit_signal({"user": name, "status": "online"})

    seen = {
        name: [s.content["user"] for s in rt.connection.signals]
        for name, rt in users.items()
    }
    for name, others in seen.items():
        assert set(others) == {"ann", "ben", "cam"}, (name, others)
    print("presence fan-out:", seen)

    # Cursor movement: high-frequency, zero sequenced ops.
    before = len(svc.docs["room"].op_log)
    for i in range(20):
        users["ann"].connection.submit_signal({"user": "ann", "cursor": i})
    after = len(svc.docs["room"].op_log)
    assert before == after, "signals must not consume sequence numbers"
    print(f"20 cursor signals, {after - before} sequenced ops (transient)")


if __name__ == "__main__":
    main()
