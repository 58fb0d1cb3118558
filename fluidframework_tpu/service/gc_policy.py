"""The process's cyclic-collector policy while a server is serving.

The served path keeps what it writes (the in-process log, ``DocOpLog``,
the checkpoint store), so young containers survive and CPython's default
policy (700 / 10 / 10, a full pass at +25%) walks the resident fleet's
heap — millions of long-lived, acyclic per-document objects — again and
again in the middle of boxcars. While a :class:`FluidNetworkServer` runs,
the collector works on the server's schedule instead:

- **Passes at the loop's safe points.** The lag sentinel calls
  :func:`tick` between two callbacks of the loop. Once enough containers
  have been allocated since the last pass, the tick collects them and
  ``gc.freeze()``s the survivors: what the service keeps is walked ONCE
  and never again. Frozen objects are still freed by reference count;
  they are only out of the collector's sight, so a pass costs what was
  allocated since the last one, never the fleet.
- **Idle time first.** A loop that had little to do for a tick takes its
  pass after :data:`IDLE_QUANTUM` containers (a pause of a few ms, off
  every frame's path) and makes it a full collection; a loop under a
  standing backlog has no idle tick and waits for :data:`BUSY_QUANTUM`,
  the bound — an allocation count, not a timer — on how long garbage
  cycles may wait, and collects the young generations only: no
  generation-2 pass while frames are queued.
- **Thresholds as the backstop.** The automatic collector stays ON with
  its young threshold above :data:`BUSY_QUANTUM`: it fires only where no
  tick comes in time (one long callback, such as a bulk join of
  thousands of documents).

What this gives up: a reference cycle whose members were frozen ALIVE
and died later is reclaimed only when the last server stops
(``gc.unfreeze()`` belongs to :func:`release` alone).

The collector is the process's, so the policy is too: servers nest
(:func:`acquire` / :func:`release` count), the first finds the
thresholds and the last restores them.
"""

from __future__ import annotations

import gc
import threading
from typing import Optional, Tuple

#: Containers allocated (net of those freed) since the last pass after
#: which a tick collects under a standing backlog. A boxcar of 128 frames
#: retains on the order of a thousand: some fifty boxcars a pass.
BUSY_QUANTUM = 50_000

#: The same count for a tick that finds the loop idle: a shorter pause,
#: taken sooner, where no frame waits behind it.
IDLE_QUANTUM = 10_000

#: ``gc.set_threshold`` while serving. Young: twice the busy quantum, so
#: the automatic collector runs only behind a tick that did not come.
#: Middle: as CPython's. Old: never on its own — a pass freezes what it
#: leaves, so the old generation holds nothing worth a walk.
THRESHOLDS = (2 * BUSY_QUANTUM, 20, 1_000_000)

_lock = threading.Lock()
_servers = 0
_found: Optional[Tuple[int, int, int]] = None  # thresholds before the first


def acquire() -> None:
    """A server starts serving: the first installs the thresholds."""
    global _servers, _found
    with _lock:
        if _servers == 0:
            _found = gc.get_threshold()
            gc.set_threshold(*THRESHOLDS)
        _servers += 1


def release() -> None:
    """A server stopped: the last restores the thresholds it found and
    hands the frozen heap back to the collector."""
    global _servers, _found
    with _lock:
        _servers -= 1
        if _servers == 0:
            gc.set_threshold(*_found)
            _found = None
            gc.unfreeze()


def tick(idle: bool) -> None:
    """One safe point of a serving loop: collect and freeze if enough
    has been allocated since the last pass."""
    young, passes, _ = gc.get_count()  # passes: the backstop's, unswept
    allocated = young + passes * THRESHOLDS[0]
    if allocated >= (IDLE_QUANTUM if idle else BUSY_QUANTUM):
        gc.collect(2 if idle else 1)
        gc.freeze()
