"""ctypes bindings for the native (C++) runtime components.

The compute path is JAX/XLA; the storage/runtime path uses C++ where the
reference used native dependencies (SURVEY §2.9: libgit2-backed git storage
-> ``native/ca_store.cpp``). Libraries build on demand with ``make`` and
load via ctypes; callers fall back to pure-Python equivalents when the
toolchain is unavailable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional, Tuple

# Native sources/binaries live in the repo's native/ sibling; deployments
# that install the package elsewhere (e.g. the Dockerfile pip-installs
# into site-packages but ships native/ at /app/native) point here:
_NATIVE_DIR = os.environ.get("FLUID_NATIVE_DIR") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)

_libs: dict = {}  # so name -> CDLL | None (None = tried and failed)


def _load_lib(so_name: str) -> Optional[ctypes.CDLL]:
    """Load (building on demand with make) one native library; cached."""
    if so_name in _libs:
        return _libs[so_name]
    _libs[so_name] = None
    so = os.path.join(_NATIVE_DIR, so_name)
    # Rebuild when missing OR stale vs any source/Makefile — binaries are
    # not checked in, and a stale .so must never shadow source changes.
    stale = not os.path.exists(so)
    if not stale:
        try:
            so_mtime = os.path.getmtime(so)
            for f in os.listdir(_NATIVE_DIR):
                if (
                    f.endswith((".cpp", ".h", ".hpp")) or f == "Makefile"
                ) and (
                    os.path.getmtime(os.path.join(_NATIVE_DIR, f))
                    > so_mtime
                ):
                    stale = True
                    break
        except OSError:
            # A file vanishing mid-scan (concurrent make clean) means we
            # cannot trust the staleness verdict — rebuild.
            stale = True
    if stale:
        try:
            subprocess.run(
                ["make", "-C", _NATIVE_DIR],
                check=True,
                capture_output=True,
                timeout=120,
            )
        except (subprocess.SubprocessError, OSError):
            # A failed rebuild of a stale binary falls back to pure Python
            # rather than silently running outdated native code.
            return None
    try:
        _libs[so_name] = ctypes.CDLL(so)
    except OSError:
        return None
    return _libs[so_name]


def native_status() -> dict:
    """so name -> loaded? for every native library (building from the
    committed ``native/*.cpp`` on demand) — the serving entry points
    print this, so a failed ``make`` is seen, not swallowed."""
    return {
        so: _load_lib(so) is not None
        for so in (
            "libticket.so", "libplog.so", "libcastore.so", "libcoord.so"
        )
    }


_castore_registered = False


def _load_castore() -> Optional[ctypes.CDLL]:
    global _castore_registered
    lib = _load_lib("libcastore.so")
    if lib is None or _castore_registered:
        return lib
    _castore_registered = True
    lib.castore_new.restype = ctypes.c_void_p
    lib.castore_new.argtypes = [ctypes.c_char_p]
    lib.castore_free.argtypes = [ctypes.c_void_p]
    lib.castore_put.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.c_char_p,
    ]
    lib.castore_size.restype = ctypes.c_int64
    lib.castore_size.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.castore_get.restype = ctypes.c_int64
    lib.castore_get.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.castore_has.restype = ctypes.c_int
    lib.castore_has.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    return lib


class NativeBlobStore:
    """C++ content-addressed blob store (raises if the library is
    unavailable — use :func:`native_store_available` to probe)."""

    def __init__(self, directory: Optional[str] = None):
        lib = _load_castore()
        if lib is None:
            raise RuntimeError("libcastore.so unavailable")
        self._lib = lib
        self._h = lib.castore_new(
            directory.encode() if directory else None
        )

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.castore_free(self._h)
            self._h = None

    def put_blob(self, data: bytes) -> str:
        out = ctypes.create_string_buffer(65)
        self._lib.castore_put(self._h, data, len(data), out)
        return out.value.decode()

    def get_blob(self, handle: str) -> bytes:
        n = self._lib.castore_size(self._h, handle.encode())
        if n < 0:
            raise KeyError(handle)
        buf = ctypes.create_string_buffer(max(int(n), 1))
        got = self._lib.castore_get(self._h, handle.encode(), buf, n)
        assert got == n
        return buf.raw[:n]

    def has(self, handle: str) -> bool:
        return bool(self._lib.castore_has(self._h, handle.encode()))


def native_store_available() -> bool:
    return _load_castore() is not None


_plog_registered = False


def _load_plog() -> Optional[ctypes.CDLL]:
    global _plog_registered
    lib = _load_lib("libplog.so")
    if lib is None or _plog_registered:
        return lib
    _plog_registered = True
    lib.plog_new.restype = ctypes.c_void_p
    lib.plog_new.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.plog_free.argtypes = [ctypes.c_void_p]
    lib.plog_partition.restype = ctypes.c_int
    lib.plog_partition.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.plog_send.restype = ctypes.c_int64
    lib.plog_send.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.plog_end_offset.restype = ctypes.c_int64
    lib.plog_end_offset.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.plog_value_size.restype = ctypes.c_int64
    lib.plog_value_size.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int64,
    ]
    lib.plog_key_size.restype = ctypes.c_int64
    lib.plog_key_size.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int64,
    ]
    lib.plog_read.restype = ctypes.c_int64
    lib.plog_read.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int64,
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.plog_commit.restype = ctypes.c_int
    lib.plog_commit.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int,
        ctypes.c_int64,
    ]
    lib.plog_committed.restype = ctypes.c_int64
    lib.plog_committed.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int,
    ]
    return lib


class NativePartitionLog:
    """C++ disk-persistent partitioned log + consumer offsets
    (``native/partition_log.cpp`` — the kafka-broker durability role).
    Framed appends fflush per record; a restarted process reloads every
    partition file and the commit table. The CRC32 partitioner matches
    ``service.queue.partition_of`` exactly (same polynomial), so native
    and Python routing agree on every key."""

    def __init__(self, directory: Optional[str], n_partitions: int):
        lib = _load_plog()
        if lib is None:
            raise RuntimeError("libplog.so unavailable")
        self._lib = lib
        self._h = lib.plog_new(
            directory.encode() if directory else None, n_partitions
        )

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.plog_free(self._h)
            self._h = None

    def send(self, topic: str, key: str, value: bytes) -> Tuple[int, int]:
        """Append; returns (partition, offset)."""
        p = self._lib.plog_partition(self._h, key.encode())
        off = self._lib.plog_send(
            self._h, topic.encode(), key.encode(), value, len(value)
        )
        return int(p), int(off)

    def end_offset(self, topic: str, partition: int) -> int:
        return int(
            self._lib.plog_end_offset(self._h, topic.encode(), partition)
        )

    def read(
        self, topic: str, partition: int, offset: int
    ) -> Optional[Tuple[str, bytes]]:
        t = topic.encode()
        vn = self._lib.plog_value_size(self._h, t, partition, offset)
        kn = self._lib.plog_key_size(self._h, t, partition, offset)
        if vn < 0 or kn < 0:
            return None
        kbuf = ctypes.create_string_buffer(max(int(kn), 1))
        vbuf = ctypes.create_string_buffer(max(int(vn), 1))
        got = self._lib.plog_read(
            self._h, t, partition, offset, kbuf, kn, vbuf, vn
        )
        assert got == vn, "record changed size mid-read"
        return kbuf.raw[:kn].decode(), vbuf.raw[:vn]

    def commit(self, group: str, topic: str, partition: int,
               offset: int) -> None:
        self._lib.plog_commit(
            self._h, group.encode(), topic.encode(), partition, offset
        )

    def committed(self, group: str, topic: str, partition: int) -> int:
        return int(
            self._lib.plog_committed(
                self._h, group.encode(), topic.encode(), partition
            )
        )


def native_plog_available() -> bool:
    return _load_plog() is not None


_coord_registered = False


def _load_coord() -> Optional[ctypes.CDLL]:
    global _coord_registered
    lib = _load_lib("libcoord.so")
    if lib is None or _coord_registered:
        return lib
    _coord_registered = True
    lib.coord_new.restype = ctypes.c_void_p
    lib.coord_new.argtypes = [ctypes.c_char_p]
    lib.coord_free.argtypes = [ctypes.c_void_p]
    lib.coord_acquire.restype = ctypes.c_int64
    lib.coord_acquire.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_int64, ctypes.c_int64,
    ]
    lib.coord_renew.restype = ctypes.c_int
    lib.coord_renew.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_int64, ctypes.c_int64,
    ]
    lib.coord_holder.restype = ctypes.c_int64
    lib.coord_holder.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.coord_epoch.restype = ctypes.c_int64
    lib.coord_epoch.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.coord_release.restype = ctypes.c_int
    lib.coord_release.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
    ]
    return lib


class NativeCoordination:
    """C++ lease coordination (the ZooKeeper-client equivalent): fenced
    epochs per document, caller-supplied clock (ms), optional append-log
    durability. Same surface as the pure-Python ReservationManager."""

    def __init__(self, clock, path: Optional[str] = None):
        lib = _load_coord()
        if lib is None:
            raise RuntimeError("libcoord.so unavailable")
        self._lib = lib
        self._clock = clock
        self._h = lib.coord_new(path.encode() if path else None)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.coord_free(self._h)
            self._h = None

    def _now_ms(self) -> int:
        return int(self._clock() * 1000)

    def acquire(self, node: str, doc_id: str, ttl_s: float) -> Optional[int]:
        epoch = self._lib.coord_acquire(
            self._h, node.encode(), doc_id.encode(),
            int(ttl_s * 1000), self._now_ms(),
        )
        return int(epoch) if epoch > 0 else None

    def renew(self, node: str, doc_id: str, ttl_s: float) -> bool:
        return bool(
            self._lib.coord_renew(
                self._h, node.encode(), doc_id.encode(),
                int(ttl_s * 1000), self._now_ms(),
            )
        )

    def holder(self, doc_id: str) -> Optional[str]:
        out = ctypes.create_string_buffer(256)
        n = self._lib.coord_holder(
            self._h, doc_id.encode(), self._now_ms(), out, 256
        )
        return out.raw[:n].decode() if n >= 0 else None

    def release(self, node: str, doc_id: str) -> bool:
        """Voluntary surrender for load migration (same fencing as a TTL
        lapse — the next acquire bumps the epoch)."""
        return bool(
            self._lib.coord_release(
                self._h, node.encode(), doc_id.encode(), self._now_ms()
            )
        )

    def epoch(self, doc_id: str) -> int:
        return int(self._lib.coord_epoch(self._h, doc_id.encode()))


def native_coordination_available() -> bool:
    return _load_coord() is not None


# -- batch deli ticket loop (native/ticket_loop.cpp) -------------------------

_ticket_registered = False


def _load_ticket():
    global _ticket_registered
    lib = _load_lib("libticket.so")
    if lib is not None and not _ticket_registered:
        lib.ticket_batch.restype = ctypes.c_int32
        lib.ticket_batch.argtypes = [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        _ticket_registered = True
    return lib


class NativeTicketLoop:
    """Fleet-wide deli ticketing in C++ (the steady-state write-client
    fast path; see native/ticket_loop.cpp for the contract). Documents
    flagged in ``err`` must replay through the Python DocumentSequencer
    slow path (which owns nacks/joins/controls)."""

    def __init__(self):
        self._lib = _load_ticket()

    @property
    def available(self) -> bool:
        return self._lib is not None

    def ticket_batch(self, doc_state, clients, ops, out, err) -> int:
        """All arrays C-contiguous int32 numpy, shapes per ticket_loop.cpp.
        Returns the number of documents that need the slow path."""
        import numpy as np

        n_docs, k, _ = ops.shape
        max_writers = clients.shape[1]
        for a in (doc_state, clients, out, err):
            assert a.dtype == np.int32 and a.flags.c_contiguous
        assert ops.dtype == np.int32 and ops.flags.c_contiguous
        return int(
            self._lib.ticket_batch(
                n_docs, k, max_writers,
                doc_state.ctypes.data, clients.ctypes.data,
                ops.ctypes.data, out.ctypes.data, err.ctypes.data,
            )
        )


def native_ticket_available() -> bool:
    return _load_ticket() is not None
