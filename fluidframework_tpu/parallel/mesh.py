"""Document sharding across the TPU mesh.

The scale-out story (SURVEY.md §2.6): the reference shards documents across
Kafka partitions consumed by lambda hosts
(``lambdas-driver/src/document-router/documentLambda.ts:20``, 8 partitions
default). Here the analog is a ``jax.sharding.Mesh`` with a ``docs`` axis:
the [D, ...] batched :class:`SegmentState` and the [D, K, W] op batches are
sharded over it, op application runs fully parallel per document (no
cross-document dependencies, so no collectives in the apply path), and only
the telemetry/stats reduction crosses shards (an all-reduce that rides ICI).
Multi-host extends the same axis over DCN — the sharding spec, not the
kernel, changes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fluidframework_tpu.ops.merge_kernel import batched_apply_ops, batched_compact
from fluidframework_tpu.ops.segment_state import SegmentState, make_batched_state
from fluidframework_tpu.parallel import aot
from fluidframework_tpu.protocol.constants import NO_CLIENT


def make_mesh(n_devices: Optional[int] = None, axis: str = "docs") -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"make_mesh({n_devices}): only {len(devs)} "
                f"{devs[0].platform} device(s) present"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def shard_state(state: SegmentState, mesh: Mesh, axis: str = "docs") -> SegmentState:
    """Place a [D, ...] batched state with the doc axis sharded over the mesh."""
    lane = NamedSharding(mesh, P(axis))
    scalar = NamedSharding(mesh, P(axis))
    return SegmentState(
        *[
            jax.device_put(x, lane if x.ndim == 2 else scalar)
            for x in state
        ]
    )


def shard_ops(ops: jnp.ndarray, mesh: Mesh, axis: str = "docs") -> jnp.ndarray:
    return jax.device_put(ops, NamedSharding(mesh, P(axis)))


def apply_and_stats(state: SegmentState, ops: jnp.ndarray):
    """One sharded service step: apply each document's op batch, then reduce
    fleet-wide telemetry (rows in use, error count, max seq) — the only
    cross-shard communication in the pipeline."""
    out = batched_apply_ops(state, ops)
    stats = {
        "rows_in_use": jnp.sum(out.count),
        "docs_with_errors": jnp.sum((out.err != 0).astype(jnp.int32)),
        "max_seq": jnp.max(out.cur_seq),
        "min_window": jnp.min(out.min_seq),
    }
    return out, stats


# One jitted XLA step shared by every DocShard: re-wrapping per instance
# (the old ``self._step = jax.jit(...)`` in __init__) made each new shard
# re-trace an identical program (graftlint recompile-hazard).
_jit_apply_and_stats = jax.jit(apply_and_stats, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _mesh_pallas_step(mesh: Mesh, axis: str, interpret: bool):
    """The Pallas apply + telemetry reduction under shard_map, cached per
    (mesh, axis, interpret) so every DocShard of one deployment shape
    shares one compiled executable (the fleet.py builder pattern)."""
    from fluidframework_tpu.ops.pallas_kernel import (
        SC_COUNT,
        SC_CUR_SEQ,
        SC_ERR,
        SC_MIN_SEQ,
        apply_ops_packed,
    )

    def per_shard(tables, scalars, ops):
        tables, scalars = apply_ops_packed(
            tables, scalars, ops, block_docs=32, interpret=interpret
        )
        stats = {
            "rows_in_use": jax.lax.psum(
                jnp.sum(scalars[:, SC_COUNT]), axis
            ),
            "docs_with_errors": jax.lax.psum(
                jnp.sum((scalars[:, SC_ERR] != 0).astype(jnp.int32)), axis
            ),
            "max_seq": jax.lax.pmax(
                jnp.max(scalars[:, SC_CUR_SEQ]), axis
            ),
            "min_window": jax.lax.pmin(
                jnp.min(scalars[:, SC_MIN_SEQ]), axis
            ),
        }
        return tables, scalars, stats

    return jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(None, axis, None), P(axis, None),
                      P(axis, None, None)),
            out_specs=(P(None, axis, None), P(axis, None), P()),
            check_vma=False,  # pallas_call outputs carry no vma info
        ),
        donate_argnums=(0, 1),
    )


@functools.lru_cache(maxsize=None)
def _mesh_pallas_compact(mesh: Mesh, axis: str, interpret: bool):
    from fluidframework_tpu.ops.pallas_compact import compact_packed

    def per_shard(tables, scalars):
        return compact_packed(tables, scalars, interpret=interpret)

    return jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(None, axis, None), P(axis, None)),
            out_specs=(P(None, axis, None), P(axis, None)),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )


# Batched multi-doc slice for the packed (pallas) layout: N docs' table
# planes + scalar rows gathered on device in one jitted call — the
# fleet.py ``_docs_gather`` analog for the packed fleet (r15 read path).
_docs_slice_packed = jax.jit(lambda tables, scalars, docs: (
    tables[:, docs], scalars[docs]
))


def unpack_packed_doc_states(
    host: np.ndarray, docs, s: int, pad: int = 0
) -> dict:
    """Split one packed-layout multi-doc readback — ``[L, pad, S]`` lane
    planes followed by ``[pad, N_SCALARS]`` scalar rows, flattened into
    one vector — into per-doc SegmentStates (``pad`` rows beyond
    ``len(docs)`` are gather padding, discarded). THE one unpack for
    the packed gather layout (``DocShard.doc_states``, pallas)."""
    from fluidframework_tpu.ops.pallas_kernel import (
        SC_COUNT,
        SC_CUR_SEQ,
        SC_ERR,
        SC_MIN_SEQ,
        SC_SELF,
    )
    from fluidframework_tpu.ops.segment_state import SEGMENT_LANES

    pad = pad or len(docs)
    nl = len(SEGMENT_LANES)
    lanes = host[: nl * pad * s].reshape(nl, pad, s)
    scal = host[nl * pad * s:].reshape(pad, -1)
    return {
        d: SegmentState(
            **{k: lanes[i, j] for i, k in enumerate(SEGMENT_LANES)},
            count=scal[j, SC_COUNT],
            min_seq=scal[j, SC_MIN_SEQ],
            cur_seq=scal[j, SC_CUR_SEQ],
            self_client=scal[j, SC_SELF],
            err=scal[j, SC_ERR],
        )
        for j, d in enumerate(docs)
    }


class DocShard:
    """A mesh-resident fleet of documents — the compute backend the service
    layer feeds with sequenced op batches (the ``TpuDeliLambda`` target).

    ``backend="xla"`` runs the vmapped XLA kernels under jit-with-shardings;
    ``backend="pallas"`` runs the VMEM-resident Pallas kernels per shard
    under ``shard_map`` (each device owns its doc slice; only the telemetry
    reduction crosses shards). Both produce bit-identical states."""

    def __init__(
        self,
        n_docs: int,
        capacity: int,
        mesh: Optional[Mesh] = None,
        axis: str = "docs",
        backend: str = "xla",
        interpret: Optional[bool] = None,
    ):
        assert backend in ("xla", "pallas"), f"unknown backend {backend!r}"
        self.mesh = mesh or make_mesh(axis=axis)
        self.axis = axis
        self.backend = backend
        n_dev = self.mesh.devices.size
        assert n_docs % n_dev == 0, (
            f"n_docs={n_docs} must divide evenly over {n_dev} devices"
        )
        self._docs_per_dev = n_docs // n_dev
        full = make_batched_state(n_docs, capacity, NO_CLIENT)
        if backend == "pallas":
            from fluidframework_tpu.ops.pallas_kernel import _on_tpu, pack_state

            self._interpret = (
                (not _on_tpu()) if interpret is None else interpret
            )
            tables, scalars = pack_state(full)
            ts = NamedSharding(self.mesh, P(None, axis, None))
            ss = NamedSharding(self.mesh, P(axis, None))
            self._tables = jax.device_put(tables, ts)
            self._scalars = jax.device_put(scalars, ss)
            self._pallas_step = _mesh_pallas_step(
                self.mesh, axis, self._interpret
            )
            self._pallas_compact = _mesh_pallas_compact(
                self.mesh, axis, self._interpret
            )
        else:
            self.state = shard_state(full, self.mesh, axis)
            self._step = _jit_apply_and_stats

    @property
    def packed(self):
        assert self.backend == "pallas"
        return self._tables, self._scalars

    def unpacked_state(self) -> SegmentState:
        """The fleet as a SegmentState (pallas backend: unpack on demand)."""
        if self.backend == "pallas":
            from fluidframework_tpu.ops.pallas_kernel import unpack_state

            return unpack_state(self._tables, self._scalars)
        return self.state

    # -- the service step -----------------------------------------------------

    def apply(self, ops: np.ndarray):
        """ops: [D, K, OP_WIDTH] int32 sequenced rows (NOOP-padded).

        Dispatches through the AOT donated-entry cache
        (``parallel/aot.py``): the mesh ``shard_map`` step is lowered and
        compiled once per (mesh, shape) bucket, so the steady-state
        serving loop pays neither tracing nor a jit cache lookup per
        boxcar — the r10 zero-per-flush-tracing contract extended to the
        mesh fleet."""
        sharded = shard_ops(jnp.asarray(ops, jnp.int32), self.mesh, self.axis)
        if self.backend == "pallas":
            key = (
                "docshard_pallas_step", self.mesh, self.axis,
                self._interpret, tuple(self._tables.shape),
                tuple(sharded.shape),
            )
            self._tables, self._scalars, stats = aot.call(
                key, lambda: self._pallas_step,
                self._tables, self._scalars, sharded,
            )
            return stats
        key = (
            "docshard_xla_step", self.mesh, self.axis,
            tuple(self.state.kind.shape), tuple(sharded.shape),
        )
        self.state, stats = aot.call(
            key, lambda: _jit_apply_and_stats, self.state, sharded
        )
        return stats

    def compact(self) -> None:
        if self.backend == "pallas":
            key = (
                "docshard_pallas_compact", self.mesh, self.axis,
                self._interpret, tuple(self._tables.shape),
            )
            self._tables, self._scalars = aot.call(
                key, lambda: self._pallas_compact,
                self._tables, self._scalars,
            )
        else:
            self.state = batched_compact(self.state)

    def doc_states(self, docs) -> dict:
        """N documents' full states in ONE batched device→host readback
        (r15 read-path fan-out — the ``telemetry_slice`` one-readback
        rule applied to snapshot reads): the per-doc gather stacks on
        device and one flat transfer serves every requested doc, instead
        of N per-doc slice round trips. Returns doc id ->
        :class:`SegmentState`, bit-identical to a per-doc slice."""
        from fluidframework_tpu.utils import pow2_at_least

        docs = [int(d) for d in docs]
        if not docs:
            return {}
        # Pow2-pad the index (padding re-gathers doc 0, discarded at
        # unpack) so compiled gather shapes stay logarithmic in reader
        # count — the DocFleet.doc_states_start rule.
        pad = pow2_at_least(len(docs))
        idx_np = np.zeros(pad, np.int32)
        idx_np[: len(docs)] = docs
        idx = jnp.asarray(idx_np)
        if self.backend == "pallas":
            lanes_dev, scal_dev = _docs_slice_packed(
                self._tables, self._scalars, idx
            )
            host = np.asarray(  # graftlint: readback(the ONE batched multi-doc gather readback — N snapshot reads, one transfer)
                jnp.concatenate(
                    [lanes_dev.reshape(-1), scal_dev.reshape(-1)]
                )
            )
            return unpack_packed_doc_states(
                host, docs, int(lanes_dev.shape[-1]), pad=pad
            )
        from fluidframework_tpu.parallel.fleet import DocFleet, _docs_gather

        host = np.asarray(_docs_gather(self.state, idx))  # graftlint: readback(the ONE batched multi-doc gather readback — N snapshot reads, one transfer)
        s = int(self.state.kind.shape[-1])
        return DocFleet.doc_states_finish(host, [(s, docs, pad)])

    def telemetry_slice(self) -> np.ndarray:
        """[n_devices, len(fleet.TELEMETRY_COLS)] per-mesh-shard health
        (occupancy, err counts by bit, seq watermarks) in ONE batched
        readback — the same jitted reductions the DocFleet pools use,
        with every doc slot live (a DocShard has no free slots). The
        pallas backend reduces straight off the packed scalar columns:
        unpacking would materialize every [D, S] lane plane just to read
        four scalars."""
        from fluidframework_tpu.parallel.fleet import (
            _pool_telemetry,
            _scalars_telemetry,
        )

        n_shards = self.mesh.devices.size
        if self.backend == "pallas":
            dev = _scalars_telemetry(self._scalars, n_shards)
        else:
            n = int(self.state.count.shape[0])
            dev = _pool_telemetry(self.state, jnp.ones(n, bool), n_shards)
        return np.asarray(dev)  # graftlint: readback(one batched per-shard telemetry readback per scrape — telemetry/README.md contract)
