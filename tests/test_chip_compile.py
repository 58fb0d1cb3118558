"""The serving path's kernel entries, compiled for the v5e by the TPU
compiler that is installed here — a described ``v5e:2x2`` topology, no
chip attached (on-chip-measurement guide, section 2, rehearsal 3).

Interpret mode cannot see what these see: a block that does not fit VMEM,
a tiling the chip refuses, a collective the partitioner slipped into the
apply path. Nothing runs, so nothing here says anything about results or
times; ``chip_smoke.py`` does that on the chip.

All chip-compile tests live in THIS file: the topology is described inside
a module-scoped fixture (one process may hold the TPU library), never at
import, in a ``skipif`` or in ``parametrize``.
"""

import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from fluidframework_tpu.ops import pallas_compact, pallas_kernel
from fluidframework_tpu.ops.pallas_kernel import N_LANES, N_SCALARS, doc_block
from fluidframework_tpu.ops.segment_state import SegmentState
from fluidframework_tpu.parallel import fleet
from fluidframework_tpu.protocol.constants import OP_WIDTH

_SCALAR_FIELDS = ("count", "min_seq", "cur_seq", "self_client", "err")
_COLLECTIVES = (
    "all-reduce", "all-gather", "all-to-all", "collective-permute",
    "reduce-scatter", "collective-broadcast",
)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip — keep it out of the way."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The wrappers ask ``default_backend()`` whether to interpret; this
    process is on the CPU, so the test answers for the described chip."""
    monkeypatch.setattr(pallas_kernel, "_on_tpu", lambda: True)
    monkeypatch.setattr(pallas_compact, "_on_tpu", lambda: True)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _packed(n_docs, cap, sharding):
    return (
        _i32((N_LANES, n_docs, cap), sharding),
        _i32((n_docs, N_SCALARS), sharding),
    )


def _state(n_docs, cap, sharding) -> SegmentState:
    return SegmentState(*[
        _i32((n_docs,) if f in _SCALAR_FIELDS else (n_docs, cap), sharding)
        for f in SegmentState._fields
    ])


def _assert_mosaic(compiled) -> str:
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


# 64 slots per tier is what the tiers phase of chip_smoke.py promotes; the
# block is the fleet's (an upper bound of 32, the kernel derives the rest).
@pytest.mark.parametrize("cap,blk", [(256, 32), (2048, 16), (16384, 8)])
def test_apply_compiles_at_fleet_tiers(one_chip, no_persistent_cache, cap, blk):
    n_slots, k = 64, 8
    assert doc_block(fleet._BLOCK_DOCS, n_slots, cap) == blk
    tables, scalars = _packed(n_slots, cap, one_chip)
    compiled = pallas_kernel.apply_ops_packed.lower(
        tables, scalars, _i32((n_slots, k, OP_WIDTH), one_chip),
        block_docs=fleet._BLOCK_DOCS, interpret=False,
    ).compile()
    _assert_mosaic(compiled)


def test_block_rule_keeps_mosaic_tiling():
    """Doc blocks are a multiple of 8 or the whole dim, at every tier the
    fleet can build and every pow2 slot count."""
    cap = 128
    while cap <= 1 << 15:
        for n in (1, 2, 4, 8, 64, 4096, 131072):
            blk = doc_block(fleet._BLOCK_DOCS, n, cap)
            assert n % blk == 0 and (blk % 8 == 0 or blk == n), (n, cap, blk)
            assert blk == n or blk * cap <= max(8 * cap, 1 << 15)
        cap *= 2


# BASELINE config 5's fleet (131,072 slots x 128 rows) under a full
# 512-row boxcar, under the small boxcars a websocket partition sends
# (B = 8 one block, B = 1 and 2 whole-dim blocks; K = 16 as the ws
# cell's warm_boxcars builds them on its 16,384 slots), and one document
# of the top tier.
@pytest.mark.parametrize("n_slots,cap,b,k", [
    (131072, 128, 512, 8), (131072, 128, 8, 8), (131072, 128, 1, 8),
    (16384, 128, 32, 16), (16384, 128, 2, 16), (8, 32768, 1, 8),
])
def test_fused_sparse_step_compiles_at_base_tier(
    one_chip, no_persistent_cache, as_on_tpu, n_slots, cap, b, k
):
    """The pump's dispatch unit (gather, apply on [B, cap], scatter back
    into the donated pool) at every kind of boxcar shape it meets."""
    # __wrapped__: a fresh jitted entry, not one another test of this
    # worker may have traced in interpret mode.
    step = fleet._fused_sparse_step.__wrapped__("pallas", None)
    compiled = step.lower(
        _state(n_slots, cap, one_chip), _i32((b, k, OP_WIDTH), one_chip),
        _i32((b,), one_chip),
    ).compile()
    _assert_mosaic(compiled)
    # The names the benchmark's trace reduction finds the step by: the
    # program on the module line, the kernel's instruction, the scopes.
    text = compiled.as_text()
    assert text.startswith("HloModule jit_fluid_step")
    assert "%apply_ops_packed" in text
    for scope in ("gather", "apply", "scatter"):
        assert f"jit(fluid_step)/{scope}/" in text, scope
    # The kernel runs over the boxcar's B documents, and nothing of the
    # pool's size is made besides the donated state itself: every lane
    # is updated in place, there is no dense op batch and no copy of a
    # lane (a scalar lane's staging through fast memory is [n_slots]).
    assert f"s32[{N_LANES},{b},{cap}]" in text
    lanes = N_LANES * n_slots * cap * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= lanes
    assert mem.temp_size_in_bytes < n_slots * cap * 4
    assert f"[{n_slots},{k},{OP_WIDTH}]" not in text
    assert f"s32[{N_LANES},{n_slots},{cap}]" not in text
    if n_slots > b:
        assert not re.search(
            rf"= s32\[{n_slots},{cap}\]\S* copy(-start)?\(", text
        )


# The dirty-set compaction (gather the slots written since the last one,
# compact on [D, cap], scatter back into the donated pool) at the pool's
# bucket: BASELINE config 5's fleet and a promoted tier of it.
@pytest.mark.parametrize("n_slots,cap", [(131072, 128), (1024, 256)])
def test_pallas_compact_compiles_at_its_tiers(
    one_chip, no_persistent_cache, as_on_tpu, n_slots, cap
):
    assert cap <= fleet._PALLAS_COMPACT_MAX_CAP
    d = fleet._Pool.compact_bucket.fget(
        types.SimpleNamespace(n_slots=n_slots, capacity=cap)
    )
    assert d == (1 << 17) // cap
    entry = fleet._compact_entry.__wrapped__(cap, "pallas", None)
    compiled = entry.lower(
        _state(n_slots, cap, one_chip), _i32((d,), one_chip)
    ).compile()
    text = _assert_mosaic(compiled)
    assert text.startswith("HloModule jit_fluid_compact")
    assert "%compact_packed" in text
    for scope in ("gather", "compact", "scatter", "scan"):
        assert f"jit(fluid_compact)/{scope}/" in text, scope
    # The kernel runs over the bucket's D documents and nothing of the
    # pool's size is made besides the donated state itself.
    assert f"s32[{N_LANES},{d},{cap}]" in text
    assert f"s32[{N_LANES},{n_slots},{cap}]" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= N_LANES * n_slots * cap * 4
    if n_slots > d:
        assert mem.temp_size_in_bytes < n_slots * cap * 4


def test_fused_apply_compact_compiles_at_headline_shape(
    one_chip, no_persistent_cache
):
    """The fused apply + compact kernel at the shape of chip_smoke.py's
    ``kernels`` phase."""
    n_docs, cap, k = 32768, 256, 64
    tables, scalars = _packed(n_docs, cap, one_chip)
    compiled = pallas_compact.apply_compact_packed.lower(
        tables, scalars, _i32((n_docs, k, OP_WIDTH), one_chip),
        block_docs=32, interpret=False,
    ).compile()
    _assert_mosaic(compiled)


def test_xla_compact_compiles_at_a_big_tier(one_chip, no_persistent_cache):
    """Past _PALLAS_COMPACT_MAX_CAP the fleet compacts with XLA's, eight
    documents a pass at the top tiers."""
    cap, n_slots = 16384, 64
    entry = fleet._compact_entry.__wrapped__(cap, "pallas", None)
    compiled = entry.lower(
        _state(n_slots, cap, one_chip), _i32((8,), one_chip)
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_mesh_step_has_kernel_and_no_collective(
    topo, no_persistent_cache, as_on_tpu
):
    """The mesh fleet's apply on the four described devices: every device
    runs the Mosaic kernel on its own quarter of the slots, and nothing
    crosses devices."""
    mesh = Mesh(topo.devices, ("docs",))
    sharding = NamedSharding(mesh, P("docs"))
    n_slots, k = 4096, 8
    state = _state(n_slots, 128, sharding)
    step = fleet._mesh_step.__wrapped__(sharding)
    text = _assert_mosaic(
        step.lower(state, _i32((n_slots, k, OP_WIDTH), sharding)).compile()
    )
    found = [c for c in _COLLECTIVES if c in text]
    assert not found, f"collectives in the mesh apply path: {found}"
    # The pump's busy-set step: the boxcar arrives replicated, every
    # device keeps the slots of its own quarter and drops the rest.
    fused = fleet._fused_sparse_step.__wrapped__("pallas", sharding)
    rep = NamedSharding(mesh, P())
    text = _assert_mosaic(
        fused.lower(
            state, _i32((512, k, OP_WIDTH), rep), _i32((512,), rep)
        ).compile()
    )
    found = [c for c in _COLLECTIVES if c in text]
    assert not found, f"collectives in the fused mesh step: {found}"
    # The dirty-set compaction, per shard as the step: the slot vector
    # replicated, no collective.
    compact = fleet._compact_entry.__wrapped__(128, "pallas", sharding)
    text = _assert_mosaic(
        compact.lower(state, _i32((1024,), rep)).compile()
    )
    found = [c for c in _COLLECTIVES if c in text]
    assert not found, f"collectives in the mesh compaction: {found}"
