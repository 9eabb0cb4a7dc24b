"""Compile the served path's programs for a DESCRIBED TPU v5e, no chip.

The TPU compiler is installed in the CPU sandbox and compiles for a
topology that is described, not attached (guide on-chip-measurement §2).
These tests hand it the main path's programs at their REAL widths:

  - the flat-row parity kernel (ops/rs_jax) at 1 MiB and 4 MiB rows;
  - the batch scheduler's two mesh programs (ops/rs_mesh) at every
    (B, n) bucket the bounded shape ladder of parallel/batcher.py can
    produce on one device, and on a 4-device batch mesh (no collective
    may appear: the batch axis is embarrassingly parallel).

A compile that passes is not a chip run — nothing executes, no time
means anything — but what the chip's compiler refuses, it refuses here.

The topology is described inside a module-scoped fixture (never at
import, never autouse) and everything compiles in this process: only
one process may hold libtpu, and under xdist every worker imports this
file.  The persistent compile cache is switched off around the compiles
(an entry written for a described chip cannot be read back without one).
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

from seaweedfs_tpu.models.coder import DEFAULT_SCHEME, RSScheme  # noqa: E402
from seaweedfs_tpu.ops import rs_jax, rs_mesh  # noqa: E402
from seaweedfs_tpu.parallel.batcher import shape_buckets  # noqa: E402

K = DEFAULT_SCHEME.data_shards
M = DEFAULT_SCHEME.parity_shards
MIB = 1 << 20
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")
# every (B, n) the scheduler's ladder can ask one device for
BUCKETS_1 = shape_buckets(max_batch=64, n_devices=1)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _batch_mesh(topo, n):
    return Mesh(np.array(topo.devices[:n]), ("batch",))


def _batch_args(mesh, B, n):
    s3 = NamedSharding(mesh, P("batch", None, None))
    words = jax.ShapeDtypeStruct((B, K, n // 4), jnp.uint32, sharding=s3)
    coeff = jax.ShapeDtypeStruct((B, M, K), jnp.uint32, sharding=s3)
    return words, coeff


@pytest.mark.parametrize("row_mib", [1, 4])
def test_flat_row_parity_compiles(one_chip, row_mib):
    rows = [jax.ShapeDtypeStruct((row_mib * MIB // 4,), jnp.uint32,
                                 sharding=one_chip)] * K
    compiled = rs_jax.parity_fn(DEFAULT_SCHEME).lower(*rows).compile()
    assert len(compiled.output_shardings) == M


def test_ladder_is_short():
    # the bound itself: what the compile tests below walk
    assert len(BUCKETS_1) <= 12, BUCKETS_1
    assert (1, MIB) in BUCKETS_1 and (1, 4 * MIB) in BUCKETS_1
    assert all(b & (b - 1) == 0 for b, _ in BUCKETS_1)


@pytest.mark.parametrize("B,n", BUCKETS_1)
def test_batch_encode_compiles_every_bucket(topo, B, n):
    mesh = _batch_mesh(topo, 1)
    words, _ = _batch_args(mesh, B, n)
    compiled = rs_mesh.batch_encode_fn(DEFAULT_SCHEME, mesh) \
        .lower(words).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 12 << 30  # fits one v5e's 16 GB


@pytest.mark.parametrize("B,n", BUCKETS_1)
def test_batch_apply_compiles_every_bucket(topo, B, n):
    mesh = _batch_mesh(topo, 1)
    compiled = rs_mesh.batch_apply_fn(DEFAULT_SCHEME, mesh) \
        .lower(*_batch_args(mesh, B, n)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 12 << 30


@pytest.mark.parametrize("kind", ["encode", "apply"])
def test_four_device_batch_mesh_has_no_collective(topo, kind):
    mesh = _batch_mesh(topo, 4)
    B, n = 16, 256 << 10  # the fullest batch the ladder gives 4 devices
    assert (B, n) in shape_buckets(max_batch=64, n_devices=4)
    words, coeff = _batch_args(mesh, B, n)
    if kind == "encode":
        compiled = rs_mesh.batch_encode_fn(DEFAULT_SCHEME, mesh) \
            .lower(words).compile()
    else:
        compiled = rs_mesh.batch_apply_fn(DEFAULT_SCHEME, mesh) \
            .lower(words, coeff).compile()
    text = compiled.as_text()
    assert not [c for c in COLLECTIVES if c in text]
    # each device holds its own B/4 lanes of the output
    (out_s,) = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert out_s.shard_shape((B, M, n // 4)) == (B // 4, M, n // 4)


@pytest.mark.parametrize("kind", ["encode", "apply"])
@pytest.mark.parametrize("n", [256 << 10, MIB])
def test_rs6_3_programs_compile_under_their_own_names(topo, kind, n):
    """A second geometry's two programs at the rungs its volumes use (a
    degraded read's 256 KiB, a seal's 1 MiB row), B = 1: each compiles
    for the chip and carries its geometry in its name, beside RS(10,4)'s."""
    scheme = RSScheme(6, 3)
    mesh = _batch_mesh(topo, 1)
    s3 = NamedSharding(mesh, P("batch", None, None))
    words = jax.ShapeDtypeStruct((1, 6, n // 4), jnp.uint32, sharding=s3)
    coeff = jax.ShapeDtypeStruct((1, 3, 6), jnp.uint32, sharding=s3)
    if kind == "encode":
        lowered = rs_mesh.batch_encode_fn(scheme, mesh).lower(words)
        ours = rs_mesh.batch_encode_fn(DEFAULT_SCHEME, mesh)
    else:
        lowered = rs_mesh.batch_apply_fn(scheme, mesh).lower(words, coeff)
        ours = rs_mesh.batch_apply_fn(DEFAULT_SCHEME, mesh)
    assert f"jit_ec_{kind}_rs_6_3" in lowered.as_text()[:200]
    assert f"jit_ec_{kind}_rs_10_4" in ours.lower(
        *_batch_args(mesh, 1, n)[:1 if kind == "encode" else 2]
    ).as_text()[:200]
    (out_s,) = jax.tree_util.tree_leaves(
        lowered.compile().output_shardings)
    assert out_s.shard_shape((1, 3, n // 4)) == (1, 3, n // 4)


def _chunks_warm_shapes():
    import json
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "traffic",
        "degraded1.chunks.json")
    with open(path) as f:
        return [tuple(s) for s in json.load(f)["warm"]["apply"]]


@pytest.mark.parametrize("B", [1, 2, 4])
def test_chunk_read_apply_program_compiles_at_one_mib(topo, B):
    """The apply program a rebuilt chunk read dispatches (cell
    ``reads.degraded1.chunks``: every lost interval a whole 1 MiB block,
    at most 4 jobs a dispatch): (B, 10, 1 MiB) in, (B, 4, 1 MiB) out
    under its own name, well inside the chip's memory; and the cell's
    traffic file warms exactly these."""
    assert (B, MIB) in _chunks_warm_shapes() and (B, MIB) in BUCKETS_1
    assert all(n == MIB and b in (1, 2, 4) for b, n in _chunks_warm_shapes())
    mesh = _batch_mesh(topo, 1)
    lowered = rs_mesh.batch_apply_fn(DEFAULT_SCHEME, mesh) \
        .lower(*_batch_args(mesh, B, MIB))
    assert "jit_ec_apply_rs_10_4" in lowered.as_text()[:200]
    compiled = lowered.compile()
    (out_s,) = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert out_s.shard_shape((B, M, MIB // 4)) == (B, M, MIB // 4)
    mem = compiled.memory_analysis()
    # operand and result of the dispatch, and little beside them
    assert mem.argument_size_in_bytes >= B * K * MIB
    assert mem.output_size_in_bytes >= B * M * MIB
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 1 << 30


@pytest.mark.parametrize("kind,width,B,n", [
    ("encode", 12, 1, MIB),            # a seal's 1 MiB row of twelve cells
    ("apply", 6, 1, 256 << 10),        # a local repair: the group's six
    ("apply", 6, 16, 256 << 10),       # ... the fullest batch of the rung
    ("apply", 12, 1, 256 << 10),       # two gone in a group: the global decode
])
def test_lrc12_2_2_programs_compile_under_their_own_names(topo, kind, width,
                                                          B, n):
    """A code FAMILY's programs (Azure's LRC(12,2,2), cell
    ``reads.degraded1.lrc12-2-2``) at the shapes its volumes use: the
    static-matrix encode of twelve rows, and the apply program at both of
    its widths, each under the family's name."""
    from seaweedfs_tpu.models.coder import LrcScheme
    scheme = LrcScheme(12, 2, 2)
    assert rs_mesh.apply_widths(scheme) == (6, 12)
    mesh = _batch_mesh(topo, 1)
    s3 = NamedSharding(mesh, P("batch", None, None))
    words = jax.ShapeDtypeStruct((B, width, n // 4), jnp.uint32, sharding=s3)
    coeff = jax.ShapeDtypeStruct((B, 4, width), jnp.uint32, sharding=s3)
    if kind == "encode":
        lowered = rs_mesh.batch_encode_fn(scheme, mesh).lower(words)
    else:
        lowered = rs_mesh.batch_apply_fn(scheme, mesh, width).lower(
            words, coeff)
    assert f"jit_ec_{kind}_lrc_12_2_2" in lowered.as_text()[:200]
    compiled = lowered.compile()
    (out_s,) = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert out_s.shard_shape((B, 4, n // 4)) == (B, 4, n // 4)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 1 << 30


@pytest.mark.parametrize("kind,width,B,n", [
    ("encode", 10, 1, MIB),            # a seal's row of ten 1 MiB blocks
    ("encode", 10, 1, 16 * MIB),       # a large-block batch of 16 units
    ("apply", 13, 1, 512 << 10),       # a lost block of groups 1-2
    ("apply", 13, 8, 512 << 10),       # ... the fullest batch of the rung
    ("apply", 14, 4, 512 << 10),       # a lost block of group 3
    ("apply", 20, 1, 256 << 10),       # the widest half-row decode
])
def test_hhxor_programs_compile_under_their_own_names(topo, kind, width,
                                                      B, n):
    """The piggybacked family's programs (Hitchhiker-XOR over RS(10,4),
    cell ``reads.degraded1.chunks.hhxor10-4``) at the shapes its volumes
    use: the seal of whole 1 MiB units with the piggybacks, and the
    apply program at its widths past k on half-block columns."""
    from seaweedfs_tpu.models.coder import HitchhikerScheme
    scheme = HitchhikerScheme(10, 4)
    assert rs_mesh.apply_widths(scheme) == (10, 13, 14, 20)
    mesh = _batch_mesh(topo, 1)
    s3 = NamedSharding(mesh, P("batch", None, None))
    words = jax.ShapeDtypeStruct((B, width, n // 4), jnp.uint32, sharding=s3)
    coeff = jax.ShapeDtypeStruct((B, 4, width), jnp.uint32, sharding=s3)
    if kind == "encode":
        lowered = rs_mesh.batch_encode_fn(scheme, mesh).lower(words)
    else:
        lowered = rs_mesh.batch_apply_fn(scheme, mesh, width).lower(
            words, coeff)
    assert f"jit_ec_{kind}_hhxor_10_4" in lowered.as_text()[:200]
    compiled = lowered.compile()
    (out_s,) = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert out_s.shard_shape((B, 4, n // 4)) == (B, 4, n // 4)
