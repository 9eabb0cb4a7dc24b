"""Distributed EC compute over a device mesh.

The scale story of the reference maps here (SURVEY.md §5.7-5.8):
  - encode: a batch of volumes × stripe length is sharded over
    ('data', 'seq'); parity is purely columnwise so the kernel runs with NO
    collectives — XLA partitions it for free. This is the 30GB-volume path:
    the stripe ('seq') axis is the long-sequence dimension.
  - degraded rebuild: surviving shards live on different devices along
    'shard' (like the reference's shards on different servers,
    weed/storage/store_ec.go:328-382). Each device computes its partial
    GF(256) contribution, then an all_gather over 'shard' + XOR-reduce
    combines them — XOR is the GF(2) addition, which psum can't express,
    so gather+reduce is the collective of record (rides ICI).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seaweedfs_tpu.models.coder import DEFAULT_SCHEME, RSScheme
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.rs_jax import _apply_matrix_words, _mat_to_tuple, _xtime


def _gf_mul_dynamic(c: jnp.ndarray, words: jnp.ndarray) -> jnp.ndarray:
    """c * words over GF(256) where c is a TRACED uint32 scalar holding a
    byte value (same constant applied to all 4 packed lanes)."""
    acc = jnp.zeros_like(words)
    d = words
    for b in range(8):
        bit = (c >> b) & 1
        mask = (jnp.uint32(0) - bit.astype(jnp.uint32))  # 0 or 0xffffffff
        acc = acc ^ (d & mask)
        if b < 7:
            d = _xtime(d)
    return acc


@functools.lru_cache(maxsize=None)
def encode_batch_fn(scheme: RSScheme, mesh: Mesh):
    """jit over the mesh: (batch, k, nw) uint32 sharded ('data', None, 'seq')
    -> (batch, m, nw) parity with matching sharding. No collectives."""
    mat = _mat_to_tuple(gf256.parity_matrix(scheme.data_shards,
                                            scheme.parity_shards))

    def one(words):
        return _apply_matrix_words(words, mat)

    in_s = NamedSharding(mesh, P("data", None, "seq"))
    out_s = NamedSharding(mesh, P("data", None, "seq"))
    return jax.jit(jax.vmap(one), in_shardings=(in_s,), out_shardings=out_s)


@functools.lru_cache(maxsize=None)
def rebuild_fn(scheme: RSScheme, mesh: Mesh, shards_per_device: int,
               n_out: int):
    """Distributed reconstruction: shard rows live along the 'shard' mesh
    axis; coefficient matrix arrives as a traced operand so one compiled
    program serves every survivor pattern.

    rows:  (S, nw) uint32, S = shard_axis_size * shards_per_device,
           sharded P('shard', 'seq')
    coeff: (n_out, S) uint32 (replicated); zero columns disable a row.
    returns (n_out, nw) sharded P(None, 'seq').
    """
    shard_axis = mesh.shape["shard"]

    def kernel(rows, coeff):
        # rows: (shards_per_device, nw_local) after shard_map partitioning
        didx = jax.lax.axis_index("shard")
        partial = jnp.zeros((n_out, rows.shape[1]), dtype=jnp.uint32)
        for local_j in range(shards_per_device):
            global_j = didx * shards_per_device + local_j
            cvec = jax.lax.dynamic_index_in_dim(coeff, global_j, axis=1,
                                                keepdims=False)  # (n_out,)
            for i in range(n_out):
                partial = partial.at[i].set(
                    partial[i] ^ _gf_mul_dynamic(cvec[i], rows[local_j]))
        # XOR-reduce across the shard axis: gather partials then fold.
        gathered = jax.lax.all_gather(partial, "shard")  # (shard_axis, n_out, nw)
        out = gathered[0]
        for d in range(1, shard_axis):
            out = out ^ gathered[d]
        return out

    # the value IS 'shard'-replicated after the XOR fold
    sm = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P("shard", "seq"), P()),
        out_specs=P(None, "seq"),
        check_vma=False)
    return jax.jit(sm)


def make_rebuild_coeff(scheme: RSScheme, present: tuple[int, ...],
                       wanted: tuple[int, ...], padded_s: int) -> np.ndarray:
    """Host-side coefficient matrix for rebuild_fn: wanted rows (data or
    parity shard ids) as GF(256) combinations of the first k present
    shards; missing/unused columns are zero."""
    k, total = scheme.data_shards, scheme.total_shards
    dm = np.asarray(gf256.decode_matrix(k, total, present))  # (k, k)
    full = np.asarray(gf256.rs_matrix(k, total))  # (total, k)
    src = list(present[:k])
    coeff = np.zeros((len(wanted), padded_s), dtype=np.uint32)
    for r, w in enumerate(wanted):
        # row of (w as combo of data shards) @ (data shards as combo of src)
        combo = gf256.gf_matmul(full[w][None, :], dm)[0]  # (k,) over src
        for j, s in enumerate(src):
            coeff[r, s] = int(combo[j])
    return coeff


def distributed_rebuild(scheme: RSScheme, mesh: Mesh,
                        shards: dict[int, np.ndarray],
                        wanted: tuple[int, ...]) -> np.ndarray:
    """Rebuild `wanted` shard rows from surviving `shards` ({id: (n,) uint8})
    across the mesh. Returns (len(wanted), n) uint8."""
    k, total = scheme.data_shards, scheme.total_shards
    present = tuple(sorted(shards))
    if len(present) < k:
        raise ValueError(f"too few shards: {len(present)} < {k}")
    n = len(next(iter(shards.values())))
    assert n % 4 == 0
    nw = n // 4
    shard_axis = mesh.shape["shard"]
    seq_axis = mesh.shape["seq"]
    assert nw % seq_axis == 0, (nw, seq_axis)
    padded_s = -(-total // shard_axis) * shard_axis
    spd = padded_s // shard_axis

    rows = np.zeros((padded_s, nw), dtype=np.uint32)
    for i, a in shards.items():
        rows[i] = np.ascontiguousarray(a, dtype=np.uint8).view(np.uint32)
    coeff = make_rebuild_coeff(scheme, present, wanted, padded_s)

    fn = rebuild_fn(scheme, mesh, spd, len(wanted))
    out = np.asarray(jax.device_get(fn(rows, coeff)))
    return out.view(np.uint8)[:, :n] if out.dtype == np.uint32 else out


def distributed_encode(scheme: RSScheme, mesh: Mesh,
                       batch: np.ndarray) -> np.ndarray:
    """batch: (B, k, n) uint8 -> (B, m, n) uint8 parity, sharded over
    ('data', 'seq')."""
    B, k, n = batch.shape
    assert k == scheme.data_shards and n % 4 == 0
    words = np.ascontiguousarray(batch).view(np.uint32)
    fn = encode_batch_fn(scheme, mesh)
    parity = np.asarray(jax.device_get(fn(words)))
    return parity.view(np.uint8)
