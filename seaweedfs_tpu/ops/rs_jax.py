"""JAX/TPU Reed-Solomon coder — the north-star compute kernel.

Replaces the reference's CPU SIMD codec (klauspost/reedsolomon, invoked from
weed/storage/erasure_coding/ec_encoder.go:199) with an XLA program that runs
on TPU.

Formulation: GF(256) multiplication is linear over GF(2), so the parity
transform factors into bitplanes. The flat-row kernel uses the Horner
form over output bits: for each parity row i, first XOR-combine the input
shards selected by bit b of the matrix constants (S_ib), then fold the 8
planes with one doubling chain per OUTPUT row:
    P_i = ((((S_i7 * 2) ^ S_i6) * 2) ^ ...) ^ S_i0
That needs 7 doublings per parity row (m=4) instead of 7 per input shard
(k=10) in the naive per-input chain — ~1.7x fewer VPU ops. We pack 4 field
elements per uint32 lane (SWAR: x2 via shift/mask/multiply) because TPU
vector registers have 32-bit lanes. The matrix is static at trace time, so
everything unrolls into an elementwise XOR/shift graph that XLA fuses into
one HBM-bound pass — no gather, no table lookup, no data-dependent control
flow.

Two operand layouts are in the tree. JaxCoder (this module) passes the
shards as SEPARATE flat device arrays (`parity_fn`, `_apply_matrix_rows`);
MeshCoder (ops/rs_mesh.py), the coder every benchmark cell serves with,
stacks a batch into one (B, k, nw) operand (`_apply_matrix_words`) so the
leading axis can be sharded over the batch mesh. What the ledger says is
about the stacked operand only: `kernel_roofline.seal` 3.40% for RS(10,4)
and 10.02% for RS(6,3) on a v5e (PERF_LEDGER.jsonl, PR 28). Flat rows
against the stacked operand on the chip is an open head-to-head (ROADMAP
S7); until it is measured both layouts stay.

This module is the home of the kernel primitives: `_xtime` and the two
static-matrix forms for encode, and `_gf_mul_dynamic`, the traced-
coefficient step of the rebuild program (`jit_ec_apply_*`). ops/gf256.py
is the semantics ground truth they are held to.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, ErasureCoder,
                                        RSScheme, register_coder)
from seaweedfs_tpu.ops import gf256

_LOW7 = np.uint32(0x7F7F7F7F)
_HIGH1 = np.uint32(0x80808080)
_RED = np.uint32(0x1D)  # 0x11D reduced into the low byte


def _xtime(v: jnp.ndarray) -> jnp.ndarray:
    """Multiply each packed byte by 2 in GF(2^8) (SWAR over uint32 lanes)."""
    hi = v & _HIGH1
    lo = (v & _LOW7) << 1
    return lo ^ ((hi >> 7) * _RED)


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


def _apply_matrix_words(words: jnp.ndarray, mat: tuple[tuple[int, ...], ...]) -> jnp.ndarray:
    """out[i] = XOR_j mat[i][j] * words[j] over GF(256), words: (k, nw) uint32.

    `mat` is a static python tuple -> the bit structure unrolls at trace time.
    """
    m = len(mat)
    k = len(mat[0])
    assert words.shape[0] == k
    acc: list[Optional[jnp.ndarray]] = [None] * m
    for j in range(k):
        d = words[j]
        for b in range(8):
            used = False
            for i in range(m):
                if (mat[i][j] >> b) & 1:
                    acc[i] = d if acc[i] is None else acc[i] ^ d
                    used = True
            # keep doubling only while some higher bit still needs it
            del used
            if b < 7 and any((mat[i][j] >> (b + 1)) for i in range(m)):
                d = _xtime(d)
    return jnp.stack([a if a is not None else jnp.zeros_like(words[0])
                      for a in acc])


def _apply_matrix_rows(rows: Sequence[jnp.ndarray],
                       mat: tuple[tuple[int, ...], ...]) -> list[jnp.ndarray]:
    """Horner-form transform over separate flat uint32 row arrays.

    Bit-identical to _apply_matrix_words (tested); JaxCoder's
    formulation (module docstring: the two layouts).
    """
    m, k = len(mat), len(mat[0])
    assert len(rows) == k
    outs = []
    for i in range(m):
        p = None
        for b in range(7, -1, -1):
            s = None
            for j in range(k):
                if (mat[i][j] >> b) & 1:
                    s = rows[j] if s is None else s ^ rows[j]
            if p is None:
                p = s
            else:
                p = _xtime(p)
                if s is not None:
                    p = p ^ s
        outs.append(p if p is not None else jnp.zeros_like(rows[0]))
    return outs


@functools.lru_cache(maxsize=None)
def _encode_fn(mat: tuple[tuple[int, ...], ...]):
    """jitted k flat uint32 rows -> tuple of m flat uint32 rows."""
    @jax.jit
    def f(*rows):
        return tuple(_apply_matrix_rows(rows, mat))
    return f


def _mat_to_tuple(mat: np.ndarray) -> tuple[tuple[int, ...], ...]:
    return tuple(tuple(int(x) for x in row) for row in np.asarray(mat))


def parity_fn(scheme: RSScheme = DEFAULT_SCHEME):
    """The jitted parity kernel: k flat uint32 rows -> tuple of m rows
    (the flat layout of the module docstring)."""
    pm = gf256.parity_matrix(scheme.data_shards, scheme.parity_shards)
    return _encode_fn(_mat_to_tuple(pm))


def decode_fn(scheme: RSScheme, present: tuple[int, ...]):
    """jitted kernel mapping the first k present shards -> all k data shards."""
    dm = gf256.decode_matrix(scheme.data_shards, scheme.total_shards, present)
    return _encode_fn(_mat_to_tuple(dm))


def bytes_to_words(rows: Sequence[bytes | np.ndarray]) -> tuple[np.ndarray, int]:
    """Stack byte rows into a (k, nw) uint32 matrix (zero-padded to 4B)."""
    n = len(rows[0])
    pad = (-n) % 4
    mats = []
    for r in rows:
        a = np.frombuffer(bytes(r), dtype=np.uint8) if not isinstance(r, np.ndarray) else r
        if pad:
            a = np.concatenate([a, np.zeros(pad, dtype=np.uint8)])
        mats.append(a.view(np.uint32))
    return np.stack(mats), n


def words_to_bytes(words: np.ndarray, n: int) -> list[bytes]:
    out = []
    for i in range(words.shape[0]):
        out.append(np.asarray(words[i]).view(np.uint8)[:n].tobytes())
    return out


@register_coder("jax")
class JaxCoder(ErasureCoder):
    """ErasureCoder running the GF(256) math on the default JAX backend
    (TPU when present). Byte-level results are bit-identical to CpuCoder."""

    def __init__(self, scheme: RSScheme = DEFAULT_SCHEME):
        super().__init__(scheme)
        self._parity_fn = parity_fn(scheme)

    def device_report(self) -> dict:
        # un-sharded jit: every dispatch lands on the default device
        from seaweedfs_tpu.parallel import mesh as mesh_mod
        return mesh_mod.device_report(mesh_mod.devices(1))

    def _run_rows(self, fn, words: np.ndarray) -> np.ndarray:
        """Apply a row-based jitted kernel to a (k, nw) uint32 host matrix,
        feeding each row as its own flat device array (module
        docstring), and restack on the host."""
        outs = fn(*[words[i] for i in range(words.shape[0])])
        return np.stack([np.asarray(jax.device_get(o)) for o in outs])

    def encode(self, shards: Sequence[bytes]) -> list[bytes]:
        k = self.scheme.data_shards
        words, n = bytes_to_words([shards[i] for i in range(k)])
        parity = self._run_rows(self._parity_fn, words)
        return [bytes(shards[i]) for i in range(k)] + words_to_bytes(parity, n)

    def encode_array(self, data: np.ndarray) -> np.ndarray:
        """(k, n) uint8 -> (m, n) uint8 parity. n must be a multiple of 4."""
        assert data.shape[1] % 4 == 0
        words = np.ascontiguousarray(data).view(np.uint32)
        parity = self._run_rows(self._parity_fn, words)
        return parity.view(np.uint8)

    def reconstruct(self, shards: Sequence[Optional[bytes]]) -> list[bytes]:
        k, total = self.scheme.data_shards, self.scheme.total_shards
        present = tuple(i for i in range(total) if shards[i] is not None)
        if len(present) < k:
            raise ValueError(f"too few shards: {len(present)} < {k}")
        missing = [i for i in range(total) if shards[i] is None]
        if not missing:
            return [bytes(s) for s in shards]
        words, n = bytes_to_words([shards[i] for i in present[:k]])
        data_words = self._run_rows(decode_fn(self.scheme, present), words)
        data_rows = words_to_bytes(data_words, n)
        out = [bytes(shards[i]) if shards[i] is not None else None
               for i in range(total)]
        for i in range(k):
            if out[i] is None:
                out[i] = data_rows[i]
        if any(i >= k for i in missing):
            parity = self._run_rows(self._parity_fn, data_words)
            prows = words_to_bytes(parity, n)
            for i in missing:
                if i >= k:
                    out[i] = prows[i - k]
        return [bytes(s) for s in out]

    def reconstruct_data(self, shards: Sequence[Optional[bytes]]) -> list[Optional[bytes]]:
        k, total = self.scheme.data_shards, self.scheme.total_shards
        present = tuple(i for i in range(total) if shards[i] is not None)
        if len(present) < k:
            raise ValueError(f"too few shards: {len(present)} < {k}")
        if all(shards[i] is not None for i in range(k)):
            return [bytes(s) if s is not None else None for s in shards]
        words, n = bytes_to_words([shards[i] for i in present[:k]])
        data_words = self._run_rows(decode_fn(self.scheme, present), words)
        rows = words_to_bytes(data_words, n)
        out = [bytes(s) if s is not None else None for s in shards]
        for i in range(k):
            out[i] = rows[i]
        return out
