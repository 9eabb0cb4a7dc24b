"""GF(2^8) arithmetic and Reed-Solomon matrix construction.

Field: GF(2^8) with the primitive polynomial x^8+x^4+x^3+x^2+1 (0x11D),
generator 2 — the same field used by the reference's vendored codec
(klauspost/reedsolomon, itself derived from Backblaze's construction; see
reference go.mod:61 and weed/storage/erasure_coding/ec_encoder.go:17-23 for
where RS(10,4) is wired in). The encoding matrix is the systematic
Vandermonde construction: rows r of V are [r^0, r^1, ..., r^(k-1)], and the
final matrix is V * inv(V[:k]) so the top k rows are the identity. Matching
this construction exactly is what makes our .ec shards bit-identical to the
reference's.

Everything here is plain numpy — it is the ground-truth/reference path. The
device path (ops/rs_jax.py's kernels, ops/rs_mesh.py over them) is validated
bit-for-bit against this module.
"""

from __future__ import annotations

import functools

import numpy as np

GF_POLY = 0x11D  # x^8 + x^4 + x^3 + x^2 + 1
GF_GENERATOR = 2


def _build_tables() -> tuple[np.ndarray, np.ndarray]:
    """exp/log tables for GF(2^8) under GF_POLY with generator 2."""
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    # duplicate so exp[(log a + log b)] never needs an explicit mod
    for i in range(255, 512):
        exp[i] = exp[i - 255]
    return exp, log


GF_EXP, GF_LOG = _build_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(GF_EXP[int(GF_LOG[a]) + int(GF_LOG[b])])


def gf_div(a: int, b: int) -> int:
    if b == 0:
        raise ZeroDivisionError("GF(256) division by zero")
    if a == 0:
        return 0
    return int(GF_EXP[(int(GF_LOG[a]) - int(GF_LOG[b])) % 255])


def gf_inv(a: int) -> int:
    return gf_div(1, a)


def gf_exp_pow(base: int, n: int) -> int:
    """base**n in GF(256), with 0**0 == 1 (matches the reference construction)."""
    if n == 0:
        return 1
    if base == 0:
        return 0
    return int(GF_EXP[(int(GF_LOG[base]) * n) % 255])


@functools.lru_cache(maxsize=None)
def _mul_table() -> np.ndarray:
    """Full 256x256 product table; MUL_TABLE[a, b] = a*b in GF(256)."""
    a = np.arange(256)
    la = GF_LOG[a][:, None]
    lb = GF_LOG[a][None, :]
    prod = GF_EXP[(la + lb) % 255].astype(np.uint8)
    prod[0, :] = 0
    prod[:, 0] = 0
    return prod


MUL_TABLE = _mul_table()


@functools.lru_cache(maxsize=None)
def nibble_tables(c: int) -> tuple[np.ndarray, np.ndarray]:
    """Split-nibble tables for constant c: (low, high), 16 entries each,
    with c*d == low[d & 0xF] ^ high[d >> 4]. This is the table shape the
    PSHUFB/VGF2P8 kernels consume (native/rs_cpu.cpp make_nibble_tables);
    exposed here for the pure-numpy fallback and its cross-validation."""
    low = MUL_TABLE[c, :16].copy()
    high = MUL_TABLE[c, [v << 4 for v in range(16)]].copy()
    low.setflags(write=False)
    high.setflags(write=False)
    return low, high


@functools.lru_cache(maxsize=None)
def pair_table(c: int) -> np.ndarray:
    """65536-entry uint16 table applying c bytewise to a little-endian
    byte pair: pair_table(c)[b0 | b1<<8] == (c*b0) | (c*b1)<<8.

    One gather per TWO bytes — the numpy analogue of widening the
    split-nibble trick to byte granularity (numpy has no in-register
    shuffle, so fewer/larger gathers beat two 16-entry lookups; measured
    3.1x over the single-byte MUL_TABLE gather, see PERF.md round 6).
    128KiB per cached coefficient; an RS(10,4) parity matrix uses <=40."""
    row = MUL_TABLE[c].astype(np.uint16)
    tab = (row[None, :] | (row[:, None] << 8)).reshape(-1)
    tab.setflags(write=False)
    return tab


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Matrix product over GF(256). a: (m, k) uint8, b: (k, n) uint8 -> (m, n)."""
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    out = np.zeros((m, n), dtype=np.uint8)
    for j in range(k):
        # out ^= a[:, j] * b[j, :] elementwise over GF(256)
        out ^= MUL_TABLE[a[:, j][:, None], b[j, :][None, :]]
    return out


def gf_mat_invert(mat: np.ndarray) -> np.ndarray:
    """Invert a square matrix over GF(256) by Gauss-Jordan elimination."""
    mat = np.array(mat, dtype=np.uint8)
    n = mat.shape[0]
    assert mat.shape == (n, n)
    work = np.concatenate([mat, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        # pivot
        if work[col, col] == 0:
            for r in range(col + 1, n):
                if work[r, col] != 0:
                    work[[col, r]] = work[[r, col]]
                    break
            else:
                raise np.linalg.LinAlgError("singular matrix over GF(256)")
        pivot = int(work[col, col])
        inv_p = gf_inv(pivot)
        work[col] = MUL_TABLE[inv_p, work[col]]
        for r in range(n):
            if r != col and work[r, col] != 0:
                factor = int(work[r, col])
                work[r] ^= MUL_TABLE[factor, work[col]]
    return work[:, n:].copy()


@functools.lru_cache(maxsize=None)
def rs_matrix(data_shards: int, total_shards: int) -> np.ndarray:
    """Systematic Vandermonde encoding matrix, (total, data) uint8.

    Top `data_shards` rows are the identity; the remaining rows generate
    parity. Construction matches the reference codec so RS(10,4) shards are
    bit-identical.
    """
    assert 0 < data_shards < total_shards <= 256
    rows = total_shards
    cols = data_shards
    vm = np.zeros((rows, cols), dtype=np.uint8)
    for r in range(rows):
        for c in range(cols):
            vm[r, c] = gf_exp_pow(r, c)
    top_inv = gf_mat_invert(vm[:cols, :cols])
    mat = gf_matmul(vm, top_inv)
    mat.setflags(write=False)
    return mat


def parity_matrix(data_shards: int, parity_shards: int) -> np.ndarray:
    """The (parity, data) sub-matrix that maps data shards to parity shards."""
    return rs_matrix(data_shards, data_shards + parity_shards)[data_shards:]


@functools.lru_cache(maxsize=None)
def decode_matrix(data_shards: int, total_shards: int,
                  present: tuple[int, ...]) -> np.ndarray:
    """Matrix mapping the first `data_shards` present shards -> data shards.

    `present` is the sorted tuple of available shard indices (>= data_shards
    of them). Returns (data_shards, data_shards) uint8 D such that
    data = D @ stack(shards[present[:data_shards]]).
    """
    assert len(present) >= data_shards
    rows = rs_matrix(data_shards, total_shards)
    sub = rows[list(present[:data_shards]), :]
    return gf_mat_invert(sub)
