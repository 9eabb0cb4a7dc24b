"""Plain reference for erasure coding over GF(2^8), by code family.

Independent of ``seaweedfs_tpu``: imports nothing of the program and
takes nothing the program has made.  A code is the ``code`` block of a
configuration file, and ``code_parity_matrix`` is the one way from a
block to its matrix:

- ``family`` ``"rs"`` (``data_shards`` k, ``parity_shards`` m): the
  published construction the upstream project uses (klauspost/reedsolomon
  as vendored by SeaweedFS): the field GF(2^8) with the polynomial
  x^8+x^4+x^3+x^2+1 (0x11d) and generator 2; a (k+m) x k Vandermonde
  matrix ``V[r][c] = r^c`` multiplied by the inverse of its top k x k
  square, so that the top is the identity (data shards are the data) and
  the bottom m rows are the parity matrix.
- ``family`` ``"lrc"`` (``data_shards`` k, ``local_groups`` l,
  ``global_parities`` g, ``parity_shards`` = l + g): a basic pyramid code
  over that same matrix (``lrc_parity_matrix``).

Each family is a function of its own here; a further family is a further
function and a line in ``code_parity_matrix``.

The shard layout is upstream's two-tier block interleave
(weed/storage/erasure_coding/ec_encoder.go): while MORE than
``large_block * k`` bytes remain a row takes k large blocks, then rows
of k small blocks take the rest, the last one zero-filled past the end
of the ``.dat``; every row appends one whole block to each of the k+m
shard files.

Speed matters only in that a run pays for the comparison after every
window: multiplication by a constant is a table look-up over pairs of
bytes (a 65,536-entry table per coefficient), nothing cleverer.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

POLY = 0x11D
MIB = 1 << 20


def _build_tables() -> tuple[list[int], list[int]]:
    exp = [0] * 512
    log = [0] * 256
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    for i in range(255, 512):
        exp[i] = exp[i - 255]
    return exp, log


EXP, LOG = _build_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return EXP[LOG[a] + LOG[b]]


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return EXP[255 - LOG[a]]


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return EXP[(LOG[a] * n) % 255]


def mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = [[0] * len(b[0]) for _ in a]
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            acc = 0
            for t, v in enumerate(row):
                acc ^= gf_mul(v, b[t][j])
            out[i][j] = acc
    return out


def mat_inv(m: list[list[int]]) -> list[list[int]]:
    """Gauss-Jordan over GF(2^8); raises on a singular matrix."""
    n = len(m)
    a = [list(row) + [1 if i == j else 0 for j in range(n)]
         for i, row in enumerate(m)]
    for col in range(n):
        piv = next((r for r in range(col, n) if a[r][col]), None)
        if piv is None:
            raise ValueError("singular matrix")
        a[col], a[piv] = a[piv], a[col]
        inv = gf_inv(a[col][col])
        a[col] = [gf_mul(v, inv) for v in a[col]]
        for r in range(n):
            if r != col and a[r][col]:
                f = a[r][col]
                a[r] = [v ^ gf_mul(f, w) for v, w in zip(a[r], a[col])]
    return [row[n:] for row in a]


def generator_matrix(k: int, m: int) -> list[list[int]]:
    """(k+m) x k systematic generator: identity on top, parity below."""
    vand = [[gf_pow(r, c) for c in range(k)] for r in range(k + m)]
    return mat_mul(vand, mat_inv(vand[:k]))


def parity_matrix(k: int, m: int) -> list[list[int]]:
    return generator_matrix(k, m)[k:]


def lrc_parity_matrix(k: int, l: int, g: int) -> list[list[int]]:
    """The l + g parity rows of LRC(k, l, g), a basic pyramid code
    (Huang, Chen, Li, "Pyramid Codes: Flexible Schemes to Trade Space for
    Access Efficiency in Reliable Data Storage Systems", 2007; the
    topology of k data, l local and g global fragments is that of Huang
    et al., "Erasure Coding in Windows Azure Storage", USENIX ATC 2012).
    Take the g + 1 parity rows of RS(k, g + 1); split row 0 into l rows,
    the i-th keeping row 0's coefficients on the i-th group of k / l
    columns and zero elsewhere (a group's local parity: any one of its
    k / l + 1 members is a combination of the others); rows 1..g stay
    as the global parities.  Shard order: data, locals, globals.

    The COEFFICIENTS are this store's choice: the pyramid construction
    says which MDS code to start from only up to its being MDS, the store
    starts from upstream's Vandermonde-derived RS matrix, and Azure's own
    coefficients are not published as a matrix.  A configuration that
    cites Azure's LRC lists that under ``assumed``."""
    if l <= 0 or k % l:
        raise ValueError(f"LRC: {l} local groups do not divide {k} "
                         "data shards evenly")
    base = parity_matrix(k, g + 1)
    size = k // l
    locals_ = [[c if i * size <= j < (i + 1) * size else 0
                for j, c in enumerate(base[0])] for i in range(l)]
    return locals_ + base[1:]


def rs_code(k: int, m: int, large_block: int = 1 << 30,
            small_block: int = MIB) -> dict:
    """The ``code`` block of plain RS(k, m), for a caller that has the
    numbers and no configuration file."""
    return {"family": "rs", "data_shards": k, "parity_shards": m,
            "large_block_bytes": large_block,
            "small_block_bytes": small_block}


def code_parity_matrix(code: dict) -> list[list[int]]:
    """From a configuration's ``code`` block to its parity matrix
    (``parity_shards`` rows of ``data_shards`` coefficients): the one
    place that reads ``family``."""
    family = code.get("family", "rs")
    k, m = code["data_shards"], code["parity_shards"]
    if family == "rs":
        return parity_matrix(k, m)
    if family == "lrc":
        l, g = code["local_groups"], code["global_parities"]
        if m != l + g:
            raise ValueError(f"LRC({k},{l},{g}) has {l + g} parity "
                             f"shards; the code block states {m}")
        return lrc_parity_matrix(k, l, g)
    raise ValueError(f"unknown code family {family!r}")


def recover_matrix(k: int, m: int, present: list[int],
                   missing: list[int]) -> list[list[int]]:
    """RS(k, m): rows that give each ``missing`` shard from the first k
    of the ``present`` ones."""
    gen = generator_matrix(k, m)
    src = sorted(present)[:k]
    inv = mat_inv([gen[s] for s in src])
    return mat_mul([gen[s] for s in missing], inv)


_pair_tables: dict[int, np.ndarray] = {}


def _pair_table(c: int) -> np.ndarray:
    """uint16 table: both bytes of a little-endian pair times ``c``."""
    t = _pair_tables.get(c)
    if t is None:
        one = np.array([gf_mul(c, v) for v in range(256)], dtype=np.uint16)
        t = (one[:, None] << 8 | one[None, :]).reshape(-1)
        _pair_tables[c] = t
    return t


def apply_matrix(mat: list[list[int]], rows: np.ndarray) -> np.ndarray:
    """(r x k) matrix over GF(2^8) times (k, n) uint8 rows, n even."""
    k, n = rows.shape
    if n % 2:
        raise ValueError("an even number of columns is needed")
    pairs = np.ascontiguousarray(rows).view(np.uint16)
    out = np.zeros((len(mat), n // 2), dtype=np.uint16)
    tmp = np.empty(n // 2, dtype=np.uint16)
    for i, coeffs in enumerate(mat):
        for j, c in enumerate(coeffs):
            if c == 0:
                continue
            if c == 1:
                out[i] ^= pairs[j]
                continue
            np.take(_pair_table(c), pairs[j], out=tmp)
            out[i] ^= tmp
    return out.view(np.uint8)


# ---- layout ----

def encode_rows(dat_size: int, k: int, large_block: int,
                small_block: int) -> list[tuple[int, int]]:
    """The rows of a ``.dat`` of ``dat_size`` bytes, in order:
    (offset of the row in the .dat, block size)."""
    rows = []
    off = 0
    remaining = dat_size
    while remaining > 0:
        block = large_block if remaining > large_block * k else small_block
        rows.append((off, block))
        off += block * k
        remaining -= block * k
    return rows


def shard_file_size(dat_size: int, k: int, large_block: int,
                    small_block: int) -> int:
    return sum(b for _, b in encode_rows(dat_size, k, large_block,
                                         small_block))


def _layout_of(code: dict) -> tuple[int, int, int]:
    """What ``encode_rows`` needs of a ``code`` block."""
    return (code["data_shards"], code["large_block_bytes"],
            code["small_block_bytes"])


def _read_into(f, offset: int, out: np.ndarray) -> None:
    out[:] = 0
    f.seek(offset)
    buf = f.read(len(out))
    if buf:
        out[:len(buf)] = np.frombuffer(buf, dtype=np.uint8)


def differing_files(dat_path: str, shard_paths: list[str], code: dict,
                    step: int = MIB,
                    part: tuple[int, int] = (0, 1)) -> list[int]:
    """Ids of the shard files that do not hold, byte for byte, what the
    reference gives for ``dat_path`` under the ``code`` block: a wrong
    size, a missing file, a data shard that is not the .dat's blocks, a
    parity shard that is not the code of them.  ``part=(i, n)`` looks at
    every n-th step from the i-th on, so that n callers cover the file
    between them."""
    pm = code_parity_matrix(code)
    k, large_block, small_block = _layout_of(code)
    dat_size = os.path.getsize(dat_path)
    want_size = shard_file_size(dat_size, k, large_block, small_block)
    bad: set[int] = set()
    files = []
    for sid, p in enumerate(shard_paths):
        if not os.path.exists(p) or os.path.getsize(p) != want_size:
            bad.add(sid)
            files.append(None)
        else:
            files.append(open(p, "rb"))
    try:
        with open(dat_path, "rb") as dat:
            shard_off = 0
            seq = -1
            data = np.empty((k, 0), dtype=np.uint8)
            for row_off, block in encode_rows(dat_size, k, large_block,
                                              small_block):
                for b in range(0, block, step):
                    seq += 1
                    if seq % part[1] != part[0]:
                        continue
                    n = min(step, block - b)
                    if data.shape[1] != n:
                        data = np.empty((k, n), dtype=np.uint8)
                    for i in range(k):
                        _read_into(dat, row_off + i * block + b, data[i])
                    parity = apply_matrix(pm, data)
                    for sid, fh in enumerate(files):
                        if fh is None or sid in bad:
                            continue
                        fh.seek(shard_off + b)
                        got = np.frombuffer(fh.read(n), dtype=np.uint8)
                        want = data[sid] if sid < k else parity[sid - k]
                        if len(got) != n or not np.array_equal(got, want):
                            bad.add(sid)
                shard_off += block
    finally:
        for fh in files:
            if fh is not None:
                fh.close()
    return sorted(bad)


def differing_shard_files(dat_path: str, shard_paths: list[str], k: int,
                          m: int, large_block: int, small_block: int,
                          step: int = MIB,
                          part: tuple[int, int] = (0, 1)) -> list[int]:
    """``differing_files`` under plain RS(k, m), by its numbers."""
    return differing_files(dat_path, shard_paths,
                           rs_code(k, m, large_block, small_block), step,
                           part)


def expected_spans(dat_path: str, spans: list[tuple[int, int]],
                   code: dict) -> list[bytes]:
    """For each (offset, length) of a SHARD file, neither crossing a
    block's end: the k+m spans the reference gives there under the
    ``code`` block, one after another (data blocks from the ``.dat``,
    zero-filled past its end, then their parity).  What a seal's sampled
    look is compared with."""
    pm = code_parity_matrix(code)
    k, large_block, small_block = _layout_of(code)
    dat_size = os.path.getsize(dat_path)
    rows = encode_rows(dat_size, k, large_block, small_block)
    out = []
    with open(dat_path, "rb") as dat:
        for off, n in spans:
            shard_off = 0
            for row_off, block in rows:
                if off < shard_off + block:
                    break
                shard_off += block
            else:
                raise ValueError(f"offset {off} is past the shard file")
            b = off - shard_off
            if b + n > block:
                raise ValueError("a span may not cross a block's end")
            data = np.empty((k, n), dtype=np.uint8)
            for i in range(k):
                _read_into(dat, row_off + i * block + b, data[i])
            out.append(data.tobytes() + apply_matrix(pm, data).tobytes())
    return out


def differing_shard_files_many(jobs: list[tuple[str, list[str]]],
                               code: dict,
                               threads: int = 4) -> list[list[int]]:
    """``differing_files`` for several volumes of one ``code``, each
    file's steps dealt out to ``threads`` workers (numpy's look-ups and
    file reads release the interpreter lock)."""
    for c in {c for row in code_parity_matrix(code) for c in row if c > 1}:
        _pair_table(c)  # built once, before the threads share them
    with ThreadPoolExecutor(max_workers=threads) as pool:
        futs = [[pool.submit(differing_files, dat, shards, code, MIB,
                             (i, threads))
                 for i in range(threads)]
                for dat, shards in jobs]
        return [sorted({sid for f in parts for sid in f.result()})
                for parts in futs]
