"""Plain reference for Hitchhiker-XOR over RS(k, r) (Rashmi, Shah, Gu,
Kuang, Borthakur, Ramchandran, "A 'Hitchhiker's' Guide to Fast and
Efficient Data Reconstruction in Erasure-coded Data Centers", SIGCOMM
2014; Hadoop 3's ``hhxor`` codec), as a configuration's ``code`` block
states it: ``family`` ``"hhxor"``, ``data_shards`` k, ``parity_shards``
r, ``substripes`` 2, ``groups`` (the r - 1 groups of data shards),
``small_block_bytes`` (the unit that is split in two) and
``large_block_bytes``.

Independent of ``seaweedfs_tpu``: built on ``reference.py``'s RS(k, r)
parity matrix and ``apply_matrix``, in straightforward numpy.

Every unit of every shard (one small block; a large block is a row of
such units) is two halves, a and b, coupled byte for byte.  Data shard
i holds (a_i, b_i); parity k holds (f1(a), f1(b)); parity k + j holds
(f_{j+1}(a), f_{j+1}(b) XOR the XOR of group j's a-halves), j = 1 ..
r - 1, with f the RS(k, r) parity rows.  The shard layout is
``reference.py``'s.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.reference import (apply_matrix, encode_rows, parity_matrix,
                                 recover_matrix)


def _numbers(code: dict) -> tuple[int, int, list[list[int]], int]:
    if code.get("family") != "hhxor" or code.get("substripes", 2) != 2:
        raise ValueError(f"not a Hitchhiker-XOR code block: {code}")
    k, r = code["data_shards"], code["parity_shards"]
    groups = [list(g) for g in code["groups"]]
    if len(groups) != r - 1 or sorted(sum(groups, [])) != list(range(k)):
        raise ValueError(f"groups {groups} are not {r - 1} parts of "
                         f"{k} data shards")
    return k, r, groups, code["small_block_bytes"]


def _halves(rows: np.ndarray, unit: int) -> tuple[np.ndarray, np.ndarray]:
    """(s, n) whole units -> the a-halves and the b-halves, (s, n / 2)
    each, unit after unit."""
    s, n = rows.shape
    v = rows.reshape(s, n // unit, 2, unit // 2)
    return (np.ascontiguousarray(v[:, :, 0]).reshape(s, -1),
            np.ascontiguousarray(v[:, :, 1]).reshape(s, -1))


def _units(a: np.ndarray, b: np.ndarray, unit: int) -> np.ndarray:
    s = a.shape[0]
    h = unit // 2
    return np.ascontiguousarray(np.stack(
        [a.reshape(s, -1, h), b.reshape(s, -1, h)], axis=2)).reshape(s, -1)


def _piggyback(a: np.ndarray, group: list[int]) -> np.ndarray:
    out = np.zeros(a.shape[1], dtype=np.uint8)
    for i in group:
        out ^= a[i]
    return out


def encode(data: np.ndarray, code: dict) -> np.ndarray:
    """(k, n) data of whole units -> (r, n) parity."""
    k, r, groups, unit = _numbers(code)
    a, b = _halves(data, unit)
    pm = parity_matrix(k, r)
    pa, pb = apply_matrix(pm, a), apply_matrix(pm, b)
    for j, group in enumerate(groups):
        pb[1 + j] ^= _piggyback(a, group)
    return _units(pa, pb, unit)


def decode(shards: list, code: dict) -> list[np.ndarray]:
    """All k + r shards (whole units each) from any k of them (None where
    lost): a from k a-halves by RS; the piggybacks taken off the
    parities' b-halves with the a-halves now known; b by RS; the lost
    parities' piggybacks put back."""
    k, r, groups, unit = _numbers(code)
    present = [i for i, s in enumerate(shards) if s is not None]
    if len(present) < k:
        raise ValueError(f"{len(present)} shards of {k + r} cannot decode")
    a = {}
    b = {}
    for i in present:
        a[i], b[i] = _halves(np.asarray(shards[i])[None], unit)
        a[i], b[i] = a[i][0], b[i][0]
    src = sorted(present)[:k]
    lost_a = [i for i in range(k) if i not in a]
    if lost_a:
        rows = apply_matrix(recover_matrix(k, r, present, lost_a),
                            np.stack([a[i] for i in src]))
        a.update(zip(lost_a, rows))
    data_a = np.stack([a[i] for i in range(k)])
    for j, group in enumerate(groups):
        if k + 1 + j in b:
            b[k + 1 + j] = b[k + 1 + j] ^ _piggyback(data_a, group)
    lost_b = [i for i in range(k) if i not in b]
    if lost_b:
        rows = apply_matrix(recover_matrix(k, r, present, lost_b),
                            np.stack([b[i] for i in src]))
        b.update(zip(lost_b, rows))
    data = _units(data_a, np.stack([b[i] for i in range(k)]), unit)
    parity = encode(data, code)
    return [data[i] for i in range(k)] + [parity[j] for j in range(r)]


def piggyback_reads(code: dict, sid: int) -> list[tuple[int, str]]:
    """The half-blocks a repair of a whole block of data shard ``sid``
    reads, as (shard, "a" | "b"): b of the other data shards and of
    parity k (b_sid by RS), b of the parity that carries sid's group, a
    of the group's other members.  k + |group| of them."""
    k, _r, groups, _unit = _numbers(code)
    j = next(j for j, g in enumerate(groups) if sid in g)
    return ([(i, "b") for i in range(k) if i != sid] + [(k, "b")]
            + [(k + 1 + j, "b")]
            + [(i, "a") for i in groups[j] if i != sid])


def iter_rows(dat_path: str, code: dict):
    """The layout's rows of ``dat_path`` in order: (offset in the shard
    files, (k + r, block) uint8 of what each shard holds there)."""
    k, _r, _groups, _unit = _numbers(code)
    dat_size = os.path.getsize(dat_path)
    shard_off = 0
    with open(dat_path, "rb") as f:
        for row_off, block in encode_rows(dat_size, k,
                                          code["large_block_bytes"],
                                          code["small_block_bytes"]):
            data = np.zeros((k, block), dtype=np.uint8)
            for i in range(k):
                f.seek(row_off + i * block)
                piece = f.read(block)
                data[i, :len(piece)] = np.frombuffer(piece, dtype=np.uint8)
            yield shard_off, np.vstack([data, encode(data, code)])
            shard_off += block


def shard_arrays(dat_path: str, code: dict) -> np.ndarray:
    """(k + r, shard size) uint8: every shard file the reference gives
    for ``dat_path``, in memory (for the small volumes of tests)."""
    rows = [r for _off, r in iter_rows(dat_path, code)]
    if not rows:
        return np.zeros((code["data_shards"] + code["parity_shards"], 0),
                        dtype=np.uint8)
    return np.hstack(rows)


def differing_files(dat_path: str, shard_paths: list[str],
                    code: dict) -> list[int]:
    """Ids of the shard files that do not hold, byte for byte, what the
    reference gives for ``dat_path``: a missing file, a wrong size, a
    row that differs.  Row by row, so a volume of any size fits."""
    k, r, _groups, _unit = _numbers(code)
    files = [open(p, "rb") if os.path.exists(p) else None
             for p in shard_paths]
    bad = {sid for sid, f in enumerate(files) if f is None}
    try:
        size = 0
        for off, want in iter_rows(dat_path, code):
            size = off + want.shape[1]
            for sid, f in enumerate(files):
                if sid in bad:
                    continue
                f.seek(off)
                got = np.frombuffer(f.read(want.shape[1]), dtype=np.uint8)
                if not np.array_equal(got, want[sid]):
                    bad.add(sid)
        for sid, p in enumerate(shard_paths):
            if sid not in bad and os.path.getsize(p) != size:
                bad.add(sid)
    finally:
        for f in files:
            if f is not None:
                f.close()
    return sorted(bad)
