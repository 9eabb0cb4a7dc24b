"""Hitchhiker-XOR (Rashmi et al., SIGCOMM 2014) on the rs_cpu GF(2^8)
substrate: a piggybacked VECTOR code over RS(k, r).

Every ``scheme.unit`` bytes of every shard (one 1 MiB block; a 1 GiB
block is 1,024 units) are two substripes: a, the unit's first half, and
b, its second, coupled byte for byte (the paper's hop-and-couple: each
substripe stays contiguous on disk).  With f the store's RS(k, r) parity
rows and the data shards split into r - 1 groups G_1 .. G_{r-1}:

    data shard i      (a_i, b_i)                      unchanged
    parity shard k    (f1(a), f1(b))
    parity k + j      (f_{j+1}(a), f_{j+1}(b) XOR a_{G_j})   j = 1..r-1

where a_{G_j} is the XOR of the group's a-halves.  The code is linear
over HALF-ROWS: a half-row is one substripe of one shard, (sid, sub),
and each of the 2 (k + r) half-rows is a GF(2^8) combination of the 2k
data half-rows (``generator``).  So every repair is one matrix over
half-rows (``plan_halves``): the sources it reads, (sid, sub) each, and
a coefficient row per wanted half-row.  The plans that matter:

  - a whole lost data block (both halves of data shard s of group j):
    b_s by RS from the other k - 1 data b-halves and parity k's, then
    a_s = (parity k+j's b-half) XOR f_{j+1}(b) XOR the group's other
    a-halves: k + |G_j| half-blocks (13 of a 1 MiB block under
    RS(10,4): 6.5 MiB instead of 10) for 2 half-blocks out;
  - a read inside one half: plain RS(k, r) on that substripe, k ranges
    (a-halves are never piggybacked; b-halves of data shards and of
    parity k are not either);
  - any r losses: decode a from k a-halves, strip the piggybacks off the
    parity b-halves, decode b.  MDS, at RS(k, r)'s storage.

The device runs the same two programs as every family (ops/rs_mesh.py):
the seal is RS(k, r) plus the XOR of each group's a-halves into parities
k+1 .. k+r-1 (``jit_ec_encode_hhxor_*``), and a repair is the apply
program at the plan's width (k, k + |G|, 2k) over half-block columns
(``jit_ec_apply_hhxor_*``).  The bytes API (``reconstruct``) and the
whole-shard rebuilds (``unit_plan``) take buffers of whole units.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

from seaweedfs_tpu.models.coder import HitchhikerScheme, register_coder
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.rs_cpu import CpuCoder

DEFAULT_HHXOR_SCHEME = HitchhikerScheme(10, 4)

A, B = 0, 1            # the two substripes of a unit


@functools.lru_cache(maxsize=None)
def generator(scheme: HitchhikerScheme) -> np.ndarray:
    """(2 (k + r), 2k) uint8: row 2 * sid + sub expresses half-row (sid,
    sub) over the data half-rows [a_0 .. a_{k-1}, b_0 .. b_{k-1}]."""
    k, m = scheme.data_shards, scheme.parity_shards
    par = np.asarray(gf256.parity_matrix(k, m), dtype=np.uint8)
    gen = np.zeros((2 * (k + m), 2 * k), dtype=np.uint8)
    for i in range(k):
        gen[2 * i + A, i] = 1
        gen[2 * i + B, k + i] = 1
    for j in range(m):
        gen[2 * (k + j) + A, :k] = par[j]
        gen[2 * (k + j) + B, k:] = par[j]
        if j:
            gen[2 * (k + j) + B, list(scheme.groups[j - 1])] ^= 1
    gen.setflags(write=False)
    return gen


def piggyback_sources(scheme: HitchhikerScheme, sid: int) -> list[tuple]:
    """The half-rows a whole-block repair of data shard ``sid`` reads:
    the other data shards' b-halves and parity k's (the RS decode of
    b_sid), the b-half of the parity that carries sid's group, and the
    group's other a-halves: k + |group| of them."""
    k = scheme.data_shards
    j = scheme.group_of(sid)
    return ([(i, B) for i in range(k) if i != sid] + [(k, B)]
            + [(k + 1 + j, B)]
            + [(i, A) for i in scheme.groups[j] if i != sid])


def _plain(scheme: HitchhikerScheme, half: tuple) -> bool:
    """Not a piggybacked half-row: every a-half, and the b-halves of
    the data shards and of parity k."""
    sid, sub = half
    return sub == A or sid <= scheme.data_shards


@functools.lru_cache(maxsize=4096)
def plan_halves(scheme: HitchhikerScheme, present: frozenset,
                wanted: tuple) -> tuple[tuple, np.ndarray]:
    """(sources, mat): the half-rows of the ``present`` shards a repair
    of the ``wanted`` half-rows reads, each a (sid, sub), and the
    (len(wanted), len(sources)) GF(2^8) matrix over them.  Both halves
    of one data shard take the piggyback plan where its sources are
    present (k + |group| reads); otherwise half-rows are taken, plain
    ones of the wanted substripes first, each that adds to the rank,
    until every wanted half-row is in their span, and the sources the
    matrix does not use are dropped.  Raises ValueError where the
    present shards cannot decode what is wanted."""
    gen = generator(scheme)
    wanted = tuple(wanted)
    subs = {sub for _sid, sub in wanted}
    cands = [(sid, sub) for sid in sorted(present) for sub in (A, B)]
    first = []
    if len(wanted) == 2 and wanted[0][0] == wanted[1][0] \
            and wanted[0][0] < scheme.data_shards and subs == {A, B}:
        first = [h for h in piggyback_sources(scheme, wanted[0][0])
                 if h[0] in present]
    rest = sorted(set(cands) - set(first), key=lambda h: (
        0 if _plain(scheme, h) and h[1] in subs else
        1 if _plain(scheme, h) else 2, h[0] >= scheme.data_shards,
        h[1], h[0]))
    order = first + rest
    n = len(order)
    basis: list[tuple] = []     # (row, its combination of order, pivot)

    def reduce(row: np.ndarray, comb: np.ndarray) -> None:
        for b, e, p in basis:
            c = int(row[p])
            if c:
                row ^= gf256.MUL_TABLE[c][b]
                comb ^= gf256.MUL_TABLE[c][e]

    def solve() -> Optional[np.ndarray]:
        mat = np.zeros((len(wanted), n), dtype=np.uint8)
        for w, (sid, sub) in enumerate(wanted):
            row = gen[2 * sid + sub].copy()
            reduce(row, mat[w])
            if row.any():
                return None
        return mat

    mat = solve() if not wanted else None
    for t, (sid, sub) in enumerate(order):
        row = gen[2 * sid + sub].copy()
        comb = np.zeros(n, dtype=np.uint8)
        comb[t] = 1
        reduce(row, comb)
        nz = np.flatnonzero(row)
        if nz.size == 0:
            continue
        inv = gf256.gf_inv(int(row[nz[0]]))
        basis.append((gf256.MUL_TABLE[inv][row],
                      gf256.MUL_TABLE[inv][comb], int(nz[0])))
        mat = solve()
        if mat is not None:
            break
    if mat is None:
        raise ValueError(
            f"{scheme!r}: half-rows {list(wanted)} cannot be decoded from "
            f"shards {sorted(present)}")
    used = [t for t in range(n) if mat[:, t].any()] or [0]
    mat = np.ascontiguousarray(mat[:, used])
    mat.setflags(write=False)
    return tuple(order[t] for t in used), mat


def to_halves(rows: np.ndarray, unit: int) -> np.ndarray:
    """(r, n) whole units -> (2r, n / 2): row 2i the a-halves of row i's
    units end to end, row 2i + 1 its b-halves."""
    r, n = rows.shape
    if n % unit:
        raise ValueError(f"{n} columns are not whole units of {unit}")
    h = unit // 2
    return np.ascontiguousarray(
        rows.reshape(r, n // unit, 2, h).transpose(0, 2, 1, 3)
    ).reshape(2 * r, n // 2)


def from_halves(halves: np.ndarray, unit: int) -> np.ndarray:
    """Inverse of ``to_halves``."""
    r2, n2 = halves.shape
    h = unit // 2
    return np.ascontiguousarray(
        halves.reshape(r2 // 2, 2, n2 // h, h).transpose(0, 2, 1, 3)
    ).reshape(r2 // 2, 2 * n2)


def add_piggybacks(scheme: HitchhikerScheme, data: np.ndarray,
                   parity: np.ndarray) -> np.ndarray:
    """XOR each group's a-halves into the b-halves of its parity (k + 1
    + j), in place, over (k, n) data and (r, n) RS parity of whole
    units; returns ``parity``."""
    n = data.shape[1]
    if n % scheme.unit:
        raise ValueError(f"{scheme!r} encodes whole units of "
                         f"{scheme.unit} bytes, not {n} columns")
    h = scheme.unit // 2
    d = data.reshape(data.shape[0], -1, 2, h)
    p = parity.reshape(parity.shape[0], -1, 2, h)
    for j, group in enumerate(scheme.groups):
        pb = p[1 + j, :, B]
        for i in group:
            pb ^= d[i, :, A]
    return parity


def unit_plan(scheme: HitchhikerScheme, present: Sequence[int],
              missing: Sequence[int]) -> tuple[list, list, np.ndarray]:
    """A whole-shard repair: (the shards read, which rows of their
    ``to_halves`` the matrix reads, the matrix) for the ``missing``
    shards' both halves (rows 2i, 2i + 1 of the result: shard
    missing[i]), from the half-rows the plan takes of ``present``."""
    wanted = tuple((s, sub) for s in missing for sub in (A, B))
    sources, mat = plan_halves(scheme, frozenset(present) - set(missing),
                               wanted)
    sids = sorted({sid for sid, _ in sources})
    rows = [2 * sids.index(sid) + sub for sid, sub in sources]
    return sids, rows, mat


def rebuild_units(coder, scheme: HitchhikerScheme, plan: tuple,
                  buf: np.ndarray) -> np.ndarray:
    """(len(sids), n) whole units of the plan's shards -> (len(missing),
    n) rebuilt, by ``reconstruct_rows`` of ``coder`` over half-rows: one
    call for every r half-rows out (the most an apply program gives)."""
    _sids, rows, mat = plan
    src = to_halves(buf, scheme.unit)[rows]
    m = scheme.parity_shards
    rec = np.vstack([np.asarray(coder.reconstruct_rows(src, mat[i:i + m]))
                     for i in range(0, len(mat), m)])
    return from_halves(rec, scheme.unit)


def rebuild_rows(coder, present: Sequence[int], missing: Sequence[int],
                 batch_size: int):
    """A whole-shard rebuild (storage/erasure_coding/encoder.
    rebuild_rows): whole units of the shards the half-row plan touches
    (``unit_plan``), in batches of whole units."""
    plan = unit_plan(coder.scheme, present, missing)
    unit = coder.scheme.unit

    def units(rows, out=None):
        rec = rebuild_units(coder, coder.scheme, plan, rows)
        if out is None:
            return rec
        out[:] = rec
        return out
    return plan[0], max(unit, batch_size - batch_size % unit), units


def rebuild_from(coder, shards: Sequence[Optional[bytes]],
                 missing: Sequence[int]) -> list[Optional[bytes]]:
    """``shards`` (whole units each, None where missing) with the
    ``missing`` ones filled in by one ``reconstruct_rows`` of ``coder``:
    the bytes API of every coder of the family."""
    scheme = coder.scheme
    out = [bytes(s) if s is not None else None for s in shards]
    if not missing:
        return out
    present = [i for i, s in enumerate(shards) if s is not None]
    plan = unit_plan(scheme, present, missing)
    buf = np.stack([np.frombuffer(bytes(shards[i]), dtype=np.uint8)
                    for i in plan[0]])
    rec = rebuild_units(coder, scheme, plan, buf)
    for r, i in enumerate(missing):
        out[i] = rec[r].tobytes()
    return out


@register_coder("hhxor")
class HitchhikerCoder(CpuCoder):
    """The family's host coder: CpuCoder's RS(k, r) parity plus the
    piggybacks, over whole units.  ``rebuild_matrix`` stays RS(k, r)'s:
    the matrix of a repair inside the a substripe (plain RS), which is
    what a caller of the scalar API gets; a repair of whole units goes
    through ``plan_halves`` (``reconstruct``, ``unit_plan``)."""

    def __init__(self, scheme: Optional[HitchhikerScheme] = None,
                 use_native: bool = True, workers: int | str = 1):
        if not isinstance(scheme, HitchhikerScheme):
            scheme = DEFAULT_HHXOR_SCHEME
        super().__init__(scheme, use_native=use_native, workers=workers)

    def plan_halves(self, present: Sequence[int], wanted: Sequence[tuple]
                    ) -> tuple[tuple, np.ndarray]:
        return plan_halves(self.scheme, frozenset(present), tuple(wanted))

    def encode(self, shards: Sequence[bytes]) -> list[bytes]:
        k = self.scheme.data_shards
        data = np.stack([np.frombuffer(bytes(shards[i]), dtype=np.uint8)
                         for i in range(k)])
        parity = self.encode_array(data)
        return [bytes(shards[i]) for i in range(k)] + \
            [row.tobytes() for row in parity]

    def encode_array(self, data: np.ndarray) -> np.ndarray:
        data = np.ascontiguousarray(data, dtype=np.uint8)
        return add_piggybacks(self.scheme, data, super().encode_array(data))

    def encode_into(self, data: np.ndarray, out: np.ndarray) -> np.ndarray:
        data = np.ascontiguousarray(data, dtype=np.uint8)
        return add_piggybacks(self.scheme, data,
                              super().encode_into(data, out))

    def reconstruct(self, shards: Sequence[Optional[bytes]]) -> list[bytes]:
        return rebuild_from(self, shards,
                            [i for i, s in enumerate(shards) if s is None])

    def reconstruct_data(self, shards: Sequence[Optional[bytes]]
                         ) -> list[Optional[bytes]]:
        return rebuild_from(self, shards,
                            [i for i in range(self.scheme.data_shards)
                             if shards[i] is None])


@register_coder("hhxor-mt")
class HitchhikerCoderMT(HitchhikerCoder):
    """HitchhikerCoder with workers='auto' (as cpu-mt, lrc-mt)."""

    def __init__(self, scheme: Optional[HitchhikerScheme] = None,
                 use_native: bool = True):
        super().__init__(scheme, use_native=use_native, workers="auto")
