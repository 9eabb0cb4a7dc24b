"""Clay codes (Vajha, Ramkumar, Puranik, Kini, Lobo, Sasidharan, Kumar,
Barg, Ye, Narayanamurthy, Hussain, Nandi, "Clay Codes: Moulding MDS
Codes to Yield an MSR Code", FAST 2018; Ceph's ``clay`` plugin) on the
rs_cpu GF(2^8) substrate: a minimum-storage regenerating VECTOR code.

Layout (``ClayScheme``).  With q = d - k + 1 and t = ceil((k + m) / q),
node i of the q x t grid is (x, y) = (i mod q, i div q): data shards
0 .. k-1 are nodes 0 .. k-1, nu = q t - k - m virtual nodes follow
(never stored, zero), and the m parities are the last column.  Every
unit of a shard (one 1 MiB block) is alpha = q^t sub-chunks, one a
plane z = (z_0 .. z_{t-1}) in Z_q^t, at index sum z_y q^(t-1-y): z_0 is
the most significant digit, so node (x, y)'s repair planes (z_y = x)
are q^y runs of q^(t-1-y) sub-chunks (Ceph's order).

Two codes over the same nodes.  The UNCOUPLED code U: in every plane
the U of all q t nodes is a codeword of the store's systematic
RS(k + nu, m) (``gf256.parity_matrix``).  The COUPLED code C, what is
stored: where z_y = x, C(x, y; z) = U(x, y; z); otherwise node (x, y)
in plane z is paired with its partner (z_y, y) in plane z' = z[y := x]
and C = U + gamma U(partner; z'), gamma one constant, not 0 or 1.  For
one column stacked as A[x, z_0 .. z_{t-1}] the partner of A[x, .., z_y
= a, ..] is A[a, .., z_y = x, ..]: the transpose of axis x with axis
z_y, so C = U + gamma U^T off the diagonal and U = (C + gamma C^T) /
(1 + gamma^2) (``couple`` / ``uncouple``).  A virtual node's C is zero;
its U is not.

  - encode: uncouple the columns of data and virtual nodes (C known),
    RS(k + nu, m) over the planes for the parities' U, couple the
    parity column;
  - repair of one node (x0, y0) from its d = n - 1 helpers' repair
    planes (z_y0 = x0: beta = alpha / q sub-chunks each, d beta in
    all): inside those planes uncouple every other column (a pair of
    column y != y0 stays inside them), solve each plane's RS for the q
    nodes of column y0, and the lost C is U(x0, y0; z) on the repair
    planes and gamma U(x, y0; z) + (C(x, y0; z) + U(x, y0; z)) / gamma
    on plane z[y0 := x], x != x0 (``repair_units``);
  - any m losses: layered decode, planes in order of how many lost
    nodes lie on their diagonal (``decode_units``), the generic path.

A repair job is CANONICAL, so that one program serves every lost
shard: its d rows are the helpers ordered by slot (``repair_plan``:
column y0 first, the lost node at its x' = 0, then the other columns in
order), each row the helper's repair planes in index order; the
program places them into the q t slots (zero for the lost and virtual
nodes), and its output, column y0's planes by x', is put back into
plane order on the host (``place``).  The device runs the encode and
this repair as structured programs on ops/rs_jax.py's primitives
(ops/rs_mesh.py: ``jit_ec_encode_clay_*``, ``jit_ec_apply_clay_*``);
the layered decode runs here, on the host.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import numpy as np

from seaweedfs_tpu.models.coder import ClayScheme, register_coder
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.rs_cpu import CpuCoder, _gf_apply

DEFAULT_CLAY_SCHEME = ClayScheme(10, 4)

# (mat, rows) -> mat x rows over GF(2^8): a host coder's own, threaded
Apply = Callable[[np.ndarray, np.ndarray], np.ndarray]


def _mul(c: int, a: np.ndarray) -> np.ndarray:
    return gf256.MUL_TABLE[c][a]


@functools.lru_cache(maxsize=None)
def constants(scheme: ClayScheme) -> tuple[int, int, int]:
    """(gamma, 1 / gamma, 1 / (1 + gamma^2)) in GF(2^8)."""
    g = scheme.gamma
    return g, gf256.gf_inv(g), gf256.gf_inv(1 ^ gf256.gf_mul(g, g))


def diagonal(q: int, ndim: int, axis: int) -> np.ndarray:
    """Broadcastable bool mask of a column array: True where the node's
    x (axis 0) equals the pair's plane digit (``axis``)."""
    return np.eye(q, dtype=bool).reshape(
        [q if i in (0, axis) else 1 for i in range(ndim)])


def couple(scheme: ClayScheme, u, axis: int, xp=np, mul=_mul):
    """C of one column from its U: ``u`` has the nodes' x on axis 0 and
    the pair's plane digit on ``axis``.  ``xp`` / ``mul``: numpy and a
    table lookup here, jax.numpy and ``rs_jax._gf_mul_static`` in the
    device programs (ops/rs_mesh.py)."""
    g, _gi, _ci = constants(scheme)
    return xp.where(diagonal(scheme.q, u.ndim, axis), u,
                    u ^ mul(g, xp.swapaxes(u, 0, axis)))


def uncouple(scheme: ClayScheme, c, axis: int, xp=np, mul=_mul):
    """U of one column from its C (``couple``'s inverse)."""
    g, _gi, ci = constants(scheme)
    return xp.where(diagonal(scheme.q, c.ndim, axis), c,
                    mul(ci, c ^ mul(g, xp.swapaxes(c, 0, axis))))


@functools.lru_cache(maxsize=None)
def generator(scheme: ClayScheme) -> np.ndarray:
    """(q t, k + nu) uint8: the uncoupled code's generator over the
    nodes, the identity over data and virtual nodes and the RS(k + nu,
    m) parity rows below."""
    kk = scheme.n_nodes - scheme.parity_shards
    gen = np.vstack([np.eye(kk, dtype=np.uint8),
                     gf256.parity_matrix(kk, scheme.parity_shards)])
    gen.setflags(write=False)
    return gen


@functools.lru_cache(maxsize=4096)
def solve_matrix(scheme: ClayScheme, unknown: tuple, known: tuple
                 ) -> np.ndarray:
    """(len(unknown), k + nu) uint8: the U of the ``unknown`` nodes of a
    plane from the U of the k + nu ``known`` ones (MDS: any such set)."""
    gen = generator(scheme)
    mat = gf256.gf_matmul(gen[list(unknown)],
                          gf256.gf_mat_invert(gen[list(known)]))
    mat.setflags(write=False)
    return mat


# ---------------------------------------------------------------- encode

def encode_units(scheme: ClayScheme, data: np.ndarray,
                 apply: Apply = _gf_apply) -> np.ndarray:
    """(k, n) data of whole units -> (m, n) parity."""
    k, m, q, t = (scheme.data_shards, scheme.parity_shards, scheme.q,
                  scheme.t)
    n = data.shape[1]
    if n % scheme.unit:
        raise ValueError(f"{scheme!r} encodes whole units of "
                         f"{scheme.unit} bytes, not {n} columns")
    shape = (n // scheme.unit,) + (q,) * t + (scheme.sub_bytes,)
    kk = scheme.n_nodes - m
    c = np.zeros((kk,) + shape, dtype=np.uint8)
    c[:k] = np.asarray(data, dtype=np.uint8).reshape((k,) + shape)
    c = c.reshape((t - 1, q) + shape)
    u = np.stack([uncouple(scheme, c[y], 2 + y) for y in range(t - 1)])
    up = apply(gf256.parity_matrix(kk, m), u.reshape(kk, -1))
    return couple(scheme, up.reshape((q,) + shape), 1 + t).reshape(m, n)


# ---------------------------------------------------------------- repair

class RepairPlan:
    """The canonical repair of one lost shard (module docstring).
    ``helpers``: the d shards a job's rows hold, in slot order;
    ``slots``: for each of the q t slots, its row, or d (a zero row:
    the lost node and the virtual ones); ``perm``: x -> x' of column y0;
    ``coeff``: the (q, k + nu) plane solve, U of column y0's slots from
    U of the others'; and the runs a helper sends of each unit, as byte
    offsets inside it: ``runs`` of ``run`` bytes from ``first``, every
    ``step``."""

    __slots__ = ("y0", "perm", "helpers", "slots", "coeff", "first",
                 "step", "runs", "run")

    def __init__(self, scheme: ClayScheme, lost: int):
        q, t, d = scheme.q, scheme.t, scheme.helpers
        node = scheme.node_of(lost)
        x0, self.y0 = node % q, node // q
        order = [x0] + [x for x in range(q) if x != x0]
        self.perm = np.argsort(order)
        cols = [self.y0] + [y for y in range(t) if y != self.y0]
        slot_nodes = [cols[j] * q + (order[xp] if j == 0 else xp)
                      for j in range(t) for xp in range(q)]
        sid_of = {scheme.node_of(s): s for s in range(scheme.total_shards)
                  if s != lost}
        self.helpers = [sid_of[nd] for nd in slot_nodes if nd in sid_of]
        self.slots = np.array(
            [self.helpers.index(sid_of[nd]) if nd in sid_of else d
             for nd in slot_nodes], dtype=np.int32)
        assert len(self.helpers) == d, (lost, self.helpers)
        self.coeff = solve_matrix(scheme, tuple(slot_nodes[:q]),
                                  tuple(slot_nodes[q:]))
        self.run = q ** (t - 1 - self.y0) * scheme.sub_bytes
        self.step = q * self.run
        self.runs = q ** self.y0
        self.first = x0 * self.run


@functools.lru_cache(maxsize=None)
def repair_plan(scheme: ClayScheme, lost: int) -> RepairPlan:
    return RepairPlan(scheme, lost)


def repair_mat(scheme: ClayScheme, lost: int) -> np.ndarray:
    """A repair job's matrix: (q, d), every entry the lost shard.  It
    names the job for the structured program, which derives its
    transforms from it (``repair_plan``): no coefficient matrix over the
    job's rows exists.  q rows out, one a quarter of the unit for
    Clay(10,4,13); a job of d rows is a repair for every coder of the
    family (its k-wide jobs are plain matrix applies)."""
    mat = np.full((scheme.q, scheme.helpers), lost, dtype=np.uint8)
    mat.setflags(write=False)
    return mat


def runs_of(scheme: ClayScheme, plan: RepairPlan,
            row: np.ndarray) -> np.ndarray:
    """A helper's job row from a row of its whole units: its repair
    runs, unit after unit."""
    v = row.reshape(-1, plan.runs, plan.step)
    return v[:, :, plan.first:plan.first + plan.run].reshape(-1)


def place(scheme: ClayScheme, plan: RepairPlan,
          canon: np.ndarray) -> np.ndarray:
    """A repair's canonical output (q, units x beta sub-chunks: column
    y0's planes by x') -> (q, same): row r the r-th q-th of each unit,
    in plane order (the job's result; ``units_of`` joins them)."""
    q, t, sub = scheme.q, scheme.t, scheme.sub_bytes
    canon = canon.reshape((q, -1) + (q,) * (t - 1) + (sub,))
    dest = np.empty(canon.shape, dtype=np.uint8)
    src = canon[plan.perm]
    if plan.y0:
        # dest is (z_0, units, z_1 ..): with z_y0 first, z_0 comes
        # before the units
        src = np.swapaxes(src, 1, 2)
    np.moveaxis(dest, 1 + plan.y0 if plan.y0 else 0, 0)[...] = src
    return dest.reshape(q, -1)


def units_of(scheme: ClayScheme, rec: np.ndarray) -> np.ndarray:
    """A repair job's result (q, units x beta sub-chunks) -> the lost
    shard's bytes, unit after unit."""
    q = rec.shape[0]
    return np.ascontiguousarray(
        rec.reshape(q, -1, scheme.repair_columns).transpose(1, 0, 2)
    ).reshape(-1)


def repair_units(scheme: ClayScheme, plan: RepairPlan, rows: np.ndarray,
                 apply: Apply = _gf_apply) -> np.ndarray:
    """The single-node repair on the host: (d, units x beta sub-chunks)
    job rows -> (q, the same) result."""
    q, t, sub = scheme.q, scheme.t, scheme.sub_bytes
    n = rows.shape[1]
    if n % scheme.repair_columns:
        raise ValueError(f"{scheme!r}: a repair job reads whole runs of "
                         f"{scheme.repair_columns} bytes, not {n}")
    shape = (n // scheme.repair_columns,) + (q,) * (t - 1) + (sub,)
    full = np.concatenate([np.asarray(rows, dtype=np.uint8),
                           np.zeros((1, n), dtype=np.uint8)])[plan.slots]
    full = full.reshape((t, q) + shape)
    known = np.stack([uncouple(scheme, full[j], 1 + j)
                      for j in range(1, t)])
    ux = apply(plan.coeff, known.reshape(scheme.n_nodes - q, -1)
               ).reshape((q,) + shape)
    g, gi, _ci = constants(scheme)
    canon = np.empty_like(ux)
    canon[0] = ux[0]
    canon[1:] = _mul(g, ux[1:]) ^ _mul(gi, full[0, 1:] ^ ux[1:])
    return place(scheme, plan, canon)


# ------------------------------------------------------- layered decode

def decode_units(scheme: ClayScheme, rows: dict, lost: Sequence[int],
                 apply: Apply = _gf_apply) -> dict:
    """{shard: (n,) whole units} of every shard but ``lost`` (at most m)
    -> {lost shard: its (n,) bytes}: the layered decode.  Planes go by
    their intersection score (how many lost nodes lie on their
    diagonal), lowest first: a surviving node's U comes from its C and
    its partner's C, or, where the partner is lost, from its partner's
    U in a plane of a lower score; the lost nodes' U by each plane's RS
    solve; their C at the end, from U."""
    q, t, m = scheme.q, scheme.t, scheme.parity_shards
    alpha, sub, nn = scheme.alpha, scheme.sub_bytes, scheme.n_nodes
    lost = sorted(lost)
    if len(lost) > m:
        raise ValueError(f"{scheme!r}: {len(lost)} lost shards cannot "
                         "be decoded")
    n = len(next(iter(rows.values())))
    units = n // scheme.unit
    width = units * sub
    c = np.zeros((nn, alpha, width), dtype=np.uint8)
    for sid, row in rows.items():
        c[scheme.node_of(sid)] = np.asarray(row, dtype=np.uint8).reshape(
            units, alpha, sub).transpose(1, 0, 2).reshape(alpha, width)
    erased = [scheme.node_of(s) for s in lost]
    kk = nn - m
    known = [i for i in range(nn) if i not in erased][:kk]
    mat = solve_matrix(scheme, tuple(erased), tuple(known))
    z = np.arange(alpha)
    digits = np.stack([(z // q ** (t - 1 - y)) % q for y in range(t)], 1)
    score = sum(digits[:, e // q] == e % q for e in erased)
    g, _gi, ci = constants(scheme)
    u = np.zeros_like(c)
    for level in range(len(erased) + 1):
        planes = np.flatnonzero(score == level)
        if not planes.size:
            continue
        for i in range(nn):
            if i in erased:
                continue
            x, y = i % q, i // q
            dig = digits[planes, y]
            on = planes[dig == x]
            u[i, on] = c[i, on]
            off = dig != x
            p_z, p_dig = planes[off], dig[off]
            mate = y * q + p_dig
            mate_z = p_z + (x - p_dig) * q ** (t - 1 - y)
            gone = np.isin(mate, erased)
            a = ~gone
            u[i, p_z[a]] = _mul(ci, c[i, p_z[a]]
                                ^ _mul(g, c[mate[a], mate_z[a]]))
            u[i, p_z[gone]] = c[i, p_z[gone]] \
                ^ _mul(g, u[mate[gone], mate_z[gone]])
        if erased:
            got = apply(mat, u[np.ix_(known, planes)].reshape(kk, -1))
            u[np.ix_(erased, planes)] = got.reshape(
                len(erased), planes.size, width)
    out = {}
    for sid, e in zip(lost, erased):
        x, y = e % q, e // q
        dig = digits[:, y]
        lost_c = u[e] ^ _mul(g, u[y * q + dig,
                                  z + (x - dig) * q ** (t - 1 - y)])
        lost_c[dig == x] = u[e, dig == x]
        out[sid] = np.ascontiguousarray(lost_c.reshape(
            alpha, units, sub).transpose(1, 0, 2)).reshape(-1)
    return out


# ------------------------------------- the bytes API and whole-shard work

def rebuild_from(coder, shards: Sequence[Optional[bytes]],
                 missing: Sequence[int]) -> list[Optional[bytes]]:
    """``shards`` (whole units each, None where lost) with the
    ``missing`` ones filled in: a single lost shard by ONE repair job of
    ``coder`` (its helpers' runs), anything else by the layered decode
    of every lost shard on the host.  The bytes API of every coder of
    the family."""
    scheme = coder.scheme
    out = [bytes(s) if s is not None else None for s in shards]
    if not missing:
        return out
    rows = {i: np.frombuffer(bytes(s), dtype=np.uint8)
            for i, s in enumerate(shards) if s is not None}
    lost = [i for i in range(scheme.total_shards) if i not in rows]
    if len(lost) == 1:
        plan = repair_plan(scheme, lost[0])
        job = np.stack([runs_of(scheme, plan, rows[s])
                        for s in plan.helpers])
        rec = coder.reconstruct_rows(job, repair_mat(scheme, lost[0]))
        out[lost[0]] = units_of(scheme, np.asarray(rec)).tobytes()
        return out
    got = decode_units(scheme, rows, lost)
    for i in missing:
        out[i] = got[i].tobytes()
    return out


def rebuild_rows(coder, present: Sequence[int], missing: Sequence[int],
                 batch_size: int):
    """A whole-shard rebuild (storage/erasure_coding/encoder.
    rebuild_rows): every surviving shard read, in batches of whole
    units, and the ``missing`` ones given by the layered decode on the
    host.  (A repair from d beta-runs, which reads 1 / q of each
    helper, is the degraded read's, ``repair_units``.)"""
    scheme = coder.scheme
    src = sorted(set(present) - set(missing))
    lost = [i for i in range(scheme.total_shards) if i not in src]
    if len(lost) > scheme.parity_shards:
        raise ValueError(f"{scheme!r}: {len(lost)} lost shards cannot be "
                         "decoded")
    apply = getattr(coder, "_apply", _gf_apply)

    def units(rows: np.ndarray, out: Optional[np.ndarray] = None):
        got = decode_units(scheme, dict(zip(src, rows)), lost, apply)
        rec = np.stack([got[i] for i in missing])
        if out is None:
            return rec
        out[:] = rec
        return out
    unit = scheme.unit
    return src, max(unit, batch_size - batch_size % unit), units


@register_coder("clay")
class ClayCoder(CpuCoder):
    """The family's host coder, over whole units.  Its jobs: a k-wide
    ``reconstruct_rows`` is a plain matrix apply (CpuCoder's, whose
    ``rebuild_matrix`` stays RS(k, m)'s and decodes no Clay bytes: the
    warm-up's shape, on zeros); a d-wide one with ``repair_mat`` is the
    single-node repair (``repair_units``)."""

    def __init__(self, scheme: Optional[ClayScheme] = None,
                 use_native: bool = True, workers: int | str = 1):
        if not isinstance(scheme, ClayScheme):
            scheme = DEFAULT_CLAY_SCHEME
        super().__init__(scheme, use_native=use_native, workers=workers)

    def encode(self, shards: Sequence[bytes]) -> list[bytes]:
        k = self.scheme.data_shards
        data = np.stack([np.frombuffer(bytes(shards[i]), dtype=np.uint8)
                         for i in range(k)])
        return [bytes(shards[i]) for i in range(k)] + \
            [row.tobytes() for row in self.encode_array(data)]

    def encode_array(self, data: np.ndarray) -> np.ndarray:
        return encode_units(self.scheme, data, self._apply)

    def encode_into(self, data: np.ndarray, out: np.ndarray) -> np.ndarray:
        out[:] = self.encode_array(data)
        return out

    def reconstruct_rows(self, srcdata: np.ndarray,
                         rebuild_mat: np.ndarray,
                         out: Optional[np.ndarray] = None) -> np.ndarray:
        if srcdata.shape[0] != self.scheme.helpers:
            return super().reconstruct_rows(srcdata, rebuild_mat, out)
        rec = repair_units(self.scheme,
                           repair_plan(self.scheme, int(rebuild_mat[0, 0])),
                           srcdata, self._apply)
        if out is None:
            return rec
        out[:] = rec
        return out

    def reconstruct(self, shards: Sequence[Optional[bytes]]) -> list[bytes]:
        return rebuild_from(self, shards,
                            [i for i, s in enumerate(shards) if s is None])

    def reconstruct_data(self, shards: Sequence[Optional[bytes]]
                         ) -> list[Optional[bytes]]:
        return rebuild_from(self, shards,
                            [i for i in range(self.scheme.data_shards)
                             if shards[i] is None])


@register_coder("clay-mt")
class ClayCoderMT(ClayCoder):
    """ClayCoder with workers='auto' (as cpu-mt, hhxor-mt)."""

    def __init__(self, scheme: Optional[ClayScheme] = None,
                 use_native: bool = True):
        super().__init__(scheme, use_native=use_native, workers="auto")
