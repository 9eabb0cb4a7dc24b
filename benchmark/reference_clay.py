"""Plain reference for Clay codes (Vajha, Ramkumar, Puranik, Kini, Lobo,
Sasidharan, Kumar, Barg, Ye, Narayanamurthy, Hussain, Nandi, "Clay
Codes: Moulding MDS Codes to Yield an MSR Code", USENIX FAST 2018;
Ceph's ``clay`` erasure-code plugin), as a configuration's ``code``
block states it: ``family`` ``"clay"``, ``data_shards`` k,
``parity_shards`` m, ``helpers`` d (= k + m - 1), ``gamma``,
``small_block_bytes`` (the unit cut into sub-chunks) and
``large_block_bytes``.

Independent of ``seaweedfs_tpu``: built on ``reference.py``'s GF(2^8),
RS parity matrix and ``apply_matrix``, in straightforward numpy, plane
by plane and pair by pair.

The equations.  q = d - k + 1, t = ceil((k + m) / q), nu = q t - k - m,
alpha = q^t.  Node i is (x, y) = (i mod q, i div q); data shards 0 ..
k-1 are nodes 0 .. k-1, nodes k .. k+nu-1 are virtual (stored nowhere,
C = 0), parity shards k .. k+m-1 are nodes k+nu .. q t - 1.  A unit of a
shard is alpha sub-chunks, one a plane z = (z_0 .. z_{t-1}) in Z_q^t at
index sum z_y q^(t-1-y).  In every plane the uncoupled U of the q t
nodes is a codeword of systematic RS(k + nu, m) (``parity_matrix``).
The stored C: C(x, y; z) = U(x, y; z) where z_y = x; otherwise, with
the partner (z_y, y) in plane z' = z with z_y replaced by x,
C(x, y; z) = U(x, y; z) + gamma U(z_y, y; z').  Hence U = (C + gamma
C') / (1 + gamma^2) for a pair.  The shard layout is ``reference.py``'s.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.reference import (apply_matrix, encode_rows, generator_matrix,
                                 gf_inv, gf_mul, mat_inv, mat_mul,
                                 parity_matrix)


class Geometry:
    """The numbers of a ``code`` block."""

    def __init__(self, code: dict):
        if code.get("family") != "clay":
            raise ValueError(f"not a Clay code block: {code}")
        self.k, self.m = code["data_shards"], code["parity_shards"]
        self.d = code["helpers"]
        if self.d != self.k + self.m - 1:
            raise ValueError(f"d = {self.d}: only d = k + m - 1 is held")
        self.gamma = code.get("gamma", 2)
        self.unit = code["small_block_bytes"]
        self.q = self.d - self.k + 1
        self.t = -(-(self.k + self.m) // self.q)
        self.nodes = self.q * self.t
        self.nu = self.nodes - self.k - self.m
        self.alpha = self.q ** self.t
        self.sub = self.unit // self.alpha
        if self.sub * self.alpha != self.unit or self.sub % 2:
            raise ValueError(f"{self.alpha} sub-chunks of an even number "
                             f"of bytes do not fill {self.unit} bytes")

    def node(self, sid: int) -> int:
        return sid if sid < self.k else sid + self.nu

    def digits(self, z: int) -> list[int]:
        return [(z // self.q ** (self.t - 1 - y)) % self.q
                for y in range(self.t)]

    def index(self, digits: list[int]) -> int:
        return sum(v * self.q ** (self.t - 1 - y)
                   for y, v in enumerate(digits))

    def partner(self, node: int, z: int) -> tuple[int, int]:
        """(partner node, partner plane) of ``node`` in plane ``z``; the
        node itself where z_y = x."""
        x, y = node % self.q, node // self.q
        dig = self.digits(z)
        a = dig[y]
        dig[y] = x
        return y * self.q + a, self.index(dig)


def _mulc(c: int, a: np.ndarray) -> np.ndarray:
    return apply_matrix([[c]], a[None])[0]


def _uncouple(g: Geometry, cc: np.ndarray, cp: np.ndarray) -> np.ndarray:
    """U of a node from its C and its partner's C."""
    return _mulc(gf_inv(1 ^ gf_mul(g.gamma, g.gamma)),
                 cc ^ _mulc(g.gamma, cp))


def encode(data: np.ndarray, code: dict) -> np.ndarray:
    """(k, n) data of whole units -> (m, n) parity, unit by unit."""
    g = Geometry(code)
    k, m = g.k, g.m
    n = data.shape[1]
    out = np.zeros((m, n), dtype=np.uint8)
    pm = parity_matrix(k + g.nu, m)
    for base in range(0, n, g.unit):
        c = np.zeros((g.nodes, g.alpha, g.sub), dtype=np.uint8)
        c[:k] = data[:, base:base + g.unit].reshape(k, g.alpha, g.sub)
        u = np.zeros_like(c)
        for z in range(g.alpha):
            for node in range(k + g.nu):
                mate, mz = g.partner(node, z)
                u[node, z] = c[node, z] if mate == node \
                    else _uncouple(g, c[node, z], c[mate, mz])
            u[k + g.nu:, z] = apply_matrix(pm, u[:k + g.nu, z])
        for z in range(g.alpha):
            for node in range(k + g.nu, g.nodes):
                mate, mz = g.partner(node, z)
                c[node, z] = u[node, z] if mate == node \
                    else u[node, z] ^ _mulc(g.gamma, u[mate, mz])
        out[:, base:base + g.unit] = c[k + g.nu:].reshape(m, g.unit)
    return out


def repair_planes(code: dict, sid: int) -> list[int]:
    """The planes (sub-chunk indices of a unit) each helper sends for
    the repair of shard ``sid``: those with z_y = x of its node (x, y),
    alpha / q of them, in index order."""
    g = Geometry(code)
    node = g.node(sid)
    x, y = node % g.q, node // g.q
    return [z for z in range(g.alpha) if g.digits(z)[y] == x]


def repair_reads(code: dict, sid: int) -> list[tuple[int, int]]:
    """(helper shard, plane) of every sub-chunk the repair of a whole
    unit of shard ``sid`` reads: the repair planes of every other
    shard, d beta in all."""
    g = Geometry(code)
    planes = repair_planes(code, sid)
    return [(h, z) for h in range(g.k + g.m) if h != sid for z in planes]


def repair(code: dict, sid: int, runs: dict) -> np.ndarray:
    """The unit of lost shard ``sid`` from ``runs``: {helper shard:
    (beta, sub) uint8, its repair planes in index order}.  In the
    repair planes every pair of a column other than the lost node's
    is inside them: uncouple it; solve each plane's RS for the lost
    node's column; the lost C is its U on the repair planes, and on
    plane z' = z with z_y0 = x (x != x0) gamma U(x, y0; z) + (C(x, y0;
    z) + U(x, y0; z)) / gamma."""
    g = Geometry(code)
    node = g.node(sid)
    x0, y0 = node % g.q, node // g.q
    planes = repair_planes(code, sid)
    at = {z: i for i, z in enumerate(planes)}
    c = np.zeros((g.nodes, len(planes), g.sub), dtype=np.uint8)
    for h, r in runs.items():
        if h == sid:
            raise ValueError("the lost shard cannot help")
        c[g.node(h)] = r
    u = np.zeros_like(c)
    col = [y0 * g.q + x for x in range(g.q)]
    rest = [i for i in range(g.nodes) if i not in col]
    for z in planes:
        for i in rest:
            mate, mz = g.partner(i, z)
            u[i, at[z]] = c[i, at[z]] if mate == i \
                else _uncouple(g, c[i, at[z]], c[mate, at[mz]])
    gen = generator_matrix(g.k + g.nu, g.m)
    solve = mat_mul([gen[i] for i in col], mat_inv([gen[i] for i in rest]))
    for z in planes:
        u[col, at[z]] = apply_matrix(solve, u[rest, at[z]])
    out = np.zeros((g.alpha, g.sub), dtype=np.uint8)
    ginv = gf_inv(g.gamma)
    for z in planes:
        out[z] = u[node, at[z]]
        for x in range(g.q):
            if x == x0:
                continue
            dig = g.digits(z)
            dig[y0] = x
            cx, ux = c[col[x], at[z]], u[col[x], at[z]]
            out[g.index(dig)] = _mulc(g.gamma, ux) ^ _mulc(ginv, cx ^ ux)
    return out.reshape(-1)


def iter_rows(dat_path: str, code: dict):
    """The layout's rows of ``dat_path`` in order: (offset in the shard
    files, (k + m, block) uint8 of what each shard holds there)."""
    g = Geometry(code)
    dat_size = os.path.getsize(dat_path)
    shard_off = 0
    with open(dat_path, "rb") as f:
        for row_off, block in encode_rows(dat_size, g.k,
                                          code["large_block_bytes"],
                                          code["small_block_bytes"]):
            data = np.zeros((g.k, block), dtype=np.uint8)
            for i in range(g.k):
                f.seek(row_off + i * block)
                piece = f.read(block)
                data[i, :len(piece)] = np.frombuffer(piece, dtype=np.uint8)
            yield shard_off, np.vstack([data, encode(data, code)])
            shard_off += block


def differing_files(dat_path: str, shard_paths: list[str],
                    code: dict) -> list[int]:
    """Ids of the shard files that do not hold, byte for byte, what the
    reference gives for ``dat_path``: a missing file, a wrong size, a
    row that differs.  Row by row, so a volume of any size fits."""
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
