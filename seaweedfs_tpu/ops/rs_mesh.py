"""Mesh-sharded erasure coder: a BATCH of block-groups per dispatch.

The single-volume coders (rs_cpu / rs_jax) encode one (k, n) block-group
per call, so concurrent ``ec.encode`` pipelines and repair jobs serialize
on the device.  MeshCoder lowers a batch of B independent block-groups —
typically coalesced from several volumes by parallel/batcher.py — into
ONE vmapped dispatch whose leading axis is sharded across a 1-D device
mesh (parallel/mesh.batch_mesh): device d computes lanes
[d*B/n .. (d+1)*B/n) with no collectives, so throughput scales with
device count for batches that fill the mesh.

Two kinds of compiled program PER SCHEME cover every operation, and
carry the scheme in their names (``jit_ec_encode_rs_6_3``,
``jit_ec_apply_rs_10_4``, ``jit_ec_encode_lrc_12_2_2`` in a device trace):

  - encode: the scheme's static parity matrix (plain RS's, or a code
    family's own: LRC's local rows read k / l columns of k, and the
    program's cost follows the set bits) unrolls at trace time into the
    same Horner/XOR graph as rs_jax (bit-identical by construction);
  - rebuild: the coefficient matrix arrives as a TRACED (B, m, w) operand
    (zero rows disabled), so one program serves every survivor pattern in
    the batch — jobs with different loss patterns ride one dispatch.  w
    is the width of the operand, as many rows as the jobs' matrices READ:
    k for plain RS; a family that repairs from a local group (LRC) has an
    apply program at the group's width too, and a piggybacked one
    (Hitchhiker-XOR, ops/hitchhiker.py) reads HALF-ROWS, at widths past
    k: k + |group| for a whole lost block, 2k for anything (``apply_widths``);
    a dispatch rides the narrowest that holds what it reads.

A regenerating code (Clay, ops/clay.py) has no matrix over its rows:
its encode is a STRUCTURED program (the pair transform of the data
columns, the static RS over the planes, the pair transform of the
parity column: ``jit_ec_encode_clay_*``), and its repair a second
structured program under the apply name (``jit_ec_apply_clay_*``) that
reads d helpers' runs of a lost unit, each lane's lost shard named by
traced slots and plane-solve coefficients (``batch_repair_fn``).

A MeshCoder is of ONE scheme; the batch scheduler keeps one per scheme
it has seen, all on the same device mesh.

Batches are zero-padded on the leading axis to a power-of-two multiple
of the device count (NamedSharding needs even division, and a bounded
set of B keeps the set of compiled programs bounded); pad lanes are
discarded on the host.
Output is bit-identical to CpuCoder in all modes — GF(256) has no
rounding to disagree about, and the tests hold it to that.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, ClayScheme, Encoded,
                                        ErasureCoder, RSScheme,
                                        code_spec_name, host_coder,
                                        register_coder, vector_ops)
from seaweedfs_tpu.ops import clay, gf256
from seaweedfs_tpu.ops.rs_jax import (_apply_matrix_words, _gf_mul_dynamic,
                                      _gf_mul_static, _mat_to_tuple)
from seaweedfs_tpu.parallel import mesh as mesh_mod
from seaweedfs_tpu.utils import tracing

# a dispatch's four stages on the host, in order (ec.mesh.<stage>): the
# first two are a ``*_batch_begin``, the last two its ``.result()``
STAGES = ("pad", "launch", "fetch", "unpack")


def _named(fn, stem: str, scheme: RSScheme):
    """Give a program its name in a device trace: ``jit_<stem>_<spec>``,
    the scheme's spec string with underscores (``jit_ec_encode_rs_6_3``,
    ``jit_ec_apply_lrc_12_2_2``).  The benchmark's metrics select by the
    prefixes ``jit_ec_encode`` / ``jit_ec_apply``; the scheme after them
    tells two codes' programs apart (the name is part of the
    compile-cache key too)."""
    fn.__name__ = fn.__qualname__ = \
        f"{stem}_{code_spec_name(scheme).replace('-', '_')}"
    return fn


def apply_widths(scheme: RSScheme) -> tuple[int, ...]:
    """The operand widths (rows a lane reads) the scheme has an apply
    program for, ascending: k, and before it k / l where the family
    repairs a lost shard from the others of its local group (k / l - 1
    data shards and the group's parity); after it, for a piggybacked
    family, k + |group| (a whole lost block of a group's shard: the
    piggyback plan) and 2k (every half-row a plan can read)."""
    k = scheme.data_shards
    if isinstance(scheme, ClayScheme):
        # its repair is a program of its own (batch_repair_fn)
        return (k,)
    if scheme.substripes > 1:
        return tuple(sorted({k, scheme.max_sources}
                            | {k + len(g) for g in scheme.groups}))
    group = getattr(scheme, "group_size", k)
    return (group, k) if group < k else (k,)


@functools.lru_cache(maxsize=None)
def batch_encode_fn(scheme: RSScheme, mesh: Mesh):
    """jit over the mesh: (B, k, nw) uint32 sharded P('batch', None, None)
    -> (B, m, nw) parity with matching sharding.  The static parity
    matrix of the scheme's own family (its host coder's), no
    collectives; a piggybacked family adds each group's a-halves into
    the b-halves of its parity (nw whole units); a regenerating one has
    a structured program of its own (``_clay_encode``)."""
    s3 = mesh_mod.batch_spec(mesh)
    if isinstance(scheme, ClayScheme):
        return jax.jit(jax.vmap(_named(_clay_encode(scheme), "ec_encode",
                                       scheme)),
                       in_shardings=(s3,), out_shardings=s3)
    mat = _mat_to_tuple(host_coder(scheme, threaded=False)._parity)
    groups = scheme.groups if scheme.substripes > 1 else ()

    def ec_encode(words):
        parity = _apply_matrix_words(words, mat)
        if not groups:
            return parity
        half = scheme.unit // 8                     # words a half-unit
        d = words.reshape(words.shape[0], -1, 2, half)
        p = parity.reshape(parity.shape[0], -1, 2, half)
        rows = [p[0]]
        for j, group in enumerate(groups):
            pig = d[group[0], :, 0]
            for i in group[1:]:
                pig = pig ^ d[i, :, 0]
            rows.append(p[1 + j].at[:, 1].set(p[1 + j, :, 1] ^ pig))
        return jnp.stack(rows).reshape(parity.shape)

    return jax.jit(jax.vmap(_named(ec_encode, "ec_encode", scheme)),
                   in_shardings=(s3,), out_shardings=s3)


def _clay_encode(scheme: ClayScheme):
    """One lane's Clay encode, (k, nw) words of whole units -> (m, nw):
    the virtual nodes' zeros beside the data, the pair transform's
    inverse on each of the t - 1 data columns (transposes and static
    constants), RS(k + nu, m) over the planes by the static-matrix
    primitive, the pair transform on the parity column.  No matrix over
    sub-chunks."""
    k, m, q, t = (scheme.data_shards, scheme.parity_shards, scheme.q,
                  scheme.t)
    kk = scheme.n_nodes - m
    mat = _mat_to_tuple(gf256.parity_matrix(kk, m))
    sub_words = scheme.sub_bytes // 4

    def ec_encode(words):
        shape = (words.shape[1] * 4 // scheme.unit,) + (q,) * t \
            + (sub_words,)
        c = words.reshape((k,) + shape)
        c = jnp.concatenate([c, jnp.zeros((kk - k,) + shape, c.dtype)])
        c = c.reshape((t - 1, q) + shape)
        u = jnp.stack([clay.uncouple(scheme, c[y], 2 + y, jnp,
                                     _gf_mul_static) for y in range(t - 1)])
        up = _apply_matrix_words(u.reshape(kk, -1), mat)
        return clay.couple(scheme, up.reshape((q,) + shape), 1 + t, jnp,
                           _gf_mul_static).reshape(m, -1)
    return ec_encode


@functools.lru_cache(maxsize=None)
def batch_repair_fn(scheme: ClayScheme, mesh: Mesh):
    """jit over the mesh: a regenerating code's single-node repair —
    (B, d, nw) words of the helpers' runs (canonical rows,
    ops/clay.repair_plan) x (B, q t) int32 slots x (B, q, k + nu)
    plane-solve coefficients -> (B, q, nw), column y0's planes by x'
    (ops/clay.place puts them in plane order).  Per lane: the rows into
    their slots (a zero row for the lost and virtual nodes), the pair
    transform's inverse on the t - 1 other columns, the RS solve of
    each plane for column y0 (traced coefficients, as the apply
    program's), and the lost planes from it with static constants.
    Named as the apply program (``jit_ec_apply_clay_*``); no matrix over
    sub-chunks."""
    q, t = scheme.q, scheme.t
    kk = scheme.n_nodes - q
    sub_words = scheme.sub_bytes // 4
    g, gi, _ci = clay.constants(scheme)

    def ec_apply(words, slots, coeff):
        nw = words.shape[1]
        shape = (nw // (scheme.beta * sub_words),) + (q,) * (t - 1) \
            + (sub_words,)
        full = jnp.concatenate([words, jnp.zeros((1, nw), words.dtype)])
        full = full[slots].reshape((t, q) + shape)
        known = jnp.stack([clay.uncouple(scheme, full[j], 1 + j, jnp,
                                         _gf_mul_static)
                           for j in range(1, t)]).reshape(kk, -1)
        ux = []
        for i in range(q):
            acc = jnp.zeros_like(known[0])
            for j in range(kk):
                acc = acc ^ _gf_mul_dynamic(coeff[i, j], known[j])
            ux.append(acc.reshape(shape))
        out = [ux[0]] + [_gf_mul_static(g, ux[x])
                         ^ _gf_mul_static(gi, full[0, x] ^ ux[x])
                         for x in range(1, q)]
        return jnp.stack(out).reshape(q, nw)

    s3 = mesh_mod.batch_spec(mesh)
    s2 = mesh_mod.batch_spec(mesh, 2)
    return jax.jit(jax.vmap(_named(ec_apply, "ec_apply", scheme)),
                   in_shardings=(s3, s2, s3), out_shardings=s3)


def batch_apply_fn(scheme: RSScheme, mesh: Mesh,
                   width: Optional[int] = None):
    """jit over the mesh: per-lane GF matrix application with TRACED
    coefficients — (B, width, nw) words x (B, m, width) coeff -> (B, m,
    nw), m the scheme's parity count (the most rows a rebuild can ask
    for), ``width`` one of ``apply_widths(scheme)`` (k when not given).
    One jitted function per (scheme, mesh, width)."""
    return _apply_fn(scheme, mesh, width or scheme.data_shards)


@functools.lru_cache(maxsize=None)
def _apply_fn(scheme: RSScheme, mesh: Mesh, width: int):
    """batch_apply_fn's program of one width.  Zero coefficient
    rows yield zero output rows, so one compiled program serves every
    (survivor pattern, missing set) mix in a batch.  A scheme's programs
    of two widths share their name: the operand's shape tells them apart
    (the stages' ``rows`` attribute, ``MeshCoder.programs``)."""
    assert width in apply_widths(scheme), (width, scheme)
    n_out = scheme.parity_shards

    def ec_apply(words, coeff):
        outs = []
        for i in range(n_out):
            acc = jnp.zeros_like(words[0])
            for j in range(words.shape[0]):
                acc = acc ^ _gf_mul_dynamic(coeff[i, j], words[j])
            outs.append(acc)
        return jnp.stack(outs)

    s3 = mesh_mod.batch_spec(mesh)
    return jax.jit(jax.vmap(_named(ec_apply, "ec_apply", scheme)),
                   in_shardings=(s3, s3), out_shardings=s3)


class Dispatched:
    """A batch on the device: what ``MeshCoder.encode_batch_begin`` /
    ``rebuild_batch_begin`` hand back once the program is enqueued.
    ``result()`` waits for it, brings the output to the host
    (``ec.mesh.fetch``) and cuts it to the jobs (``ec.mesh.unpack``).
    The two-step form of ``ErasureCoder.encode_begin`` one layer down:
    the batch scheduler launches the next dispatch between the steps."""

    __slots__ = ("_coder", "_out", "_rows", "_unpack")

    def __init__(self, coder: "MeshCoder", out, rows: int, unpack):
        self._coder, self._out, self._rows = coder, out, rows
        self._unpack = unpack

    def result(self):
        coder = self._coder
        with tracing.stage("ec.mesh.fetch") as st:
            coder._note(st, self._rows)
            # waits for the device, then device -> host
            got = np.asarray(jax.device_get(self._out))
        coder._staged("fetch", st)
        self._out = None                 # the device's copy may go
        with tracing.stage("ec.mesh.unpack") as st:
            coder._note(st, self._rows)
            res = self._unpack(got)
        coder._staged("unpack", st)
        return res


@register_coder("mesh")
class MeshCoder(ErasureCoder):
    """ErasureCoder whose unit of dispatch is a batch of block-groups
    sharded across a 1-D device mesh.  The scalar ErasureCoder API is a
    batch of one (bit-identical, just not faster); the batch API is what
    parallel/batcher.py feeds, in its two-step form (``*_batch_begin``
    launches, ``.result()`` collects: the scheduler launches the next
    dispatch between the two); the one-step forms are ``begin`` +
    ``result``, the same code."""

    def __init__(self, scheme: RSScheme = DEFAULT_SCHEME,
                 n_devices: int | None = None, mesh: Optional[Mesh] = None):
        super().__init__(scheme)
        self.spec = code_spec_name(scheme)  # the stages' ``spec`` attribute
        self.mesh = mesh if mesh is not None else mesh_mod.batch_mesh(n_devices)
        # the scheme's own host coder, for rebuild matrices and plans
        # only (pure numpy); a family that plans its sources (LRC) hands
        # its plans through
        self._host = host_coder(scheme, threaded=False)
        if hasattr(self._host, "plan_rebuild"):
            self.plan_rebuild = self._host.plan_rebuild
        self.apply_widths = apply_widths(scheme)
        # (B, columns) of the k-wide apply whose repair programs are
        # compiled beside it (_prepare_repairs)
        self._prepared: set[tuple] = set()
        # distinct (kind, padded operand shape) THIS coder dispatched, all
        # of its one scheme; the batcher's stats() reports the count over
        # its coders as programs_compiled, and per scheme under by_spec.
        # Not a count of compiles: the jitted functions are cached per
        # (scheme, mesh), so a shape another MeshCoder of the process
        # ran first (a warm-up) is counted here though nothing compiled.
        # Real compiles: mesh_mod.CompileWatch (backend_compiles).
        self.programs: set[tuple] = set()
        # seconds and entries per host stage of a dispatch.  One writer
        # a stage under the batch scheduler (its launching thread is the
        # only caller of the begins, its collector of the results); a
        # coder shared by several threads can lose adds
        self.stage_s = dict.fromkeys(STAGES, 0.0)
        self.stage_n = dict.fromkeys(STAGES, 0)
        self.compile_watch = mesh_mod.install_tracing()
        # {distinct devices holding a shard of a dispatch's output:
        # dispatches} — on an n-device mesh every dispatch should land
        # under key n; anything under a smaller key ran on fewer chips
        self.output_spread: dict[int, int] = {}

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def device_report(self) -> dict:
        return mesh_mod.device_report(list(self.mesh.devices.flat))

    def _staged(self, key: str, st: tracing.stage) -> None:
        self.stage_s[key] += st.elapsed
        self.stage_n[key] += 1

    def _note(self, st: tracing.stage, rows: int) -> None:
        """A stage's attributes: the scheme and the operand's width."""
        st.annotate("spec", self.spec)
        st.annotate("rows", rows)

    def _launch(self, kind: str, fn, operands: tuple, unpack
                ) -> "Dispatched":
        """Dispatch, note the program's shape and where the output's
        shards lived; what comes back is on the device."""
        self.programs.add((kind,) + operands[0].shape)
        rows = operands[0].shape[1]
        with tracing.stage("ec.mesh.launch") as st:
            self._note(st, rows)
            # host -> device copies and the enqueue; returns before the
            # device is done
            out = fn(*operands)
            spread = len({s.device for s in out.addressable_shards})
            self.output_spread[spread] = \
                self.output_spread.get(spread, 0) + 1
        self._staged("launch", st)
        return Dispatched(self, out, rows, unpack)

    # ---- batch API (the batcher's entry points) ----

    def _pad_batch(self, words: np.ndarray) -> np.ndarray:
        b = words.shape[0]
        pb = self.n_devices
        while pb < b:
            pb *= 2
        if pb == b:
            return words
        pad = np.zeros((pb - b,) + words.shape[1:], dtype=words.dtype)
        return np.concatenate([words, pad], axis=0)

    def encode_batch(self, batch: np.ndarray) -> np.ndarray:
        """(B, k, n) uint8 -> (B, m, n) uint8 parity, one sharded
        dispatch.  n must be a multiple of 4 (uint32 lanes)."""
        return self._encode_begin(batch).result()

    def encode_batch_begin(self, batch: np.ndarray):
        """``encode_batch`` in two steps: pad and launch now, fetch and
        unpack in ``.result()`` (``Dispatched``).  A one-step form that
        was REPLACED, by a subclass or by a fault planted on the class
        (benchmark/tests/faulty_volume.py), is what this coder does: the
        begin then runs it whole (``Encoded``: done when handed back)."""
        if getattr(self.encode_batch, "__func__", None) is not _ENCODE_BATCH:
            return Encoded(self.encode_batch(batch))
        return self._encode_begin(batch)

    def _encode_begin(self, batch: np.ndarray) -> Dispatched:
        B, k, n = batch.shape
        assert k == self.scheme.data_shards, (k, self.scheme)
        assert n % 4 == 0, n
        if self.scheme.substripes > 1 and n % self.scheme.unit:
            raise ValueError(f"{self.scheme!r} encodes whole units of "
                             f"{self.scheme.unit} bytes, not {n} columns")
        with tracing.stage("ec.mesh.pad") as st:
            self._note(st, k)
            words = self._pad_batch(
                np.ascontiguousarray(batch).view(np.uint32))
            fn = batch_encode_fn(self.scheme, self.mesh)
        self._staged("pad", st)
        return self._launch(
            "encode", fn, (words,),
            lambda out: np.ascontiguousarray(out[:B]).view(np.uint8))

    def _fit_width(self, srcdata: np.ndarray, coeff: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Bring a dispatch's operand (B, w, n) and coefficients (B, m, w)
        to the narrowest of the scheme's apply widths that holds what the
        jobs READ.  As it comes where w is that width already (every job
        of the served path: plain RS at k, a local repair at k / l);
        operand rows whose coefficients are zero in every job are dropped
        where that reaches a narrower program (a local repair written
        over k rows), and zero rows added where w is between widths."""
        w = srcdata.shape[1]
        widths = self.apply_widths
        if w == widths[0]:
            return srcdata, coeff
        read = np.flatnonzero(coeff.any(axis=(0, 1)))
        width = next(x for x in widths if x >= len(read))  # w <= widths[-1]
        if width == w:
            return srcdata, coeff
        if len(read) < w:
            srcdata, coeff = srcdata[:, read], coeff[:, :, read]
        fit = np.zeros((srcdata.shape[0], width, srcdata.shape[2]),
                       dtype=srcdata.dtype)
        fit[:, :srcdata.shape[1]] = srcdata
        fit_coeff = np.zeros(coeff.shape[:2] + (width,), dtype=coeff.dtype)
        fit_coeff[:, :, :coeff.shape[2]] = coeff
        return fit, fit_coeff

    def rebuild_batch(self, srcdata: np.ndarray,
                      mats: Sequence[np.ndarray]) -> list[np.ndarray]:
        """srcdata: (B, w, n) uint8 — per job, the rows its matrix reads
        (for plain RS the first k present shards; w <= k).  mats[i]:
        (r_i, w) uint8 rebuild matrix (from rebuild_matrix() or a plan;
        r_i <= parity_shards).  Returns a list of (r_i, n) uint8
        recovered rows, one per job, in one sharded dispatch even when
        jobs lost different shards."""
        return self._rebuild_begin(srcdata, mats).result()

    def rebuild_batch_begin(self, srcdata: np.ndarray,
                            mats: Sequence[np.ndarray]):
        """``rebuild_batch`` in two steps, as ``encode_batch_begin``."""
        if getattr(self.rebuild_batch, "__func__", None) \
                is not _REBUILD_BATCH:
            return Encoded(self.rebuild_batch(srcdata, mats))
        return self._rebuild_begin(srcdata, mats)

    def _rebuild_begin(self, srcdata: np.ndarray,
                       mats: Sequence[np.ndarray]) -> Dispatched:
        B, w, n = srcdata.shape
        if isinstance(self.scheme, ClayScheme) and w == self.scheme.helpers:
            return self._repair_begin(srcdata, mats)
        assert w <= self.apply_widths[-1] and n % 4 == 0
        assert len(mats) == B
        m = self.scheme.parity_shards
        with tracing.stage("ec.mesh.pad") as st:
            coeff = np.zeros((B, m, w), dtype=np.uint32)
            for i, mt in enumerate(mats):
                mt = np.asarray(mt)
                assert mt.shape == (mt.shape[0], w) \
                    and mt.shape[0] <= m, mt.shape
                coeff[i, :mt.shape[0]] = mt.astype(np.uint32)
            srcdata, coeff = self._fit_width(srcdata, coeff)
            self._note(st, srcdata.shape[1])
            words = self._pad_batch(
                np.ascontiguousarray(srcdata).view(np.uint32))
            coeff = self._pad_batch(coeff)
            fn = batch_apply_fn(self.scheme, self.mesh, srcdata.shape[1])
            if srcdata.shape[1] == self.scheme.data_shards:
                self._prepare_repairs(words.shape[0], n)
        self._staged("pad", st)
        n_rows = [np.asarray(mt).shape[0] for mt in mats]

        def unpack(out: np.ndarray) -> list[np.ndarray]:   # (pb, m, nw)
            out8 = np.ascontiguousarray(out[:B]).view(np.uint8)  # (B,m,n)
            return [np.ascontiguousarray(out8[i, :r])
                    for i, r in enumerate(n_rows)]
        return self._launch("apply", fn, (words, coeff), unpack)

    def _repair_begin(self, srcdata: np.ndarray,
                      mats: Sequence[np.ndarray]) -> Dispatched:
        """A regenerating family's repair jobs (ops/clay.repair_mat),
        one program whatever shard each lane lost: its slots and plane
        solve ride as operands, and the output is put in plane order
        when unpacked."""
        B, w, _n = srcdata.shape
        plans = [clay.repair_plan(self.scheme, int(np.asarray(mt)[0, 0]))
                 for mt in mats]
        with tracing.stage("ec.mesh.pad") as st:
            self._note(st, w)
            words = self._pad_batch(
                np.ascontiguousarray(srcdata).view(np.uint32))
            slots = self._pad_batch(np.stack([p.slots for p in plans]))
            coeff = self._pad_batch(
                np.stack([p.coeff for p in plans]).astype(np.uint32))
            fn = batch_repair_fn(self.scheme, self.mesh)
        self._staged("pad", st)

        def unpack(out: np.ndarray) -> list[np.ndarray]:   # (pb, q, nw)
            out8 = np.asarray(out[:B]).view(np.uint8)
            return [clay.place(self.scheme, p, out8[i])
                    for i, p in enumerate(plans)]
        return self._launch("apply", fn, (words, slots, coeff), unpack)

    def _prepare_repairs(self, pb: int, n: int) -> None:
        """A vector family's repair of n columns of shard bytes (whole
        units) is a job of n / unit x ``repair_columns`` columns at its
        widths past k (half-rows at the piggyback widths, or a
        regenerating code's d helpers' runs): the first k-wide apply of
        whole units at a padded batch ``pb`` (a warm-up's: a served
        read's k-wide jobs are of one piece) compiles those programs
        too, on zeros, at every batch up to the same columns."""
        scheme = self.scheme
        unit = getattr(scheme, "unit", 0)
        if not unit or n % unit or (pb, n) in self._prepared:
            return
        self._prepared.add((pb, n))
        k, m = scheme.data_shards, scheme.parity_shards
        nw = n // unit * scheme.repair_columns // 4
        b = self.n_devices
        while b * nw <= pb * n // 4:
            if isinstance(scheme, ClayScheme):
                jax.block_until_ready(batch_repair_fn(scheme, self.mesh)(
                    np.zeros((b, scheme.helpers, nw), dtype=np.uint32),
                    np.zeros((b, scheme.n_nodes), dtype=np.int32),
                    np.zeros((b, scheme.q, scheme.n_nodes - scheme.q),
                             dtype=np.uint32)))
            for width in self.apply_widths:
                if k < width < scheme.max_sources:
                    fn = batch_apply_fn(scheme, self.mesh, width)
                    jax.block_until_ready(fn(
                        np.zeros((b, width, nw), dtype=np.uint32),
                        np.zeros((b, m, width), dtype=np.uint32)))
            b *= 2

    # ---- scalar ErasureCoder API (batch of one) ----

    def encode_array(self, data: np.ndarray) -> np.ndarray:
        assert data.shape[1] % 4 == 0
        return self.encode_batch(
            np.ascontiguousarray(data, dtype=np.uint8)[None])[0]

    def encode(self, shards: Sequence[bytes]) -> list[bytes]:
        k = self.scheme.data_shards
        n = len(shards[0])
        pad = (-n) % 4
        data = np.zeros((k, n + pad), dtype=np.uint8)
        for i in range(k):
            data[i, :n] = np.frombuffer(bytes(shards[i]), dtype=np.uint8)
        parity = self.encode_batch(data[None])[0]
        return [bytes(shards[i]) for i in range(k)] + \
            [parity[i, :n].tobytes() for i in range(self.scheme.parity_shards)]

    def rebuild_matrix(self, present: Sequence[int],
                       missing: Sequence[int]) -> np.ndarray:
        return self._host.rebuild_matrix(present, missing)

    def job_rows(self, n: int, rows: Optional[int] = None) -> np.ndarray:
        """A zeroed (rows, n rounded up to the uint32 lanes) operand for
        reconstruct_rows: a caller fills rows[:, :n] in place.  ``rows``
        is as many as the job's matrix reads (a plan's sources); k when
        not given."""
        return np.zeros((rows or self.scheme.data_shards, n + (-n) % 4),
                        dtype=np.uint8)

    def reconstruct_rows(self, srcdata: np.ndarray,
                         rebuild_mat: np.ndarray,
                         out: Optional[np.ndarray] = None) -> np.ndarray:
        rec = self.rebuild_batch(
            np.ascontiguousarray(srcdata, dtype=np.uint8)[None],
            [rebuild_mat])[0]
        if out is not None:
            out[:] = rec
            return out
        return rec

    def _rebuild_from(self, shards: Sequence[Optional[bytes]],
                      missing: Sequence[int]) -> list[Optional[bytes]]:
        """``shards`` with the ``missing`` ones filled in, in one
        dispatch: from the sources the family's plan names where it
        plans (LRC: the first k present can be rank-deficient), else
        from the first k present shards, whose rebuild matrix expresses
        data AND parity losses directly; a vector family's as its
        plans say (ops/hitchhiker.py, ops/clay.py)."""
        if self.scheme.substripes > 1:
            return vector_ops(self.scheme).rebuild_from(self, shards,
                                                        missing)
        k, total = self.scheme.data_shards, self.scheme.total_shards
        present = [i for i in range(total) if shards[i] is not None]
        out = [bytes(s) if s is not None else None for s in shards]
        if not missing:
            return out
        plan = getattr(self, "plan_rebuild", None)
        if plan is not None:
            sids, mat = plan(present, missing)
        elif len(present) < k:
            raise ValueError(f"too few shards: {len(present)} < {k}")
        else:
            sids, mat = present[:k], self.rebuild_matrix(present, missing)
        n = len(shards[present[0]])
        src = self.job_rows(n, len(sids))
        for r, i in enumerate(sids):
            src[r, :n] = np.frombuffer(bytes(shards[i]), dtype=np.uint8)
        rec = self.rebuild_batch(src[None], [mat])[0]
        for r, i in enumerate(missing):
            out[i] = rec[r, :n].tobytes()
        return out

    def reconstruct(self, shards: Sequence[Optional[bytes]]) -> list[bytes]:
        return self._rebuild_from(
            shards, [i for i, s in enumerate(shards) if s is None])

    def reconstruct_data(self, shards: Sequence[Optional[bytes]]
                         ) -> list[Optional[bytes]]:
        return self._rebuild_from(
            shards, [i for i in range(self.scheme.data_shards)
                     if shards[i] is None])


# the one-step forms as defined here: a coder whose own differ from them
# had them replaced (``encode_batch_begin``)
_ENCODE_BATCH = MeshCoder.encode_batch
_REBUILD_BATCH = MeshCoder.rebuild_batch
