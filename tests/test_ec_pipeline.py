"""Staged EC pipeline: bit-identity, crash-safety, decoder tails.

The contract under test (parallel/streaming.py + encoder/decoder):
  * pipelined and serial paths produce byte-identical shards — both walk
    the single layout.iter_encode_batches plan;
  * an interrupted pipeline (any stage) leaves NO .ecNN / .dat under a
    final name and no .tmp litter (AtomicFileGroup);
  * decoder.write_dat_file reassembles every tail shape, including the
    exactly-k*large_block size the old `>=` row loop misread.

Blocks are scaled down (LB=640/SB=160 vs 1GB/1MB) so the full two-tier
row structure — multiple large rows, small rows, partial tail — fits in
kilobytes; layout.py keeps the same strict-> split at any scale.
"""

import errno
import glob
import os
import threading

import numpy as np
import pytest

from benchmark import reference
from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, Encoded,
                                        ErasureCoder, LrcScheme, RSScheme,
                                        host_coder, make_coder,
                                        scheme_to_dict)
from seaweedfs_tpu.native import rs_native
from seaweedfs_tpu.ops import rs_cpu
from seaweedfs_tpu.parallel import streaming
from seaweedfs_tpu.storage.erasure_coding import decoder as ecdec
from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
from seaweedfs_tpu.storage.erasure_coding import layout

LB, SB = 640, 160
K = layout.DATA_SHARDS_COUNT
TOTAL = layout.TOTAL_SHARDS_COUNT


def _make_dat(base: str, size: int, seed: int = 0) -> bytes:
    dat = np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    with open(base + ".dat", "wb") as f:
        f.write(dat)
    return dat


def _shards(base: str) -> list[bytes]:
    return [open(base + layout.shard_ext(i), "rb").read()
            for i in range(TOTAL)]


def _leftovers(d) -> list[str]:
    return sorted(os.path.basename(p) for p in glob.glob(str(d) + "/*")
                  if not p.endswith((".dat", ".keep")))


# ---- bit-identity: serial vs pipelined, all coder/reader variants ----

SIZES = [
    1,                          # single byte
    SB * K - 7,                 # partial small row, non-multiple of k*SB
    2 * LB * K,                 # exactly k*large_block (the `>=` bug size)
    2 * LB * K + 3,
    2 * LB * K + 3 * SB * K + 77,   # large rows: a batch's rows LB apart
]


def _library(monkeypatch, library: str) -> None:
    """``native``: the stages' one call a batch (skipped where the
    library did not build); ``absent``: the library made unavailable, a
    call a row."""
    if library == "absent":
        monkeypatch.setattr(rs_native, "available", lambda: False)
    elif not rs_native.available():
        pytest.skip("no native library on this machine")


@pytest.mark.parametrize("size,library", [
    pytest.param(s, lib, id=str(s) if lib == "native" else f"{s}-{lib}")
    for lib in ("native", "absent") for s in SIZES])
def test_pipelined_matches_serial(tmp_path, monkeypatch, size, library):
    _library(monkeypatch, library)
    sbase, pbase = str(tmp_path / "s"), str(tmp_path / "p")
    for b in (sbase, pbase):
        _make_dat(b, size, seed=size)
    ecenc.write_ec_files(sbase, make_coder("cpu"), LB, SB, batch_size=SB)
    ecenc.write_ec_files(pbase, make_coder("cpu-mt"), LB, SB,
                         batch_size=SB, pipelined=True)
    assert _shards(sbase) == _shards(pbase)


def test_pipelined_multi_reader_matches(tmp_path):
    sbase, pbase = str(tmp_path / "s"), str(tmp_path / "p")
    size = 3 * LB * K + 2 * SB * K + 11
    for b in (sbase, pbase):
        _make_dat(b, size, seed=2)
    ecenc.write_ec_files(sbase, make_coder("cpu"), LB, SB, batch_size=SB)
    # readers=2 interleave by sequence number; the coder stage reorders
    ecenc.write_ec_files(pbase, make_coder("cpu"), LB, SB, batch_size=SB,
                         pipelined=True, readers=2)
    assert _shards(sbase) == _shards(pbase)


def test_pipelined_odd_batch_snaps_to_block(tmp_path):
    # batch_size not dividing the block must snap to one-batch-per-block,
    # never split a row unevenly (layout.iter_encode_batches contract)
    sbase, pbase = str(tmp_path / "s"), str(tmp_path / "p")
    size = LB * K + SB * K + 5
    for b in (sbase, pbase):
        _make_dat(b, size, seed=3)
    ecenc.write_ec_files(sbase, make_coder("cpu"), LB, SB, batch_size=LB)
    ecenc.write_ec_files(pbase, make_coder("cpu"), LB, SB, batch_size=77,
                         pipelined=True)
    assert _shards(sbase) == _shards(pbase)


@pytest.mark.parametrize("library", ["native", "absent"])
@pytest.mark.parametrize("scheme", [DEFAULT_SCHEME, RSScheme(6, 3),
                                    LrcScheme(12, 2, 2)],
                         ids=["rs10-4", "rs6-3", "lrc12-2-2"])
def test_io_calls_count_a_batchs_moves_and_the_files_are_the_references(
        tmp_path, monkeypatch, scheme, library):
    """Every geometry's shard files are the benchmark reference's bytes,
    large-block rows included, and ``io_calls`` counts the calls that
    moved them: the reader's one and each writer's one a batch with the
    library, a row each (k read, k + m written) without it."""
    _library(monkeypatch, library)
    k, total = scheme.data_shards, scheme.total_shards
    base = str(tmp_path / "v")
    _make_dat(base, 2 * LB * k + 3 * SB * k + 77, seed=k)
    stats: dict = {}
    streaming.pipelined_encode_file(base, host_coder(scheme, False), LB, SB,
                                    batch_size=SB, stats=stats)
    code = {**scheme_to_dict(scheme), "parity_shards": total - k,
            "large_block_bytes": LB, "small_block_bytes": SB}
    assert reference.differing_files(
        base + ".dat", [base + layout.shard_ext(i) for i in range(total)],
        code) == []
    per_batch = 3 if library == "native" else 2 * k + (total - k)
    assert stats["batches"] == 2 * LB // SB + 4     # 2 large rows, 4 small
    assert stats["io_calls"] == per_batch * stats["batches"]


# ---- crash-safety: no truncated shard ever visible ----

class _BoomCoder(ErasureCoder):
    """Wraps a real coder; fails on the Nth encode call."""

    def __init__(self, blow_at: int):
        self._inner = make_coder("cpu")
        super().__init__(self._inner.scheme)
        self.calls = 0
        self.blow_at = blow_at

    def encode(self, shards):
        return self._inner.encode(shards)

    def reconstruct(self, shards):
        return self._inner.reconstruct(shards)

    def encode_into(self, data, out):
        self.calls += 1
        if self.calls >= self.blow_at:
            raise RuntimeError("disk on fire")
        return np.asarray(self._inner.encode_array(data))

    def encode_array(self, data):
        self.calls += 1
        if self.calls >= self.blow_at:
            raise RuntimeError("disk on fire")
        return self._inner.encode_array(data)


def test_pipelined_encode_crash_leaves_nothing(tmp_path):
    base = str(tmp_path / "v")
    _make_dat(base, 2 * LB * K + SB * K)
    with pytest.raises(RuntimeError, match="disk on fire"):
        ecenc.write_ec_files(base, _BoomCoder(blow_at=3), LB, SB,
                             batch_size=SB, pipelined=True)
    assert _leftovers(tmp_path) == []


def test_serial_encode_crash_leaves_nothing(tmp_path):
    base = str(tmp_path / "v")
    _make_dat(base, 2 * LB * K + SB * K)
    with pytest.raises(RuntimeError, match="disk on fire"):
        ecenc.write_ec_files(base, _BoomCoder(blow_at=3), LB, SB,
                             batch_size=SB)
    assert _leftovers(tmp_path) == []


def test_pipelined_reader_stage_crash_raises_pipeline_error(
        tmp_path, monkeypatch):
    base = str(tmp_path / "v")
    _make_dat(base, 2 * LB * K + 2 * SB * K)
    real = streaming._read_rows
    state = {"n": 0}

    def flaky(f, buf, desc, k):
        state["n"] += 1
        if state["n"] == 4:
            raise IOError("surprise EIO")
        return real(f, buf, desc, k)

    monkeypatch.setattr(streaming, "_read_rows", flaky)
    with pytest.raises(streaming.PipelineError) as ei:
        ecenc.write_ec_files(base, make_coder("cpu"), LB, SB,
                             batch_size=SB, pipelined=True)
    assert isinstance(ei.value.__cause__, IOError)
    assert _leftovers(tmp_path) == []


def test_rebuild_crash_on_truncated_survivor(tmp_path):
    base = str(tmp_path / "v")
    _make_dat(base, LB * K + 3 * SB * K)
    ecenc.write_ec_files(base, make_coder("cpu"), LB, SB, batch_size=SB)
    os.remove(base + layout.shard_ext(12))
    # survivor .ec05 loses its tail -> reader short-read -> abort
    # (not .ec00: the first source shard DEFINES shard_size, so its
    # truncation just shortens the walk instead of erroring)
    sz = os.path.getsize(base + layout.shard_ext(5))
    with open(base + layout.shard_ext(5), "r+b") as f:
        f.truncate(sz - 16)
    with pytest.raises(streaming.PipelineError):
        ecenc.rebuild_ec_files(base, make_coder("cpu"), batch_size=SB,
                               pipelined=True)
    assert not os.path.exists(base + layout.shard_ext(12))
    assert not glob.glob(str(tmp_path) + "/*.tmp")


# ---- the window of two: batch N+1 is begun before batch N is collected ----

class _Begun:
    """What _OffThreadCoder.encode_begin returns: filled, or failed, by
    the coder's own thread, when the coder's rule says so."""

    def __init__(self, coder, seq, data, out):
        self.coder, self.seq, self.data, self.out = coder, seq, data, out
        self.finished = threading.Event()
        self.error = None

    def done(self):
        return self.finished.is_set()

    def result(self):
        assert self.finished.wait(60)
        with self.coder.cv:
            self.coder.collected.append(self.seq)
        if self.error is not None:
            raise self.error
        return self.out


class _OffThreadCoder(ErasureCoder):
    """Begun batches are finished by ANOTHER thread, and batch N only on
    request: once batch N+1 has been begun, or N is the last of
    ``batches`` (so the pipeline's window is full at every step it can
    be).  ``fail_at``: that batch fails instead; every batch after it is
    never finished."""

    def __init__(self, scheme, batches, fail_at=None):
        super().__init__(scheme)
        self._inner = rs_cpu.CpuCoder(scheme)
        self.batches, self.fail_at = batches, fail_at
        self.cv = threading.Condition()
        self.begun: list[_Begun] = []
        self.collected: list[int] = []
        self.most_uncollected = 0
        self.closed = False
        self._thread = threading.Thread(target=self._finisher, daemon=True)
        self._thread.start()

    def encode(self, shards):
        return self._inner.encode(shards)

    def reconstruct(self, shards):
        return self._inner.reconstruct(shards)

    def encode_begin(self, data, out):
        with self.cv:
            b = _Begun(self, len(self.begun), data, out)
            self.begun.append(b)
            self.most_uncollected = max(
                self.most_uncollected,
                len(self.begun) - len(self.collected))
            self.cv.notify_all()
        return b

    def _finisher(self):
        for seq in range(self.batches):
            want = min(self.batches, seq + 2)
            with self.cv:
                self.cv.wait_for(
                    lambda: len(self.begun) >= want or self.closed)
                if self.closed:
                    return
                b = self.begun[seq]
            if seq == self.fail_at:
                b.error = ValueError(f"batch {seq} lost")
                b.finished.set()
                return
            self._inner.encode_into(b.data, b.out)
            b.finished.set()

    def close(self):
        with self.cv:
            self.closed = True
            self.cv.notify_all()
        self._thread.join(10)


def _tail_size(k: int, batches: int) -> int:
    """A .dat of ``batches`` small rows (a batch each at batch_size=SB),
    the last of them short."""
    return (batches - 1) * SB * k + 37


@pytest.mark.parametrize("batches", [1, 2, 3, 7])
@pytest.mark.parametrize("scheme", [DEFAULT_SCHEME, RSScheme(6, 3)],
                         ids=["rs10-4", "rs6-3"])
def test_window_of_two_matches_the_serial_walk(tmp_path, scheme, batches):
    """A coder that finishes batch N on another thread, and only once
    N+1 is begun: byte-identical shards, written in order; never more
    than two batches begun and not collected; every batch but the first
    begun while the one before it was still in the coder."""
    k, total = scheme.data_shards, scheme.total_shards
    sbase, pbase = str(tmp_path / "s"), str(tmp_path / "p")
    for b in (sbase, pbase):
        _make_dat(b, _tail_size(k, batches), seed=batches)
    ecenc.write_ec_files(sbase, rs_cpu.CpuCoder(scheme), LB, SB,
                         batch_size=SB)
    coder = _OffThreadCoder(scheme, batches)
    stats: dict = {}
    try:
        streaming.pipelined_encode_file(pbase, coder, LB, SB,
                                        batch_size=SB, stats=stats)
    finally:
        coder.close()
    for i in range(total):
        ext = layout.shard_ext(i)
        assert open(pbase + ext, "rb").read() == \
            open(sbase + ext, "rb").read(), ext
    assert not os.path.exists(pbase + layout.shard_ext(total))
    assert coder.collected == list(range(batches))
    assert coder.most_uncollected == min(2, batches)
    assert stats["batches"] == batches
    assert stats["overlapped"] == batches - 1
    assert stats["encode_s"] > 0


class _SpyCoder(rs_cpu.CpuCoder):
    """The host coder, recording what encode_into is handed."""

    def __init__(self):
        super().__init__()
        self.calls = []

    def encode_into(self, data, out):
        self.calls.append((data.tobytes(), out.shape, out.dtype))
        return super().encode_into(data, out)


def test_host_coder_leaves_nothing_in_flight(tmp_path):
    """A coder that works on the caller's thread: one encode_into a
    batch, in the plan's order, on the (k, step) rows and an (m, step)
    buffer as before the window existed, and ``overlapped`` 0."""
    base = str(tmp_path / "v")
    size = LB * K + 2 * SB * K + 11
    dat = _make_dat(base, size, seed=4)
    coder = _SpyCoder()
    stats: dict = {}
    streaming.pipelined_encode_file(base, coder, LB, SB, batch_size=SB,
                                    stats=stats)
    want = []
    padded = dat + bytes(LB * K + 3 * SB * K - size)
    for row_off, block, b, step in layout.iter_encode_batches(
            size, LB, SB, SB, K):
        rows = b"".join(padded[row_off + i * block + b:
                               row_off + i * block + b + step]
                        for i in range(K))
        want.append((rows, (TOTAL - K, step), np.uint8))
    assert coder.calls == want
    assert stats["batches"] == len(want) and stats["overlapped"] == 0
    # the seam's two-step form on a host coder: all done in the begin
    rows = np.frombuffer(want[0][0], dtype=np.uint8).reshape(K, -1)
    out = np.empty((TOTAL - K, rows.shape[1]), dtype=np.uint8)
    began = coder.encode_begin(rows, out)
    assert isinstance(began, Encoded) and began.done()
    assert began.result() is out
    assert np.array_equal(out, make_coder("cpu").encode_array(rows))
    sbase = str(tmp_path / "s")
    _make_dat(sbase, size, seed=4)
    ecenc.write_ec_files(sbase, make_coder("cpu"), LB, SB, batch_size=SB)
    assert _shards(base) == _shards(sbase)


@pytest.mark.parametrize("fail_at,batches", [(0, 2), (1, 4), (2, 4)])
def test_failed_batch_is_raised_with_the_next_still_in_the_coder(
        tmp_path, fail_at, batches):
    """Batch N fails while N+1 is in the coder (and is never finished):
    N's error is the PipelineError's cause, no shard has a final name,
    no .tmp is left, and the call is back without N+1."""
    base = str(tmp_path / "v")
    _make_dat(base, _tail_size(K, batches), seed=8)
    coder = _OffThreadCoder(DEFAULT_SCHEME, batches, fail_at=fail_at)
    try:
        with pytest.raises(streaming.PipelineError) as ei:
            streaming.pipelined_encode_file(base, coder, LB, SB,
                                            batch_size=SB)
        assert isinstance(ei.value.__cause__, ValueError)
        assert str(ei.value.__cause__) == f"batch {fail_at} lost"
        assert len(coder.begun) == fail_at + 2
        assert not coder.begun[fail_at + 1].done()
        assert coder.collected == list(range(fail_at + 1))
    finally:
        coder.close()
    assert _leftovers(tmp_path) == []


class _SpiedFile:
    """A shard's .tmp file that notes which thread writes it, and can be
    made to fail."""

    def __init__(self, f, log, fail):
        self._f, self._log, self._fail = f, log, fail

    def write(self, row):
        self._log.append(threading.get_ident())
        if self._fail and len(self._log) >= 2:
            raise IOError("no space left")
        return self._f.write(row)

    def close(self):
        self._f.close()


def _spy_on_shard_writes(monkeypatch, library, failing=()):
    """Which thread writes each shard's .tmp file, {shard: [thread a
    write]}, and the library's calls, [(thread, shards, offset)]. With
    the library a writer's batch is ONE ``pwrite_rows``; ``absent``:
    the file objects' writes, a row each. A shard in ``failing`` fails
    its writer's second write with an IOError."""
    _library(monkeypatch, library)
    logs: dict[int, list[int]] = {}
    calls: list[tuple[int, list[int], int]] = []
    shard_of: dict[int, int] = {}
    real_init = streaming.AtomicFileGroup.__init__
    real_pwrite = rs_native.pwrite_rows

    def init(self, paths):
        real_init(self, paths)
        if library == "absent":
            self.files = [
                _SpiedFile(f, logs.setdefault(i, []), i in failing)
                for i, f in enumerate(self.files)]
        else:
            shard_of.update({f.fileno(): i for i, f in enumerate(self.files)})

    def pwrite_rows(fds, offset, rows, size):
        me, shards = threading.get_ident(), [shard_of[fd] for fd in fds]
        calls.append((me, shards, offset))
        for i in shards:
            logs.setdefault(i, []).append(me)
        if set(shards) & set(failing) and \
                sum(1 for c in calls if c[1] == shards) >= 2:
            raise OSError(errno.ENOSPC, "no space left")
        return real_pwrite(fds, offset, rows, size)

    monkeypatch.setattr(streaming.AtomicFileGroup, "__init__", init)
    monkeypatch.setattr(rs_native, "pwrite_rows", pwrite_rows)
    return logs, calls


def _two_writers(tmp_path, monkeypatch, library):
    """A seal of five batches: every data shard's file is written by one
    writer thread, every parity shard's by the other, a batch at a time,
    and the files are the serial path's. Returns the library's calls."""
    logs, calls = _spy_on_shard_writes(monkeypatch, library)
    sbase, pbase = str(tmp_path / "s"), str(tmp_path / "p")
    for b in (sbase, pbase):
        _make_dat(b, _tail_size(K, 5), seed=6)
    stats: dict = {}
    streaming.pipelined_encode_file(pbase, make_coder("cpu"), LB, SB,
                                    batch_size=SB, stats=stats)
    assert [len(logs[i]) for i in range(TOTAL)] == [5] * TOTAL
    data_threads = {t for i in range(K) for t in logs[i]}
    parity_threads = {t for i in range(K, TOTAL) for t in logs[i]}
    assert len(data_threads) == len(parity_threads) == 1
    assert data_threads != parity_threads
    assert threading.get_ident() not in data_threads | parity_threads
    assert stats["write_s"] > 0
    monkeypatch.undo()
    ecenc.write_ec_files(sbase, make_coder("cpu"), LB, SB, batch_size=SB)
    assert _shards(pbase) == _shards(sbase)
    return calls


def test_data_rows_and_parity_rows_have_a_writer_each(
        tmp_path, monkeypatch):
    """Two writer threads, each ONE call of the library a batch: the
    data writer's covers the k data shards' files, the parity writer's
    the m others', each at the offset the batches before it reached."""
    calls = _two_writers(tmp_path, monkeypatch, "native")
    for shards in (list(range(K)), list(range(K, TOTAL))):
        assert [off for _t, s, off in calls if s == shards] == \
            [i * SB for i in range(5)]
    assert len(calls) == 2 * 5


def test_without_the_library_each_writer_writes_a_row_at_a_time(
        tmp_path, monkeypatch):
    """The same two writers through the file objects, one write a row,
    where the native library is unavailable."""
    assert _two_writers(tmp_path, monkeypatch, "absent") == []


@pytest.mark.parametrize("failing,library", [
    (3, "native"), (K + 1, "native"), (3, "absent"), (K + 1, "absent")],
    ids=["data-writer", "parity-writer", "data-writer-absent",
         "parity-writer-absent"])
def test_either_writer_crash_raises_pipeline_error(tmp_path, monkeypatch,
                                                   failing, library):
    _spy_on_shard_writes(monkeypatch, library, failing=(failing,))
    base = str(tmp_path / "v")
    _make_dat(base, _tail_size(K, 6), seed=7)
    with pytest.raises(streaming.PipelineError) as ei:
        streaming.pipelined_encode_file(base, make_coder("cpu"), LB, SB,
                                        batch_size=SB)
    assert isinstance(ei.value.__cause__, IOError)
    assert _leftovers(tmp_path) == []


# ---- pipelined rebuild / decode identity ----

@pytest.mark.parametrize("drop", [[1, 11], [0, 2, 11, 13]])
def test_pipelined_rebuild_matches_originals(tmp_path, drop):
    base = str(tmp_path / "v")
    _make_dat(base, 2 * LB * K + SB * K + 9, seed=5)
    ecenc.write_ec_files(base, make_coder("cpu"), LB, SB, batch_size=SB)
    want = _shards(base)
    for i in drop:
        os.remove(base + layout.shard_ext(i))
    got_ids = ecenc.rebuild_ec_files(base, make_coder("cpu-mt"),
                                     batch_size=SB, pipelined=True)
    assert sorted(got_ids) == sorted(drop)
    assert _shards(base) == want


@pytest.mark.parametrize("size", [
    1,
    SB * K - 7,
    SB * K * 5 + SB // 2,
    2 * LB * K,                 # regression: old `>=` read this as a
    2 * LB * K + 3,             # large row and scrambled the reassembly
    2 * LB * K + 3 * SB * K + 77,
])
@pytest.mark.parametrize("pipelined", [False, True])
def test_write_dat_file_roundtrip(tmp_path, size, pipelined):
    base = str(tmp_path / "v")
    dat = _make_dat(base, size, seed=size % 97)
    ecenc.write_ec_files(base, make_coder("cpu"), LB, SB, batch_size=SB)
    os.remove(base + ".dat")
    ecdec.write_dat_file(base, size, LB, SB, pipelined=pipelined)
    assert open(base + ".dat", "rb").read() == dat


@pytest.mark.parametrize("pipelined", [False, True])
def test_write_dat_file_crash_removes_tmp(tmp_path, pipelined):
    base = str(tmp_path / "v")
    size = LB * K + SB * K
    _make_dat(base, size)
    ecenc.write_ec_files(base, make_coder("cpu"), LB, SB, batch_size=SB)
    os.remove(base + ".dat")
    sz = os.path.getsize(base + layout.shard_ext(0))
    with open(base + layout.shard_ext(0), "r+b") as f:
        f.truncate(sz - 8)      # reader hits EOF before `take` satisfied
    with pytest.raises((IOError, streaming.PipelineError)):
        ecdec.write_dat_file(base, size, LB, SB, pipelined=pipelined)
    assert not os.path.exists(base + ".dat")
    assert not os.path.exists(base + ".dat.tmp")


# ---- multi-core CpuCoder sharding ----

def test_cpu_workers_bit_identical():
    from seaweedfs_tpu.ops import rs_cpu
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, (K, 1 << 17), dtype=np.uint8)
    base = make_coder("cpu").encode_array(data)
    for native in (True, False):
        if native and rs_cpu._native() is None:
            continue
        mt = rs_cpu.CpuCoder(use_native=native, workers=3)
        assert np.array_equal(mt.encode_array(data), base), native


def test_cpu_mt_registered_and_auto_workers():
    from seaweedfs_tpu.ops import rs_cpu
    mt = make_coder("cpu-mt")
    assert mt.workers == rs_cpu.auto_workers() >= 1
    rng = np.random.default_rng(10)
    data = rng.integers(0, 256, (K, 4096), dtype=np.uint8)
    assert np.array_equal(mt.encode_array(data),
                          make_coder("cpu").encode_array(data))


def test_numpy_fallback_methods_agree():
    """pair16 (production fallback) vs split-nibble (independent method)
    vs the native kernel: three GF(256) matrix-apply implementations,
    one answer."""
    from seaweedfs_tpu.ops import rs_cpu
    from seaweedfs_tpu.ops.gf256 import rs_matrix
    rng = np.random.default_rng(11)
    mat = np.asarray(rs_matrix(10, 14))[10:]
    for n in (1, 2, 63, 64, 65, 4097):
        data = rng.integers(0, 256, (10, n), dtype=np.uint8)
        out = np.zeros((4, n), dtype=np.uint8)
        rs_cpu._gf_apply_numpy_into(mat, data, out)
        assert np.array_equal(out, rs_cpu._gf_apply_nibble(mat, data)), n
        if rs_cpu._native() is not None:
            assert np.array_equal(
                out, rs_cpu._gf_apply(mat, data, use_native=True)), n


# ---- the pipeline's account of a seal, in /admin/ec/generate's reply ----

def test_generate_reply_carries_the_pipelines_stats(tmp_path, monkeypatch):
    """``pipeline``: busy seconds per stage, the commit, the bytes read;
    ``base`` as before.  At the default sample rate's "off" side (rate
    0) no stage of the seal or of a read allocates a child span."""
    import time

    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils import tracing
    from seaweedfs_tpu.utils.httpd import http_call, http_json

    children = []
    real_child = tracing.Span.child
    monkeypatch.setattr(tracing.Span, "child", lambda self, *a, **kw: (
        children.append(a), real_child(self, *a, **kw))[1])
    master = MasterServer(volume_size_limit_mb=64, tracing_enabled=False)
    master.start()
    vs = VolumeServer([str(tmp_path / "v")], master.url,
                      scrub_interval_s=0, trace_sample=0.0)
    try:
        vs.start()
        mc = MasterClient(master.url, cache_ttl=0.0)
        deadline = time.time() + 10
        while True:
            try:
                up = operation.upload_data(mc, os.urandom(300_000))
                break
            except Exception:  # noqa: BLE001 — the node is still joining
                if time.time() > deadline:
                    raise
                time.sleep(0.05)
        vid = int(up.fid.split(",")[0])
        del children[:]
        reply = http_json("POST", f"http://{vs.url}/admin/ec/generate",
                          {"volume_id": vid})
        dat = os.path.join(str(tmp_path / "v"), reply["base"] + ".dat")
        p = reply["pipeline"]
        assert reply["base"] == str(vid)
        assert p["bytes_in"] == os.path.getsize(dat)
        assert p["read_s"] + p["encode_s"] + p["write_s"] > 0
        assert p["batches"] >= 1 and p["commit_s"] > 0
        # the default coder works on the caller's thread: no batch is
        # begun while another is in the coder
        assert p["overlapped"] == 0
        assert p["wall_s"] >= p["commit_s"]
        # one call a batch in each of the reader and the two writers
        assert p["io_calls"] == p["batches"] * (
            3 if rs_native.available() else 2 * K + TOTAL - K)
        for i in range(TOTAL):
            assert os.path.exists(os.path.join(
                str(tmp_path / "v"), reply["base"] + layout.shard_ext(i)))
        # the serial path has no pipeline to account for
        serial = http_json("POST", f"http://{vs.url}/admin/ec/generate",
                           {"volume_id": vid, "pipelined": False})
        assert serial == {"base": str(vid), "pipeline": {}}
        http_json("POST", f"http://{vs.url}/admin/ec/mount",
                  {"volume_id": vid,
                   "shard_ids": list(range(TOTAL))})
        http_json("POST", f"http://{vs.url}/admin/delete_volume",
                  {"volume_id": vid})
        status, body, _ = http_call("GET", f"http://{vs.url}/{up.fid}")
        assert status == 200 and len(body) == 300_000
        stat = http_json(
            "GET", f"http://{vs.url}/admin/ec/shard_stat?volumeId={vid}")
        assert set(stat["recover_stats"]) == {"local", "global", "generic"}
        assert stat["read_stats"]["intervals_local"] >= 1
        assert stat["read_stats"]["intervals_recovered"] == 0
        # (http_call's client spans for outbound RPCs are the tracer's
        # own and older than the stages)
        assert [c for c in children if c[0].startswith(
            ("ec.", "store.", "volume."))] == []
    finally:
        vs.stop()
        master.stop()
