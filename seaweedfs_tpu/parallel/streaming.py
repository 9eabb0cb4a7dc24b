"""Staged EC pipelines: overlapped read -> code -> write for whole volumes.

A 30GB volume is walked in column-aligned batches: reader threads
prefetch from the .dat, the caller's thread takes each batch through the
coder and two writer threads drain the coded ones to the shard files
(one the data shards' rows, one the parity shards': a single thread
copying a whole batch into the page cache took as long as the device
took to code it). The
caller uses the coder's two-step form (``ErasureCoder.encode_begin``):
it BEGINS batch N+1 as soon as the reader has it and only then collects
batch N and hands it to the writer, in order. A coder that hands its
batches to another thread therefore always has the next one waiting
when it is done with one (never more than two begun and not collected;
``overlapped`` in the stats counts the batches begun while the one
before was not finished); a coder that works on the caller's thread has
finished when its begin returns, so nothing is ever ahead and the calls
come in the order they always did. The pipeline knows files and the
``ErasureCoder`` seam, nothing of devices: the same stages serve a host
coder (whose native kernel releases the GIL, so the reader/writer
threads genuinely overlap the GF compute) and the batch scheduler's
facade (``BatchCoder``: the begin is a job's submit, the collect its
wait / dispatch / result on the device queue, and the dispatcher finds
job N+1 queued the moment it has demuxed job N).

Each I/O stage of the encode moves a batch between the files and its
buffer with ONE foreign call (``native.rs_native.pread_rows`` for the
reader's k rows of the .dat, ``pwrite_rows`` for a writer's rows into
its shard files), so it gives the interpreter lock away once a batch and
not once a row, and wins it back from the process's other busy threads
once. Without the native library the same stages make one ``readinto``
or ``write`` a row (the rule of ``ec_volume.read_shards_into``).
``io_calls`` in the stats counts those calls: 3 a batch, or 2k + m.

Stage plumbing invariants:
  - every inter-stage queue is BOUNDED (maxsize=prefetch): a slow writer
    backpressures the coder, a slow coder backpressures the readers, so
    peak memory is O(prefetch * batch) regardless of volume size (the
    batch ahead in the coder is one more data and one more parity
    buffer);
  - a failing stage records its exception in the _Pipeline and trips the
    shared abort event; every blocking put/get polls that event, so all
    threads unwind promptly and the first error is re-raised to the caller
    (a batch that fails in the coder likewise; the batch begun behind it
    is abandoned where it is, not waited for);
  - shard outputs go to `.tmp` names and are renamed into place only after
    every stage has finished cleanly — an interrupted pipeline never
    leaves a truncated file under a final shard name;
  - the data buffers are pooled and recycled writer -> reader, so their
    steady-state allocation is zero.  A batch's parity is the array the
    coder's begin hands back (``encode_begin`` is given no buffer): under
    the batch scheduler the rows its collector fetched the device's
    output into, so the parity reaches host memory once and is never
    copied on the caller's thread; a host coder computes it into an
    array of its own.  The parity writer writes it and drops it.

Each run is one ``ec.pipeline`` stage (utils/tracing.stage) with an
``ec.pipeline.read`` / ``.write`` stage per batch in the thread that does
it, an ``ec.pipeline.encode`` stage per turn of the caller in the coder
(begin N+1, collect N: one a batch, and one more for the last collect
when batches were ahead) and ``ec.pipeline.commit`` at the end; the same
busy seconds fill the caller's ``stats`` dict.
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Optional, Sequence

import numpy as np

from seaweedfs_tpu.models.coder import ErasureCoder
from seaweedfs_tpu.native import rs_native
from seaweedfs_tpu.storage.erasure_coding import layout
from seaweedfs_tpu.utils import clockctl, tracing

DEFAULT_PIPE_BATCH = 16 * 1024 * 1024


class PipelineError(RuntimeError):
    """A pipeline stage failed; the original exception is the __cause__."""


class _Aborted(Exception):
    """Internal control flow: the shared abort event tripped."""


class _Pipeline:
    """Shared failure state for one pipeline run: first-error capture plus
    an abort event that every blocking queue operation polls."""

    _POLL = 0.05

    def __init__(self):
        self.abort = threading.Event()
        self._lock = threading.Lock()
        self._error: Optional[BaseException] = None
        self._threads: list[threading.Thread] = []

    def fail(self, exc: BaseException) -> None:
        with self._lock:
            if self._error is None:
                self._error = exc
        self.abort.set()

    def check(self) -> None:
        if self._error is not None:
            raise PipelineError(
                f"pipeline stage failed: {self._error!r}") from self._error

    def put(self, q: "queue.Queue", item) -> None:
        while True:
            if self.abort.is_set():
                raise _Aborted()
            try:
                q.put(item, timeout=self._POLL)
                return
            except queue.Full:
                continue

    def get(self, q: "queue.Queue"):
        while True:
            if self.abort.is_set():
                raise _Aborted()
            try:
                return q.get(timeout=self._POLL)
            except queue.Empty:
                continue

    def spawn(self, fn, *args) -> threading.Thread:
        """Run fn(*args) in a daemon thread; any exception trips abort.
        The spawner's ambient span is re-entered there (ContextVars do
        not cross threads), so the stage's spans hang off the caller's."""
        span = tracing.current_span()

        def run():
            try:
                with tracing.span_scope(span):
                    fn(*args)
            except _Aborted:
                pass
            except BaseException as e:  # noqa: BLE001 — must reach caller
                self.fail(e)
        t = threading.Thread(target=run, daemon=True,
                             name="ec-stream")
        t.start()
        self._threads.append(t)
        return t

    def join(self) -> None:
        for t in self._threads:
            t.join()
        self.check()


class _BufferPool:
    """Recycles equal-shaped uint8 arrays writer -> reader. get() falls
    back to allocation on shape change (large rows -> small-row tail)."""

    def __init__(self):
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()

    def get(self, shape: tuple[int, ...]) -> np.ndarray:
        try:
            while True:
                buf = self._q.get_nowait()
                if buf.shape == shape:
                    return buf
                # stale shape from a previous block tier — drop it
        except queue.Empty:
            return np.empty(shape, dtype=np.uint8)

    def put(self, buf: np.ndarray) -> None:
        self._q.put(buf)


class AtomicFileGroup:
    """A set of output files written under `.tmp` names and renamed into
    place together on commit(). discard() removes the temporaries; either
    way no truncated file is ever visible under a final name. A writer
    may write the files' descriptors at offsets of its own (pwrite):
    the file objects then hold nothing to flush."""

    def __init__(self, paths: Sequence[str]):
        self.paths = list(paths)
        self._tmps = [p + ".tmp" for p in self.paths]
        self.files = [open(t, "wb") for t in self._tmps]
        self._open = True

    def _close(self) -> None:
        if self._open:
            for f in self.files:
                f.close()
            self._open = False

    def commit(self) -> None:
        self._close()
        for tmp, final in zip(self._tmps, self.paths):
            os.replace(tmp, final)

    def discard(self) -> None:
        self._close()
        for tmp in self._tmps:
            try:
                os.remove(tmp)
            except OSError:
                pass


def _merge_stats(stats: Optional[dict], lock: threading.Lock,
                 **deltas) -> None:
    if stats is None:
        return
    with lock:
        for key, v in deltas.items():
            stats[key] = stats.get(key, 0) + v


def _read_rows(f, buf: np.ndarray, desc, k: int) -> int:
    """Fill buf (k, step) with the descriptor's per-shard slices of the
    .dat, zero-filling past EOF (encodeDataOneBatch semantics). Returns
    the calls that moved the bytes: one with the native library (row i
    from row_off + b + i * block, adjacent on small-block rows and a
    large block apart on large ones), a ``readinto`` a row without."""
    row_off, block, b, step = desc
    if rs_native.available():
        got = rs_native.pread_rows([f.fileno()] * k, row_off + b, buf, step,
                                   offset_step=block)
        calls = 1
    else:
        got = []
        for i in range(k):
            f.seek(row_off + i * block + b)
            got.append(f.readinto(memoryview(buf[i])))
        calls = k
    for i, n in enumerate(got):
        if n < step:
            buf[i, n:] = 0
    return calls


def pipelined_encode_file(base_file_name: str,
                          coder: ErasureCoder,
                          large_block: int = layout.LARGE_BLOCK_SIZE,
                          small_block: int = layout.SMALL_BLOCK_SIZE,
                          batch_size: int = DEFAULT_PIPE_BATCH,
                          prefetch: int = 2,
                          readers: int = 1,
                          stats: Optional[dict] = None) -> None:
    """write_ec_files as a staged pipeline; identical on-disk output.

    The calling thread stands between the reader and writer stages and
    keeps up to two batches in the coder (module docstring). `stats`,
    when a dict, receives per-stage busy seconds (read_s / encode_s: the
    caller's time in the coder, begins and collects / write_s: the two
    writers' together / commit_s), wall_s, bytes_in, batches,
    overlapped (batches begun while the one before was still in the
    coder: batches - 1 under the batch scheduler, 0 under a host coder)
    and io_calls (the reader's and the writers' calls that moved bytes:
    3 a batch with the native library, 2k + m without) — the
    ``pipeline`` object of ``/admin/ec/generate``'s reply."""
    scheme = coder.scheme
    k = scheme.data_shards
    total = scheme.total_shards
    dat_path = base_file_name + ".dat"
    dat_size = os.path.getsize(dat_path)
    descs = list(layout.iter_encode_batches(dat_size, large_block,
                                            small_block, batch_size, k))
    readers = max(1, min(readers, len(descs) or 1))

    pl = _Pipeline()
    read_q: "queue.Queue" = queue.Queue(maxsize=prefetch)
    data_q: "queue.Queue" = queue.Queue(maxsize=prefetch)
    parity_q: "queue.Queue" = queue.Queue(maxsize=prefetch)
    data_pool = _BufferPool()
    slock = threading.Lock()
    wall0 = clockctl.monotonic()

    def reader_stage(rid: int):
        busy, calls = 0.0, 0
        with open(dat_path, "rb") as f:
            for seq in range(rid, len(descs), readers):
                with tracing.stage("ec.pipeline.read") as st:
                    buf = data_pool.get((k, descs[seq][3]))
                    calls += _read_rows(f, buf, descs[seq], k)
                busy += st.elapsed
                pl.put(read_q, (seq, buf))
        _merge_stats(stats, slock, read_s=busy, io_calls=calls)

    def writer_stage(q: "queue.Queue", files: list,
                     pool: Optional[_BufferPool]):
        """One of the two writers: a batch's rows, one to a file, in the
        order they are handed over (every file grows by the batch's
        width, so one running offset serves them all: ONE foreign call a
        batch with the native library, a write a file without); the
        buffer then goes back to its pool (the data writer's) or is
        dropped (the parity writer's: the coder's own array)."""
        fds = [f.fileno() for f in files] if rs_native.available() \
            else None
        busy, calls, offset = 0.0, 0, 0
        while True:
            rows = pl.get(q)
            if rows is None:
                break
            with tracing.stage("ec.pipeline.write") as st:
                if fds is not None:
                    rs_native.pwrite_rows(fds, offset, rows, rows.shape[1])
                    offset += rows.shape[1]
                    calls += 1
                else:
                    for f, row in zip(files, rows):
                        f.write(row)
                    calls += len(files)
            busy += st.elapsed
            if pool is not None:
                pool.put(rows)
        _merge_stats(stats, slock, write_s=busy, io_calls=calls)

    def hand_over(rows, parity):
        pl.put(data_q, rows)
        pl.put(parity_q, parity)

    outs = AtomicFileGroup([base_file_name + layout.shard_ext(i)
                            for i in range(total)])
    whole = tracing.stage_begin("ec.pipeline")
    try:
        # the data shards' files and the parity shards' have a writer
        # each: one thread copying 14 MiB a batch into the page cache is
        # as slow as the dispatch it runs beside
        pl.spawn(writer_stage, data_q, outs.files[:k], data_pool)
        pl.spawn(writer_stage, parity_q, outs.files[k:], None)
        for rid in range(readers):
            pl.spawn(reader_stage, rid)

        encode_busy = 0.0
        overlapped = 0
        stash: dict[int, np.ndarray] = {}
        # the batch that is in the coder and not collected yet: (its
        # data, what encode_begin returned).  One here at most, and one
        # more while the next is begun: never more than two
        ahead = None
        for expected in range(len(descs)):
            while expected not in stash:
                seq, buf = pl.get(read_q)
                stash[seq] = buf
            data = stash.pop(expected)
            ready = []
            # the caller's turn in the coder: batch N+1 is begun, only
            # then batch N collected.  Under the batch scheduler the
            # begin CONTAINS the job's submit and the collect its wait /
            # dispatch / result: the dispatcher finds N+1 queued when it
            # is done with N.  A host coder does all of it in the begin
            # and leaves nothing ahead
            with tracing.stage("ec.pipeline.encode") as st:
                if ahead is not None and not ahead[1].done():
                    overlapped += 1
                began = coder.encode_begin(data)
                if ahead is not None:
                    ready.append((ahead[0], _collect(pl, ahead[1])))
                    ahead = None
                if began.done():
                    ready.append((data, _collect(pl, began)))
                else:
                    ahead = (data, began)
            encode_busy += st.elapsed
            for rows, parity in ready:
                hand_over(rows, parity)
        if ahead is not None:
            # the last batch, with none to begin behind it
            with tracing.stage("ec.pipeline.encode") as st:
                parity = _collect(pl, ahead[1])
            encode_busy += st.elapsed
            hand_over(ahead[0], parity)
        hand_over(None, None)
        pl.join()
        with tracing.stage("ec.pipeline.commit") as st:
            outs.commit()
        _merge_stats(stats, slock, encode_s=encode_busy,
                     commit_s=st.elapsed,
                     wall_s=clockctl.monotonic() - wall0,
                     bytes_in=dat_size, batches=len(descs),
                     overlapped=overlapped)
        if stats is not None:
            for key in ("read_s", "encode_s", "write_s", "commit_s",
                        "wall_s", "bytes_in", "batches", "overlapped",
                        "io_calls"):
                whole.annotate(key, stats.get(key))
    except _Aborted:
        # a stage failed and tripped abort while the main thread blocked;
        # surface the stage's exception, not the control-flow marker
        _unwind(pl, outs)
    except BaseException:
        pl.abort.set()
        _unwind(pl, outs, reraise=False)
        raise
    finally:
        tracing.stage_end(whole)


def _collect(pl: _Pipeline, began) -> np.ndarray:
    """The parity of a begun batch.  Its failure is the pipeline's: kept
    as the first error if none came before, and the unwinding waits for
    no batch that is still in the coder."""
    try:
        return began.result()
    except Exception as e:  # noqa: BLE001 — re-raised as PipelineError
        pl.fail(e)
        raise _Aborted() from e


def _unwind(pl: _Pipeline, outs: "AtomicFileGroup",
            reraise: bool = True) -> None:
    for t in pl._threads:
        t.join(timeout=5)
    outs.discard()
    if reraise:
        pl.check()
        raise PipelineError("pipeline aborted without a recorded error")


def pipelined_rebuild_files(base_file_name: str,
                            coder: ErasureCoder,
                            batch_size: int = DEFAULT_PIPE_BATCH,
                            prefetch: int = 2,
                            stats: Optional[dict] = None) -> list[int]:
    """Regenerate missing .ecNN files from survivors with overlapped
    shard reads, GF reconstruction and writes. Returns generated ids.

    The coefficient matrix mapping the first k surviving shards to every
    missing shard is computed ONCE (CpuCoder.rebuild_matrix) and streamed
    over the batches — the serial path re-derives it per batch through
    the bytes API."""
    k = coder.scheme.data_shards
    total = coder.scheme.total_shards
    present = [i for i in range(total)
               if os.path.exists(base_file_name + layout.shard_ext(i))]
    missing = [i for i in range(total) if i not in present]
    if not missing:
        return []
    if len(present) < k and not hasattr(coder, "plan_rebuild"):
        raise ValueError(f"need {k} shards, have {len(present)}")

    if not hasattr(coder, "rebuild_matrix"):
        from seaweedfs_tpu.ops.rs_cpu import CpuCoder
        coder = CpuCoder(coder.scheme, workers="auto")
    from seaweedfs_tpu.storage.erasure_coding.encoder import rebuild_rows
    src, batch_size, rebuild = rebuild_rows(coder, present, missing,
                                            batch_size)
    n_src = len(src)

    shard_size = os.path.getsize(base_file_name + layout.shard_ext(src[0]))
    offs = list(range(0, shard_size, batch_size))

    pl = _Pipeline()
    read_q: "queue.Queue" = queue.Queue(maxsize=prefetch)
    write_q: "queue.Queue" = queue.Queue(maxsize=prefetch)
    data_pool = _BufferPool()
    out_pool = _BufferPool()
    slock = threading.Lock()
    wall0 = clockctl.monotonic()

    def reader_stage():
        busy = 0.0
        ins = [open(base_file_name + layout.shard_ext(i), "rb") for i in src]
        try:
            for off in offs:
                n = min(batch_size, shard_size - off)
                with tracing.stage("ec.pipeline.read") as st:
                    buf = data_pool.get((n_src, n))
                    for r, f in enumerate(ins):
                        f.seek(off)
                        got = f.readinto(memoryview(buf[r]))
                        if got < n:
                            raise IOError(
                                f"short read on {base_file_name}"
                                f"{layout.shard_ext(src[r])} at {off}")
                busy += st.elapsed
                pl.put(read_q, buf)
            pl.put(read_q, None)
        finally:
            for f in ins:
                f.close()
        _merge_stats(stats, slock, read_s=busy)

    def writer_stage(outs: AtomicFileGroup):
        busy = 0.0
        while True:
            item = pl.get(write_q)
            if item is None:
                break
            with tracing.stage("ec.pipeline.write") as st:
                for r in range(len(missing)):
                    outs.files[r].write(item[r])
            busy += st.elapsed
            out_pool.put(item)
        _merge_stats(stats, slock, write_s=busy)

    outs = AtomicFileGroup([base_file_name + layout.shard_ext(i)
                            for i in missing])
    whole = tracing.stage_begin("ec.pipeline")
    try:
        writer_t = pl.spawn(writer_stage, outs)
        pl.spawn(reader_stage)
        busy = 0.0
        while True:
            buf = pl.get(read_q)
            if buf is None:
                break
            with tracing.stage("ec.pipeline.encode") as st:
                rec = rebuild(buf,
                              out_pool.get((len(missing), buf.shape[1])))
            busy += st.elapsed
            pl.put(write_q, rec)
            data_pool.put(buf)
        pl.put(write_q, None)
        writer_t.join()
        pl.join()
        with tracing.stage("ec.pipeline.commit") as st:
            outs.commit()
        _merge_stats(stats, slock, encode_s=busy, commit_s=st.elapsed,
                     wall_s=clockctl.monotonic() - wall0,
                     bytes_in=shard_size * n_src, batches=len(offs),
                     rebuilt_bytes=shard_size * len(missing))
        if stats is not None:
            with slock:
                stats["sources"] = list(src)
    except _Aborted:
        _unwind(pl, outs)
    except BaseException:
        pl.abort.set()
        _unwind(pl, outs, reraise=False)
        raise
    finally:
        tracing.stage_end(whole)
    return missing
