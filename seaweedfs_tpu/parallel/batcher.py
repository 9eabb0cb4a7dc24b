"""Cross-volume EC batch scheduler: coalesce, dispatch sharded, demux.

One device-mesh dispatch amortizes across many block-groups (ops/
rs_mesh.py), but the work arrives one block-group at a time from
independent callers: concurrent ``ec.encode`` pipelines on different
volumes, the repair queue's rebuild jobs, degraded reads.  This module
is the funnel between them and the mesh:

  submit (any thread) -> bounded queue -> the launching thread takes
  what is queued -> one MeshCoder dispatch, launched -> the collector
  thread fetches it -> per-job futures.

Scheduling contract:
  - the submission queue is BOUNDED (overload becomes backpressure on
    the submitting pipeline, not memory growth);
  - the dispatcher never WAITS for company: it takes the first job,
    drains what is already queued beside it (up to max_batch) and
    dispatches.  A lone job on an idle scheduler goes straight to the
    device — not held, and not copied either (a dispatch of one job
    hands the coder a view of the job's own buffer; ``lone_dispatches``
    counts them);
  - up to TWO dispatches are on the device (``IN_FLIGHT``, a constant):
    a dispatch is LAUNCHED (stack, pad, host -> device copy, enqueue:
    the mesh coder's ``*_batch_begin``) as soon as a job is queued and
    one of the two places is free, and dispatches are COLLECTED (wait,
    device -> host, unpack, demux: ``.result()`` of what the begin
    returned) in the order they were launched, by a second thread, so
    the thread that watches the queue never blocks on the device: job
    N+1's copy in rides beside job N's program and copy out
    (``overlapped_dispatches`` counts the dispatches launched while
    another was still on the device; over ``mesh_batches`` it says how
    often the window of two engages).  Coalescing happens behind TWO
    busy places: jobs that arrive while both are taken queue up and
    ride the next dispatch together, so a burst still fills a
    device-sized batch.  A host dispatch (a shape the mesh does not
    take, a benched mesh) stays synchronous on the launching thread;
  - jobs are ordered by QoS class (interactive > write > background —
    the ambient class is captured at submit, same as every other
    fan-out edge) before dispatch, so a background rebuild flood cannot
    starve a degraded-read reconstruction sharing the mesh.  The order
    is among the jobs of ONE batch: an interactive job that arrives
    while two background dispatches are on the device waits both out
    (at seal sizes <= ~9 ms, where one dispatch ahead was <= ~4.5 ms);
  - a scheduler is built FOR the device: when none is handed in it
    builds a MeshCoder and refuses to start where JAX found only the
    CPU and the CPU was not asked for by name (parallel/mesh.
    require_accelerator) — it never settles on the CPU coder unseen;
  - the mid-run CPU drain is safety code, per dispatch: when a mesh
    dispatch raises at its launch OR at its collect (the device was
    lost mid-run), its jobs, those of a dispatch in flight behind it
    (not asked of the device again) and everything queued drain through
    CpuCoderMT with bit-identical results, ``coder_fallbacks``
    increments once, and the mesh is benched for a cooldown before
    being retried; ``stop()`` collects what is in flight before the
    threads end;
  - the set of compiled shapes is BOUNDED: a job's columns pad up a
    short fixed ladder (COLUMN_LADDER), a batch pads to a power of two
    (MeshCoder._pad_batch) and one dispatch carries at most
    MAX_DISPATCH_COLUMNS columns, so a degraded read of a new needle
    size reuses a compiled program instead of compiling its own;
  - a job carries its code GEOMETRY (the scheme whose programs run it:
    a volume's own, plain RS (k, m) or another family's such as
    LRC(12,2,2), read from its .vif by the store).  Jobs of one
    geometry coalesce, jobs of two never share a dispatch, and the
    scheduler keeps one mesh coder (on the one device mesh) and one
    host fallback per geometry it has seen; ``by_spec`` counts each,
    ``by_rung`` each job width (which rung of the ladder the traffic
    rides, and how full its dispatches get) and ``cap_splits`` the groups
    that MAX_DISPATCH_COLUMNS cut into more than one dispatch;
  - a rebuild job carries as many ROWS as its matrix reads (k for plain
    RS; a family's local repair the k / l survivors of a group), and a
    dispatch groups jobs of one row count: a job of 6 rows and one of 12
    never share one (``by_spec[spec]["rows"]`` splits the jobs by it).

Where the time goes is counted always and traced when sampled: every
stage a job passes through (``STAGES``; utils/tracing.stage) adds its
seconds to ``stats()["stage_s"]``, the LAUNCHING thread's own time is
split into idle / hold (the wait for a place, the drain) / dispatch (a
dispatch's first half: stack, pad, launch) (``loop_s``), ``wait_hist``
is submit -> launch, and a job submitted under a SAMPLED request span
carries that span to the scheduler's threads, which record the job's
stages as its children: one ``ec.batch.dispatch`` span a dispatch, from
its launch to the end of its demux.  In a device trace's host plane
``ec.batch.dispatch`` is the first half, on the launching thread's
line, and ``ec.batch.collect`` the second, on the collector's.

All behavioral timing routes through clockctl so the scheduler stays
legible to the virtual-clock sim; blocking primitives (queue waits)
stay real because the batcher never runs inside the sim kernel.
"""

from __future__ import annotations

import collections
import queue
import threading
from concurrent.futures import Future
from typing import Callable, Optional, Sequence

import numpy as np

from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, ErasureCoder,
                                        RSScheme, code_spec_name,
                                        host_coder)
from seaweedfs_tpu.qos import CLASSES, current_class
from seaweedfs_tpu.utils import clockctl, glog, profiler, tracing
from seaweedfs_tpu.utils.metrics import RED_BUCKETS, Histogram

# coalesced-batch-size buckets: powers of two up to the default
# max_batch, so "how full are my mesh dispatches" reads straight off
# the histogram
BATCH_SIZE_BUCKETS = (1, 2, 4, 8, 16, 32, 64)

# Compiled-shape bound.  Every distinct (B, k, n) is a fresh XLA
# program (seconds of compile each — ~10 s for the traced-coefficient
# rebuild program), so job widths snap UP to this ladder before they
# reach the mesh: zero columns are inert in a GF-linear map and are
# sliced off again by the job's own ``n``.  The rungs are the widths the
# served path produces anyway — degraded reads (needle intervals, at
# most one 1 MiB small block), encode (1 MiB small blocks) and rebuild /
# large-block encode (4 MiB pipeline batches); anything wider pads to a
# multiple of the top rung.
COLUMN_LADDER = (256 << 10, 1 << 20, 4 << 20)
# One dispatch carries at most this many columns (B x n), or one job
# per device if that is more: bounds the device memory of a coalesced
# batch AND, with the ladder, the number of (B, n) programs the
# scheduler can ever ask for (shape_buckets below lists them).
MAX_DISPATCH_COLUMNS = 4 << 20

# the stages of a job, in the order it passes through them.  Caller
# thread: submit (contiguous copy, ladder pad, queue put) and result
# (future wait, copy out).  Launching thread: stack, then the mesh
# coder's pad / launch; collector thread: its fetch / unpack
# (ops/rs_mesh.STAGES), then demux.
CALLER_STAGES = ("submit", "result")
DISPATCH_STAGES = ("stack", "pad", "launch", "fetch", "unpack", "demux")
STAGES = CALLER_STAGES[:1] + DISPATCH_STAGES + CALLER_STAGES[1:]
# the launching thread's time, partitioned (stats()["loop_s"])
LOOP_PARTS = ("idle", "hold", "dispatch")
# An idle dispatcher ends its ec.batch.idle stage and begins a fresh one
# this often: a profiler records a stage only if it was running when the
# stage BEGAN, so a device trace started in the middle of a long idle
# (the seconds between two seals) names it from here on, not from the
# next job
IDLE_REARM_S = 0.2

# Mesh dispatches on the device at once: one whose result is being
# fetched and one whose operand is being copied in behind it.  The
# link carries both ways at once (PERF.md section 5: a copy in hides
# 0.68-0.98 of itself under the program and copy out before it), and a
# third would only queue behind the second.  A constant, not an option
IN_FLIGHT = 2

_STOP = object()
_CLASS_RANK = {c: i for i, c in enumerate(CLASSES)}


def bucket_columns(n: int) -> int:
    """The ladder rung a job of ``n`` columns pads up to."""
    for rung in COLUMN_LADDER:
        if n <= rung:
            return rung
    top = COLUMN_LADDER[-1]
    return -(-n // top) * top


def shape_buckets(max_batch: int = 64, n_devices: int = 1
                  ) -> list[tuple[int, int]]:
    """Every (B, n) the scheduler can hand the mesh for ladder-width
    jobs: B the padded batch (a power-of-two multiple of the device
    count), capped by ``max_batch`` and MAX_DISPATCH_COLUMNS.  The
    compile tests walk this list; it is the bound on compiled programs
    per kind."""
    out = []
    for n in COLUMN_LADDER:
        cap = max(n_devices, min(max_batch, MAX_DISPATCH_COLUMNS // n))
        b = n_devices
        while True:
            out.append((b, n))
            if b >= cap:
                break
            b *= 2
    return out


def _rank(cls: Optional[str]) -> int:
    # unknown/absent class sorts after background: un-classed work is
    # by definition not latency-sensitive
    return _CLASS_RANK.get(cls, len(CLASSES))


class _Job:
    __slots__ = ("scheme", "kind", "data", "mat", "n", "cls", "span",
                 "submitted", "future", "taken")

    def __init__(self, scheme: RSScheme, kind: str, data: np.ndarray,
                 mat: Optional[np.ndarray], n: int, cls: Optional[str],
                 span, submitted: float):
        self.scheme = scheme      # the geometry whose programs run it
        self.kind = kind          # "encode" | "rebuild"
        self.data = data          # (rows, bucket_columns(n)) uint8:
        #                           k rows, or as many as ``mat`` reads
        self.mat = mat            # rebuild only: (r, rows) uint8
        self.n = n                # original column count pre-padding
        self.cls = cls
        # the submitter's ambient span when it is sampled (else None),
        # captured like cls: ContextVars do not reach the dispatcher
        self.span = span
        self.submitted = submitted
        self.future: Future = Future()
        # set once the job has LEFT the queue for its launch (a place on
        # the device was free; or stop() drained it): nothing submitted
        # from then on can share its dispatch (BatchCoder.encode_begin)
        self.taken = threading.Event()


class _Geometry:
    """What the scheduler keeps per code geometry it has seen: the mesh
    coder and the host fallback of that scheme (each built when first
    needed, by the dispatcher thread) and its counters (``by_spec``;
    ``rows``: its jobs by the rows their operand carries)."""

    __slots__ = ("scheme", "spec", "mesh", "cpu", "counters", "rows")

    def __init__(self, scheme: RSScheme, mesh=None,
                 cpu: Optional[ErasureCoder] = None):
        self.scheme = scheme
        self.spec = code_spec_name(scheme)
        self.mesh = mesh
        self.cpu = cpu
        self.counters = dict.fromkeys(
            ("jobs", "mesh_dispatches", "overlapped_dispatches",
             "cpu_dispatches", "bytes_in", "bytes_out"), 0)
        self.rows: dict[int, int] = {}


class _Flown:
    """A mesh dispatch between its two halves: launched (stacked, padded,
    enqueued: the launching thread) and not yet collected (fetched,
    unpacked, demuxed: the collector)."""

    __slots__ = ("jobs", "g", "pending", "gen", "span", "t0",
                 "overlapped")

    def __init__(self, jobs: list, g: _Geometry, pending, gen: int,
                 span, t0: float, overlapped: bool):
        self.jobs = jobs
        self.g = g
        self.pending = pending    # the mesh coder's: .result()
        # coder_fallbacks when it was launched: stale once any dispatch
        # has failed since, and then it is not asked of the device
        self.gen = gen
        self.span = span          # its ec.batch.dispatch span, or None
        self.t0 = t0              # when its launch began
        # another dispatch was still on the device when this one's
        # launch began
        self.overlapped = overlapped


class _CallerCells:
    """Seconds and entries of the stages that run in the CALLERS'
    threads.  Each thread adds into a cell of its own (one writer, no
    lock); ``totals`` sums the cells.  Cells of threads that have ended
    are folded into ``_retired`` when a new thread registers (the only
    time the lock is taken), so worker churn does not grow the list."""

    def __init__(self):
        self._local = threading.local()
        self._lock = threading.Lock()
        self._retired = [0.0, 0] * len(CALLER_STAGES)
        self._cells: list[tuple[threading.Thread, list]] = []

    def cell(self) -> list:
        c = getattr(self._local, "cell", None)
        if c is None:
            c = self._local.cell = [0.0, 0] * len(CALLER_STAGES)
            with self._lock:
                live = []
                for t, old in self._cells:
                    if t.is_alive():
                        live.append((t, old))
                    else:
                        for i, v in enumerate(old):
                            self._retired[i] += v
                live.append((threading.current_thread(), c))
                self._cells = live
        return c

    def totals(self) -> list:
        with self._lock:
            out = list(self._retired)
            for _t, c in self._cells:
                for i, v in enumerate(c):
                    out[i] += v
        return out


class EcBatchScheduler:
    """The funnel.  Construct one per process (the volume server owns
    one); hand pipelines a BatchCoder facade over it, one per code
    geometry.  ``scheme`` is the geometry of a job submitted without
    one."""

    def __init__(self, scheme: RSScheme = DEFAULT_SCHEME, *,
                 mesh_coder=None, cpu_coder: Optional[ErasureCoder] = None,
                 max_batch: int = 64, queue_depth: int = 256,
                 cooldown_s: float = 30.0,
                 on_fallback: Optional[Callable[[str], None]] = None):
        self.scheme = scheme
        self.max_batch = max_batch
        self.cooldown_s = cooldown_s
        self._on_fallback = on_fallback
        self._q: "queue.Queue" = queue.Queue(maxsize=queue_depth)
        self.fallback_reason: Optional[str] = None
        self._mesh = mesh_coder
        self.compile_cache_dir: Optional[str] = None
        if self._mesh is None:
            # asked for the device: make_coder refuses to build a device
            # coder (raises with the reason) where JAX found only the
            # CPU and nobody named it — never serve from the CPU unseen
            from seaweedfs_tpu.models.coder import make_coder
            from seaweedfs_tpu.parallel import mesh as mesh_mod
            self._mesh = make_coder("mesh", scheme)
            self.compile_cache_dir = mesh_mod.ensure_compile_cache()
        # {"platform", "device_kind", "count"} of the devices the mesh
        # coder dispatches to (None for an injected coder that cannot say)
        report = getattr(self._mesh, "device_report", None)
        self.device: Optional[dict] = report() if report else None
        # the process's backend compiles (parallel/mesh.CompileWatch),
        # where the coder is a device coder that watches them
        self._compiles = getattr(self._mesh, "compile_watch", None)
        # per geometry: mesh coder, host fallback, counters.  Further
        # geometries are added by the dispatcher thread when their first
        # job arrives; stats() only reads
        self._geometries: dict[RSScheme, _Geometry] = {
            scheme: _Geometry(scheme, self._mesh, cpu_coder)}
        self._down_until = 0.0
        # every counter has ONE writer (the launching thread: what is
        # taken and launched; the collector: what came back from the
        # mesh) but the two a host dispatch touches, which either thread
        # can run (_fallback_lock); readers (stats/metrics) tolerate a
        # stale int
        self.jobs_total = 0
        self.batches_total = 0
        self.mesh_batches = 0
        # mesh dispatches of ONE job: not held, not copied (_launch)
        self.lone_dispatches = 0
        # mesh dispatches launched while another was still on the device:
        # over mesh_batches, how often the window of IN_FLIGHT engages
        self.overlapped_dispatches = 0
        self.cpu_batches = 0
        self.coder_fallbacks = 0
        self.max_coalesced = 0
        # per job width (a ladder rung, or a multiple of the top one):
        # {columns: [jobs, mesh dispatches, most jobs in one of them]},
        # and the groups cut by MAX_DISPATCH_COLUMNS
        self._by_rung: dict[int, list[int]] = {}
        self.cap_splits = 0
        # where the time goes (module docstring).  One writer a key:
        # the launching thread of stack and by_kind, the collector of
        # demux
        self.stage_s = dict.fromkeys(("stack", "demux"), 0.0)
        self.stage_n = dict.fromkeys(("stack", "demux"), 0)
        # the dispatcher's time, as it publishes it at every turn, in
        # one tuple so that a reader sees totals and the running part
        # together: (idle, hold, dispatch seconds done, index of the part
        # it is in, since when).  stats() adds the running part, so two
        # reads differ by the wall time between them however long the
        # dispatcher has sat in one part
        self._loop_pub = (0.0, 0.0, 0.0, 0, clockctl.monotonic())
        # per kind of job as submitted (an encode that carries its own
        # matrix rides as a rebuild): bytes_in = rows x the job's own
        # columns, bytes_padded = the same at its ladder rung (the batch
        # pad to a power of two is not in it), bytes_out = rows returned
        self.by_kind = {k: {"jobs": 0, "bytes_in": 0, "bytes_padded": 0,
                            "bytes_out": 0} for k in ("encode", "rebuild")}
        self._callers = _CallerCells()
        # RED-discipline wait histogram (submit -> dispatch, labelled by
        # QoS class) + coalescing-quality histogram; both ride stats()
        # as mergeable snapshots, same transport as the serving RED
        self.wait_hist = Histogram(
            "ec_batch_wait_seconds",
            "submit-to-dispatch queueing delay", ("class",),
            buckets=RED_BUCKETS)
        self.size_hist = Histogram(
            "ec_batch_coalesced_jobs",
            "jobs coalesced per dispatched batch",
            buckets=BATCH_SIZE_BUCKETS)
        self._stopped = False
        # the dispatches on the device, oldest first: appended by the
        # launching thread, taken off by the collector when it has
        # fetched one (never more than IN_FLIGHT); ``_launching`` goes
        # False when the launching thread ends
        self._flight: collections.deque = collections.deque()
        self._flight_cv = threading.Condition()
        self._launching = True
        # the two threads meet only where a dispatch goes to the host
        # coder: the fallback's bookkeeping and the host dispatches' count
        self._fallback_lock = threading.Lock()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="ec-batcher")
        self._collector = threading.Thread(target=self._collect_loop,
                                           daemon=True,
                                           name="ec-batch-collect")
        self._thread.start()
        self._collector.start()

    # ---- submission (any thread) ----

    def _submit(self, kind: str, data: np.ndarray,
                mat: Optional[np.ndarray], cls: Optional[str],
                scheme: Optional[RSScheme]) -> _Job:
        if self._stopped:
            raise RuntimeError("EC batch scheduler is stopped")
        if scheme is None:
            scheme = self.scheme
        span = tracing.current_span()
        if span is not None and not span.sampled:
            span = None
        with tracing.stage("ec.batch.submit") as st:
            if st.span is not None:
                st.annotate("spec", code_spec_name(scheme))
                st.annotate("rows", data.shape[0])
            data = np.ascontiguousarray(data, dtype=np.uint8)
            n = data.shape[1]
            pad = bucket_columns(n) - n
            if pad:
                data = np.concatenate(
                    [data, np.zeros((data.shape[0], pad), dtype=np.uint8)],
                    axis=1)
            if cls is None:
                cls = current_class()
            job = _Job(scheme, kind, data, mat, n, cls, span,
                       clockctl.monotonic())
            self._q.put(job)  # bounded: blocks -> backpressure
        self.note_caller(0, st.elapsed)
        return job

    def note_caller(self, stage: int, seconds: float) -> None:
        """Add to a caller-thread stage (index into CALLER_STAGES)."""
        c = self._callers.cell()
        c[2 * stage] += seconds
        c[2 * stage + 1] += 1

    def submit_encode(self, data: np.ndarray,
                      cls: Optional[str] = None,
                      mat: Optional[np.ndarray] = None,
                      scheme: Optional[RSScheme] = None) -> Future:
        """(k, n) uint8 -> Future of (m, n) uint8 parity: the parity of
        ``scheme`` (the scheduler's own when None; plain RS or a family
        such as LrcScheme), by that scheme's static-matrix program.
        Pass ``mat`` — an (m, k) GF(256) matrix — to apply a matrix of
        the caller's own instead: matrix-carrying encodes ride the
        per-job-matrix path of rebuilds (parity IS mat @ data over
        GF(256)), beside the scheme's rebuilds, and every future demuxes
        exactly its own rows."""
        return self._encode_job(data, cls, mat, scheme).future

    def _encode_job(self, data: np.ndarray, cls: Optional[str],
                    mat: Optional[np.ndarray],
                    scheme: Optional[RSScheme]) -> _Job:
        if mat is not None:
            return self._submit("rebuild", data,
                                np.ascontiguousarray(mat, dtype=np.uint8),
                                cls, scheme)
        return self._submit("encode", data, None, cls, scheme)

    def submit_rebuild(self, srcdata: np.ndarray, rebuild_mat: np.ndarray,
                       cls: Optional[str] = None,
                       scheme: Optional[RSScheme] = None) -> Future:
        """(rows, n) survivors + (r, rows) rebuild matrix -> Future of
        (r, n) recovered rows: for plain RS the first k present shards,
        for a family's plan the sources it reads (rows <= k)."""
        return self._submit("rebuild", srcdata,
                            np.ascontiguousarray(rebuild_mat,
                                                 dtype=np.uint8), cls,
                            scheme).future

    def encode(self, data: np.ndarray, cls: Optional[str] = None,
               mat: Optional[np.ndarray] = None,
               scheme: Optional[RSScheme] = None) -> np.ndarray:
        return self.submit_encode(data, cls, mat, scheme).result()

    def rebuild(self, srcdata: np.ndarray, rebuild_mat: np.ndarray,
                cls: Optional[str] = None,
                scheme: Optional[RSScheme] = None) -> np.ndarray:
        return self.submit_rebuild(srcdata, rebuild_mat, cls,
                                   scheme).result()

    # ---- dispatcher ----

    def _loop(self) -> None:
        # the launching thread's time, partitioned: every instant since
        # the thread started is in exactly one of idle (blocked on an
        # empty queue), hold (holding >= 1 job: waiting for a place on
        # the device, then draining what queued up beside it) and
        # dispatch (stack, pad, launch; a host dispatch whole)
        done = [0.0, 0.0, 0.0]      # idle, hold, dispatch (LOOP_PARTS)
        t = self._loop_pub[4]

        def turn(part: int) -> None:
            """The dispatcher leaves ``part`` for the next one."""
            nonlocal t
            now = clockctl.monotonic()
            done[part] += now - t
            t = now
            self._loop_pub = (done[0], done[1], done[2],
                              (part + 1) % 3, now)

        try:
            while True:
                st = tracing.stage_begin("ec.batch.idle")
                try:
                    job = self._q.get(timeout=IDLE_REARM_S)
                except queue.Empty:
                    job = None
                tracing.stage_end(st)
                if job is None:
                    continue
                turn(0)
                if job is _STOP:
                    return
                batch = [job]
                stopping = False
                st = tracing.stage_begin("ec.batch.hold")
                # nothing is WAITED for but a place on the device: what
                # queued up while both were taken rides this dispatch,
                # and a job that finds one free leaves at once
                self._wait_for_place()
                while len(batch) < self.max_batch:
                    try:
                        nxt = self._q.get_nowait()
                    except queue.Empty:
                        break
                    if nxt is _STOP:
                        stopping = True
                        break
                    batch.append(nxt)
                tracing.stage_end(st)
                turn(1)
                self._dispatch(batch)
                turn(2)
                if stopping:
                    return
        finally:
            with self._flight_cv:
                self._launching = False     # the collector drains, then ends
                self._flight_cv.notify_all()

    def _wait_for_place(self) -> None:
        """Block while IN_FLIGHT dispatches are on the device.  Only the
        launching thread adds one, so a place found free stays free."""
        with self._flight_cv:
            while len(self._flight) >= IN_FLIGHT:
                self._flight_cv.wait()

    def _mesh_healthy(self) -> bool:
        return clockctl.monotonic() >= self._down_until

    def _dispatch(self, batch: list) -> None:
        self.jobs_total += len(batch)
        self.batches_total += 1
        self.max_coalesced = max(self.max_coalesced, len(batch))
        now = clockctl.monotonic()
        for j in batch:
            j.taken.set()
            # in a device trace: a marker on this thread's line where the
            # job's wait ends (a TraceMe cannot be back-dated); the
            # wait's length is the span below and wait_hist
            with tracing.stage("ec.batch.wait"):
                self.wait_hist.observe(max(0.0, now - j.submitted),
                                       j.cls or "-")
            if j.span is not None:
                j.span.record("ec.batch.wait", j.submitted, now)
            bk = self.by_kind[j.kind]
            g = self._geometry(j.scheme)
            bs = g.counters
            rows = j.data.shape[0]
            g.rows[rows] = g.rows.get(rows, 0) + 1
            rows_out = j.scheme.parity_shards if j.mat is None \
                else j.mat.shape[0]
            bk["jobs"] += 1
            bs["jobs"] += 1
            bk["bytes_in"] += rows * j.n
            bs["bytes_in"] += rows * j.n
            bk["bytes_padded"] += rows * j.data.shape[1]
            bk["bytes_out"] += rows_out * j.n
            bs["bytes_out"] += rows_out * j.n
            self._rung(j.data.shape[1])[0] += 1
        self.size_hist.observe(len(batch))
        # QoS ordering: a group containing an interactive job dispatches
        # before an all-background group.  It orders what one batch
        # holds: a job that arrives while IN_FLIGHT dispatches of another
        # class are on the device waits both out (at seal sizes ~9 ms,
        # where it was one dispatch, ~4.5 ms, before the window of two)
        batch.sort(key=lambda j: (_rank(j.cls), j.submitted))
        groups: dict[tuple, list] = {}
        for j in batch:
            groups.setdefault((j.scheme, j.kind) + j.data.shape,
                              []).append(j)
        # profiler attribution: the dispatcher thread does the batch's
        # work, so samples land under the batch's best (first) class
        with profiler.scope(cls=batch[0].cls or "background",
                            route="ec-batch"):
            for jobs in groups.values():
                # one dispatch carries at most MAX_DISPATCH_COLUMNS,
                # or a job per device
                step = max(self._mesh.n_devices, MAX_DISPATCH_COLUMNS
                           // max(1, jobs[0].data.shape[1]))
                if len(jobs) > step:
                    self.cap_splits += 1
                for i in range(0, len(jobs), step):
                    self._run_group(jobs[i:i + step])

    def _rung(self, columns: int) -> list[int]:
        return self._by_rung.setdefault(columns, [0, 0, 0])

    def _geometry(self, scheme: RSScheme) -> _Geometry:
        g = self._geometries.get(scheme)
        if g is None:
            g = self._geometries[scheme] = _Geometry(scheme)
        return g

    def _mesh_compatible(self, jobs: list) -> bool:
        # a geometry's programs are traced for (<=k, <=m)-shaped work
        # (the mesh coder fits an operand of fewer rows to its apply
        # widths); anything else is a routing decision, not a mesh
        # failure — send it to the CPU coder without benching the mesh
        j = jobs[0]  # groups share scheme and data.shape by construction
        rows, k = j.data.shape[0], j.scheme.data_shards
        if rows > k or (j.kind == "encode" and rows != k):
            return False
        return all(jj.mat is None
                   or jj.mat.shape[0] <= j.scheme.parity_shards
                   for jj in jobs)

    def _run_group(self, jobs: list) -> None:
        """One dispatch: launched onto the device for the collector to
        finish, or (a shape the mesh does not take, a benched mesh, a
        launch that raised) done here on the host coder."""
        g = self._geometry(jobs[0].scheme)
        if self._mesh_healthy() and self._mesh_compatible(jobs):
            # read before the wait: a failure met while this waits makes
            # the generation stale, and the collector sends a dispatch of
            # a stale generation to the host without asking the device
            gen = self.coder_fallbacks
            self._wait_for_place()
            # is one still on the device as this launch begins
            overlapped = bool(self._flight)
            try:
                if g.mesh is None:
                    # a further geometry's first job: one more MeshCoder,
                    # on the same device mesh
                    from seaweedfs_tpu.ops.rs_mesh import MeshCoder
                    g.mesh = MeshCoder(g.scheme, mesh=getattr(
                        self._mesh, "mesh", None))
                flown = self._launch(jobs, g, gen, overlapped)
            except Exception as e:  # noqa: BLE001 — the fallback ladder
                self._fell_back(e, gen)
            else:
                with self._flight_cv:
                    self._flight.append(flown)
                    self._flight_cv.notify_all()
                return
        self._run_host(jobs, g)

    def _fell_back(self, e: Exception, gen: int) -> None:
        """A mesh dispatch launched at fallback generation ``gen`` raised
        (at its launch or at its collect): count it, bench the mesh and
        tell the observer — once a failure, whichever thread met it, and
        not again for the dispatches that were in flight beside it."""
        with self._fallback_lock:
            if gen != self.coder_fallbacks:
                return
            from seaweedfs_tpu.parallel import mesh as mesh_mod
            self.coder_fallbacks += 1
            self.fallback_reason = mesh_mod.classify_failure(repr(e))
            self._down_until = clockctl.monotonic() + self.cooldown_s
        glog.warning(
            "EC batcher: mesh dispatch failed (%s: %s); draining "
            "through the CPU coder for %.0fs", type(e).__name__,
            e, self.cooldown_s)
        if self._on_fallback is not None:
            try:
                self._on_fallback(self.fallback_reason or "error")
            except Exception:  # noqa: BLE001 — observer only
                pass

    def _staged(self, key: str, st: tracing.stage) -> None:
        self.stage_s[key] += st.elapsed
        self.stage_n[key] += 1

    def _launch(self, jobs: list, g: _Geometry, gen: int,
                overlapped: bool) -> _Flown:
        """The first half of a mesh dispatch, on the launching thread:
        stack, then the mesh coder's pad and launch."""
        kind = jobs[0].kind
        # one ec.batch.dispatch span per dispatch, under the first
        # sampled job, from here to the end of its demux; its stages
        # (and the mesh coder's) nest in it, on whichever thread
        lead = next((j.span for j in jobs if j.span is not None), None)
        span = lead.child("ec.batch.dispatch", "internal") \
            if lead is not None else None
        compiles = self._compiles
        n_compiled = compiles.n if compiles is not None else 0
        # in a device trace ``ec.batch.dispatch`` is this half, on this
        # thread's line, and ``ec.batch.collect`` the other, on the
        # collector's
        with tracing.stage("ec.batch.dispatch") as disp:
            tok = tracing.attach(span)
            try:
                with tracing.stage("ec.batch.stack") as st:
                    # one job is its own batch: a view of the buffer
                    # submit made contiguous and padded, not a copy
                    stacked = jobs[0].data[None] if len(jobs) == 1 \
                        else np.stack([j.data for j in jobs])
                self._staged("stack", st)
                if kind == "encode":
                    pending = g.mesh.encode_batch_begin(stacked)
                else:
                    pending = g.mesh.rebuild_batch_begin(
                        stacked, [j.mat for j in jobs])
            except BaseException as e:
                if span is not None:
                    span.finish(status=500,
                                error=f"{type(e).__name__}: {e}")
                raise
            finally:
                tracing.detach(tok)
        if compiles is not None and compiles.n != n_compiled:
            # which step recompiled: the warm-up (or the ladder)
            # missed this shape and a request paid for it
            glog.warning(
                "EC batcher: %d program(s) compiled or loaded "
                "inside a dispatch: %s %s of shape %s",
                compiles.n - n_compiled, g.spec, kind, stacked.shape)
        if span is not None:
            span.annotate("spec", g.spec)
            span.annotate("kind", kind)
            span.annotate("shape", list(stacked.shape))
            span.annotate("rows", stacked.shape[1])
        return _Flown(jobs, g, pending, gen, span, disp.t0, overlapped)

    def _collect_loop(self) -> None:
        """The collector thread: the second half of every mesh dispatch,
        in the order they were launched."""
        while True:
            with self._flight_cv:
                while not self._flight:
                    if not self._launching:
                        return
                    self._flight_cv.wait()
                flown = self._flight[0]
            with profiler.scope(cls=flown.jobs[0].cls or "background",
                                route="ec-batch"), \
                    tracing.stage("ec.batch.collect"):
                self._collect(flown)

    def _collect(self, flown: _Flown) -> None:
        jobs, g, span = flown.jobs, flown.g, flown.span
        out = None
        tok = tracing.attach(span)
        try:
            try:
                try:
                    # a dispatch that was in flight when another failed
                    # is not asked of the device: it goes the same way
                    if flown.gen == self.coder_fallbacks:
                        out = flown.pending.result()
                finally:
                    # off the device either way: its place is free
                    with self._flight_cv:
                        self._flight.popleft()
                        self._flight_cv.notify_all()
                if out is not None:
                    with tracing.stage("ec.batch.demux") as st:
                        rows = [np.ascontiguousarray(out[i][:, :j.n])
                                for i, j in enumerate(jobs)]
                        for j, r in zip(jobs, rows):
                            j.future.set_result(r)
                    self._staged("demux", st)
            except Exception as e:  # noqa: BLE001 — the fallback ladder
                self._fell_back(e, flown.gen)
                out = None
            if out is None:
                self._run_host(jobs, g)
        finally:
            tracing.detach(tok)
        t1 = clockctl.monotonic()
        if span is not None:
            span.finish(status=200 if out is not None else 500)
            # the other sampled jobs of the dispatch each get a child
            # that names the one dispatch span by its id
            for j in jobs:
                if j.span is not None and j.span.span_id != span.parent_id:
                    j.span.record("ec.batch.dispatch", flown.t0, t1,
                                  {"dispatch_id": span.span_id,
                                   "jobs": len(jobs), "spec": g.spec,
                                   "rows": jobs[0].data.shape[0]})
        if out is None:
            return
        # counted once its futures are set
        self.mesh_batches += 1
        g.counters["mesh_dispatches"] += 1
        rung = self._rung(jobs[0].data.shape[1])
        rung[1] += 1
        rung[2] = max(rung[2], len(jobs))
        if len(jobs) == 1:
            self.lone_dispatches += 1
        if flown.overlapped:
            self.overlapped_dispatches += 1
            g.counters["overlapped_dispatches"] += 1

    def _run_host(self, jobs: list, g: _Geometry) -> None:
        """A dispatch on the host coder, whole, on the calling thread
        (the launching thread's, or the collector's after a failure)."""
        with self._fallback_lock:
            self.cpu_batches += 1
            g.counters["cpu_dispatches"] += 1
        self._run_cpu(jobs)

    def _run_cpu(self, jobs: list) -> None:
        for j in jobs:
            try:
                g = self._geometry(j.scheme)
                with self._fallback_lock:
                    if g.cpu is None:
                        g.cpu = host_coder(g.scheme, threaded=True)
                cpu = g.cpu
                if j.kind == "encode":
                    out = np.asarray(cpu.encode_array(j.data))
                else:
                    out = np.asarray(cpu.reconstruct_rows(j.data, j.mat))
                j.future.set_result(np.ascontiguousarray(out[:, :j.n]))
            except BaseException as e:  # noqa: BLE001 — per-job demux
                j.future.set_exception(e)

    # ---- lifecycle / observability ----

    def stop(self) -> None:
        """Stop the dispatcher: what is in flight is collected, anything
        still queued drains through the CPU coder, so no submitted
        future is ever abandoned."""
        if self._stopped:
            return
        self._stopped = True
        self._q.put(_STOP)
        self._thread.join(timeout=10)
        # what is on the device is collected before the collector ends
        self._collector.join(timeout=10)
        leftovers = []
        while True:
            try:
                j = self._q.get_nowait()
            except queue.Empty:
                break
            if j is not _STOP:
                j.taken.set()
                leftovers.append(j)
        if leftovers:
            self._run_cpu(leftovers)
            self.cpu_batches += 1

    def stats(self) -> dict:
        stage_s = dict(self.stage_s)
        stage_n = dict(self.stage_n)
        programs = None
        output_spread: dict = {}
        # per geometry that has had a job, keyed by its spec ("rs-6-3"):
        # jobs add up to jobs_total, the dispatches to mesh_batches and
        # cpu_batches, programs to programs_compiled
        by_spec = {}
        for g in list(self._geometries.values()):
            # pad / launch / fetch / unpack are the mesh coders' own
            for k, v in (getattr(g.mesh, "stage_s", None) or {}).items():
                stage_s[k] = stage_s.get(k, 0.0) + v
            for k, v in (getattr(g.mesh, "stage_n", None) or {}).items():
                stage_n[k] = stage_n.get(k, 0) + v
            for k, v in (getattr(g.mesh, "output_spread", None)
                         or {}).items():
                output_spread[k] = output_spread.get(k, 0) + v
            mine = getattr(g.mesh, "programs", None)
            if mine is not None:
                programs = (programs or 0) + len(mine)
            if g.counters["jobs"]:
                by_spec[g.spec] = {
                    **g.counters, "programs": len(mine or ()),
                    "rows": {str(r): n for r, n in sorted(g.rows.items())}}
        callers = self._callers.totals()
        for i, name in enumerate(CALLER_STAGES):
            stage_s[name], stage_n[name] = callers[2 * i:2 * i + 2]
        compiles = self._compiles
        pub = self._loop_pub
        loop_s = dict(zip(LOOP_PARTS, pub[:3]))
        if self._thread.is_alive():
            loop_s[LOOP_PARTS[pub[3]]] += max(
                0.0, clockctl.monotonic() - pub[4])
        return {
            "max_batch": self.max_batch,
            "queue_depth": self._q.maxsize,
            "queued": self._q.qsize(),
            "mesh_devices": self._mesh.n_devices,
            "device": self.device,
            # distinct (geometry, kind, B, k, n) THIS scheduler's coders
            # have dispatched.  Not a count of compiles (the jitted
            # functions are per process: a shape a warm-up ran first is
            # counted here though nothing compiled) — that is
            # backend_compiles
            "programs_compiled": programs,
            "backend_compiles": compiles.n
            if compiles is not None else None,
            "backend_compile_s": compiles.seconds
            if compiles is not None else None,
            "compile_cache_dir": self.compile_cache_dir,
            # {devices an output was spread over: dispatches}
            "output_spread": output_spread,
            "mesh_healthy": self._mesh_healthy(),
            "jobs_total": self.jobs_total,
            "batches_total": self.batches_total,
            "mesh_batches": self.mesh_batches,
            "lone_dispatches": self.lone_dispatches,
            "overlapped_dispatches": self.overlapped_dispatches,
            "cpu_batches": self.cpu_batches,
            "coder_fallbacks": self.coder_fallbacks,
            "max_coalesced": self.max_coalesced,
            "fallback_reason": self.fallback_reason,
            "wait_hist": self.wait_hist.snapshot(),
            "size_hist": self.size_hist.snapshot(),
            "by_kind": {k: dict(v) for k, v in self.by_kind.items()},
            "by_spec": by_spec,
            # keyed by the jobs' columns: the jobs add up to jobs_total,
            # the dispatches to mesh_batches
            "by_rung": {str(n): dict(zip(
                ("jobs", "mesh_dispatches", "max_coalesced"), r))
                for n, r in sorted(self._by_rung.items())},
            "cap_splits": self.cap_splits,
            "stage_s": {k: stage_s.get(k, 0.0) for k in STAGES},
            "stage_n": {k: stage_n.get(k, 0) for k in STAGES},
            "loop_s": loop_s,
        }


class _QueuedEncode:
    """An encode on the device queue (``BatchCoder.encode_begin``): the
    wait, the copy out and the caller's ``result`` stage happen when the
    result is asked for."""

    __slots__ = ("_coder", "_fut", "_out")

    def __init__(self, coder: "BatchCoder", fut: Future, out: np.ndarray):
        self._coder, self._fut, self._out = coder, fut, out

    def done(self) -> bool:
        return self._fut.done()

    def result(self) -> np.ndarray:
        return self._coder._result(self._fut, self._out)


class BatchCoder(ErasureCoder):
    """ErasureCoder facade of ONE scheme over an EcBatchScheduler — a
    drop-in for the Store/pipeline coder seam.  Each pipeline keeps
    calling encode_begin/reconstruct_rows per block-group as for any
    coder; the facade turns those calls into scheduler submissions, so N
    concurrent volume pipelines coalesce into device-sized mesh batches
    without knowing about each other, and a pipeline's begun batch is
    launched onto the device behind the one before it.

    A scheduler serves as many facades as there are schemes among the
    store's volumes (``for_scheme``).  A scheme submits under ITSELF as
    the queue's key, plain RS of any (k, m) and another code FAMILY
    (LrcScheme) alike: encodes run the scheme's own static-matrix
    program, rebuilds its apply program.  Rebuild matrices (and, for a
    family that plans its sources, the plan: ``plan_rebuild``, the
    sources a repair reads and its matrix over them) come from the
    scheme's host coder; a planned job carries only the rows it reads."""

    def __init__(self, scheduler: EcBatchScheduler,
                 scheme: Optional[RSScheme] = None):
        if scheme is None:
            scheme = scheduler.scheme
        super().__init__(scheme)
        self.scheduler = scheduler
        # the scheme's own host coder, for matrix derivation only
        self._host = host_coder(scheme, threaded=False)
        if hasattr(self._host, "plan_rebuild"):
            self.plan_rebuild = self._host.plan_rebuild
        # per calling thread: ``taken`` of the job its last encode_begin
        # submitted (the event alone: the job holds its buffers)
        self._begun = threading.local()

    def for_scheme(self, scheme: RSScheme) -> ErasureCoder:
        """A facade over the SAME scheduler, whatever the scheme's
        family: every volume of the store submits to the one device
        queue, under its own scheme."""
        if scheme == self.scheme:
            return self
        return BatchCoder(self.scheduler, scheme)

    def device_report(self) -> Optional[dict]:
        return self.scheduler.device

    def _result(self, fut: Future,
                out: Optional[np.ndarray] = None) -> np.ndarray:
        """Wait for a submitted job and (with ``out``) copy its rows
        out: the caller's last stage."""
        with tracing.stage("ec.batch.result") as st:
            rec = fut.result()
            if out is not None:
                out[:] = rec
                rec = out
        self.scheduler.note_caller(1, st.elapsed)
        return rec

    def _encode(self, data: np.ndarray,
                out: Optional[np.ndarray] = None) -> np.ndarray:
        return self._result(self.scheduler.submit_encode(
            data, scheme=self.scheme), out)

    def _rebuild(self, src: np.ndarray, mat: np.ndarray,
                 out: Optional[np.ndarray] = None) -> np.ndarray:
        return self._result(self.scheduler.submit_rebuild(
            src, mat, scheme=self.scheme), out)

    def encode_array(self, data: np.ndarray) -> np.ndarray:
        return self._encode(data)

    def encode_into(self, data: np.ndarray, out: np.ndarray) -> np.ndarray:
        return self.encode_begin(data, out).result()

    def encode_begin(self, data: np.ndarray,
                     out: np.ndarray) -> _QueuedEncode:
        """Submit now, wait and copy out when asked: the caller may begin
        its next batch while the device has this one.  Not before this
        caller's previous job has LEFT the queue for its launch, though:
        a lone pipeline keeps two jobs in the coder (on the device, when
        both places were free) and never two in the queue, so its jobs
        cannot share a dispatch (a B = 2 program that nothing warmed:
        ~2 s of compile inside a seal) whatever the threads' timing.  In
        step the wait is over long before it is asked for."""
        last = getattr(self._begun, "taken", None)
        if last is not None:
            last.wait()
        job = self.scheduler._encode_job(data, None, None, self.scheme)
        self._begun.taken = job.taken
        return _QueuedEncode(self, job.future, out)

    def encode(self, shards: Sequence[bytes]) -> list[bytes]:
        k = self.scheme.data_shards
        data = np.stack([np.frombuffer(bytes(shards[i]), dtype=np.uint8)
                         for i in range(k)])
        parity = self._encode(data)
        return [bytes(shards[i]) for i in range(k)] + \
            [parity[i].tobytes() for i in range(self.scheme.parity_shards)]

    def rebuild_matrix(self, present: Sequence[int],
                       missing: Sequence[int]) -> np.ndarray:
        return self._host.rebuild_matrix(present, missing)

    def job_rows(self, n: int, rows: Optional[int] = None) -> np.ndarray:
        """A zeroed (rows, rung) operand for a job of ``n`` columns:
        ``rows`` as many as the job's matrix reads (a plan's sources), k
        when not given.  A caller that fills rows[:, :n] in place (a
        degraded read's survivors) hands submit a buffer that is
        contiguous and on the ladder already: nothing is stacked, padded
        or copied on the way to the device.  The rows come back
        rung-wide; columns past ``n`` are zero in, zero out."""
        return np.zeros((rows or self.scheme.data_shards,
                         bucket_columns(n)), dtype=np.uint8)

    def reconstruct_rows(self, srcdata: np.ndarray,
                         rebuild_mat: np.ndarray,
                         out: Optional[np.ndarray] = None) -> np.ndarray:
        return self._rebuild(srcdata, rebuild_mat, out)

    def _rebuild_from(self, shards: Sequence[Optional[bytes]],
                      missing: Sequence[int]) -> list[Optional[bytes]]:
        """``shards`` with the ``missing`` ones filled in by one job: from
        the sources the family's plan names where it plans (LRC: the
        first k of sorted(present) can be rank-deficient for it), else
        from the first k present shards."""
        k, total = self.scheme.data_shards, self.scheme.total_shards
        present = [i for i in range(total) if shards[i] is not None]
        out = [bytes(s) if s is not None else None for s in shards]
        if not missing:
            return out
        plan = getattr(self, "plan_rebuild", None)
        if plan is not None:
            src_sids, mat = plan(present, missing)
        elif len(present) < k:
            raise ValueError(f"too few shards: {len(present)} < {k}")
        else:
            src_sids, mat = present[:k], \
                self.rebuild_matrix(present, missing)
        src = np.stack([np.frombuffer(bytes(shards[i]), dtype=np.uint8)
                        for i in src_sids])
        rec = self._rebuild(src, mat)
        for r, i in enumerate(missing):
            out[i] = rec[r].tobytes()
        return out

    def reconstruct(self, shards: Sequence[Optional[bytes]]) -> list[bytes]:
        return self._rebuild_from(
            shards, [i for i, s in enumerate(shards) if s is None])

    def reconstruct_data(self, shards: Sequence[Optional[bytes]]
                         ) -> list[Optional[bytes]]:
        return self._rebuild_from(
            shards, [i for i in range(self.scheme.data_shards)
                     if shards[i] is None])
