"""Mesh-sharded EC coder + cross-volume batch scheduler.

Four layers:

1. MeshCoder (ops/rs_mesh.py) — batched encode/rebuild bit-identical to
   CpuCoder, heterogeneous loss patterns in one dispatch, odd batch
   sizes zero-padded to the device-count multiple, the scalar
   ErasureCoder API, and registry wiring;
2. EcBatchScheduler (parallel/batcher.py) — a lone job leaves at once
   (not held, not copied), coalescing behind a busy dispatch, per-job
   demux, QoS-class ordering, the LOAD-BEARING CPU fallback: a mesh that
   raises mid-run drains every queued job through CpuCoder
   bit-identically, increments coder_fallbacks, classifies the reason
   and benches the mesh for the cooldown;
3. the volume-server seam — ec_batcher=True routes a real ec.encode
   through the scheduler (jobs counted at /admin/ec/batcher) and the
   encoded volume still reads back;
4. the device-scaling measurement — well-formed + bit-identical under
   tier-1's virtual devices; the >=1.6x 1->2 floor binds (slow-marked)
   only on real multi-device hardware, because virtual host-platform
   devices time-slice one CPU and cannot scale wall-clock.

conftest.py forces 8 virtual CPU devices, so every mesh path here runs
genuinely sharded.
"""

import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.models.coder import DEFAULT_SCHEME, make_coder
from seaweedfs_tpu.ops.rs_cpu import CpuCoder
from seaweedfs_tpu.ops.rs_mesh import MeshCoder
from seaweedfs_tpu.parallel import mesh as mesh_mod
from seaweedfs_tpu.parallel.batcher import (COLUMN_LADDER, BatchCoder,
                                            EcBatchScheduler)
from seaweedfs_tpu.qos import BACKGROUND, INTERACTIVE, class_scope

CPU = CpuCoder(DEFAULT_SCHEME)
K = DEFAULT_SCHEME.data_shards
M = DEFAULT_SCHEME.parity_shards
TOTAL = DEFAULT_SCHEME.total_shards


def _batch(b, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(b, K, n), dtype=np.uint8)


# --------------------------------------------------------- MeshCoder

def test_mesh_discovery_and_probe_cached():
    assert mesh_mod.device_count() >= 2  # conftest forces 8 virtual
    p1 = mesh_mod.probe()
    assert p1["ok"] and p1["n_devices"] >= 2
    assert p1["fallback_reason"] is None
    assert mesh_mod.probe() == p1  # cached


def test_classify_failure_vocabulary():
    assert mesh_mod.classify_failure(None) is None
    assert mesh_mod.classify_failure("jax device_put rejected") == \
        "device_put"
    assert mesh_mod.classify_failure("DeadlineExceeded: timeout") == \
        "timeout"
    assert mesh_mod.classify_failure("boom") == "probe_error"


def test_mesh_coder_registered():
    assert isinstance(make_coder("mesh"), MeshCoder)


MIB = 1 << 20


@pytest.mark.parametrize("n_devices,B,n", [
    (nd, B, MIB) for nd in (1, 2, 4, 8) for B in (1, 3)] + [(8, 5, 4096)])
def test_encode_batch_bit_identical_odd_batch(n_devices, B, n):
    """A batch smaller than, or not a multiple of, the device count is
    zero-padded up to it (B=1 and 3 on 2, 4, 8 devices; B=3 on one is
    padded to the next power of two): the pad lanes never leak."""
    mc = MeshCoder(DEFAULT_SCHEME, n_devices=n_devices)
    data = _batch(B, n)
    out = mc.encode_batch(data)
    assert out.shape == (B, M, n)
    for i in range(B):
        assert np.array_equal(out[i], CPU.encode_array(data[i]))
    (spread,) = mc.output_spread       # one dispatch, on every device
    assert spread == n_devices


@pytest.mark.parametrize("n_devices", [1, 8])
@pytest.mark.parametrize("drop", [(0, 3, 7, 9), (10, 11, 12, 13),
                                  (0, 5, 11, 13), (9,)])
def test_rebuild_batch_heterogeneous_loss_one_dispatch(drop, n_devices):
    """Jobs with DIFFERENT survivor patterns (the case's own — data-only,
    parity-only, mixed, single-shard — beside a lost data shard and a
    lost parity shard) ride one traced-coefficient dispatch, at 1 MiB a
    shard."""
    mc = MeshCoder(DEFAULT_SCHEME, n_devices=n_devices)
    losses = [drop, (2,), (12,)]
    data = _batch(len(losses), MIB, seed=1)
    srcs, mats, want = [], [], []
    for i, lost in enumerate(losses):
        full = np.concatenate([data[i], CPU.encode_array(data[i])])
        present = [j for j in range(TOTAL) if j not in lost]
        srcs.append(full[present[:K]])
        mats.append(CPU.rebuild_matrix(present, list(lost)))
        want.append(full[list(lost)])
    recs = mc.rebuild_batch(np.stack(srcs), mats)
    assert sum(mc.output_spread.values()) == 1
    for rec, expect in zip(recs, want):
        assert np.array_equal(rec, expect)


def test_mesh_coder_scalar_bytes_api():
    rng = np.random.default_rng(2)
    mc = MeshCoder(DEFAULT_SCHEME)
    shards = [rng.integers(0, 256, 997, dtype=np.uint8).tobytes()
              for _ in range(K)]
    full = mc.encode(shards)
    assert [bytes(s) for s in full] == \
        [bytes(s) for s in CPU.encode(shards)]
    holes = [s if i not in (0, 5, 12) else None
             for i, s in enumerate(full)]
    assert [bytes(s) for s in mc.reconstruct(holes)] == \
        [bytes(s) for s in full]
    dr = mc.reconstruct_data(
        [s if i != 3 else None for i, s in enumerate(full)])
    assert bytes(dr[3]) == bytes(full[3])


# -------------------------------------------------- EcBatchScheduler

class _Begun:
    """What a stand-in's ``*_batch_begin`` hands back."""

    def __init__(self, result):
        self.result = result


class _AtLaunch:
    """The scheduler's two-step seam for a stand-in that does its work
    whole in the begin: the launching thread is inside it meanwhile, as
    the one dispatcher thread was until PR 36."""

    def encode_batch_begin(self, b):
        out = self.encode_batch(b)
        return _Begun(lambda: out)

    def rebuild_batch_begin(self, s, mats):
        out = self.rebuild_batch(s, mats)
        return _Begun(lambda: out)


class _Recorder(_AtLaunch):
    """Mesh stand-in that records the operands it is handed, in dispatch
    order, and answers via CPU."""
    n_devices = 1

    def __init__(self):
        self.seen = []

    @property
    def shapes(self):
        return [b.shape for b in self.seen]

    def encode_batch(self, b):
        self.seen.append(b)
        return np.stack([CPU.encode_array(x) for x in b])

    def rebuild_batch(self, s, mats):
        self.seen.append(s)
        return [CPU.reconstruct_rows(s[i], mats[i])
                for i in range(s.shape[0])]


class _Gated(_AtLaunch):
    """A mesh coder behind a gate: every dispatch says it has ``entered``
    and then waits for the gate, in its LAUNCH.  What the scheduler is
    handed while one launch is held shut queues up behind it — the
    tests' way to make coalescing deterministic, now that the scheduler
    waits for nobody."""

    def __init__(self, inner):
        self.inner = inner
        self.n_devices = inner.n_devices
        self.gate = threading.Event()
        self.entered = threading.Event()

    def __getattr__(self, name):        # stage_s, programs, device_report...
        return getattr(self.inner, name)

    def _held(self):
        self.entered.set()
        assert self.gate.wait(60)

    def encode_batch(self, b):
        self._held()
        return self.inner.encode_batch(b)

    def rebuild_batch(self, s, mats):
        self._held()
        return self.inner.rebuild_batch(s, mats)

    def plug(self, sched):
        """Submit one job and wait until its dispatch is held shut."""
        fut = sched.submit_encode(_batch(1, 8, seed=99)[0])
        assert self.entered.wait(60)
        return fut


@pytest.mark.parametrize("n_jobs", [2, 7])
def test_jobs_behind_a_busy_dispatch_leave_in_one(n_jobs):
    """Coalescing is kept where it pays: N jobs submitted while a
    dispatch runs ride ONE following dispatch, each future demuxing its
    own rows."""
    gated = _Gated(MeshCoder(DEFAULT_SCHEME))
    sched = EcBatchScheduler(mesh_coder=gated)
    try:
        plug = gated.plug(sched)
        datas = [_batch(1, 1000, seed=i)[0] for i in range(n_jobs)]
        futs = [sched.submit_encode(d) for d in datas]
        gated.gate.set()
        plug.result(timeout=60)
        for d, f in zip(datas, futs):
            assert np.array_equal(f.result(timeout=60),
                                  CPU.encode_array(d))
        st = sched.stats()
        assert st["jobs_total"] == n_jobs + 1
        assert st["mesh_batches"] == 2 and st["cpu_batches"] == 0
        assert st["coder_fallbacks"] == 0
        assert st["max_coalesced"] == n_jobs
        assert st["lone_dispatches"] == 1        # the plug
    finally:
        gated.gate.set()
        sched.stop()


def test_lone_job_on_an_idle_scheduler_is_not_held():
    """Nobody to share a dispatch with: the job is dispatched without
    waiting (until PR 26 every job was held 5 ms for company), and
    ``lone_dispatches`` counts it."""
    sched = EcBatchScheduler(mesh_coder=_Recorder())
    n = 9
    try:
        for i in range(n):
            d = _batch(1, 1000, seed=i)[0]
            assert np.array_equal(sched.encode(d), CPU.encode_array(d))
        st = sched.stats()
    finally:
        sched.stop()
    assert st["jobs_total"] == st["mesh_batches"] == n
    assert st["lone_dispatches"] == n and st["max_coalesced"] == 1
    (_labels, counts, total, _ex), = st["wait_hist"]["series"]
    assert sum(counts) == n
    # submit -> dispatch is a thread wake-up: under the first bucket
    # (1 ms) but for a straggler when the machine is busy
    assert counts[0] >= n - 2, counts
    assert total < n * 0.005
    assert "window_s" not in st


class _OnDevice(_Recorder):
    """Mesh stand-in whose dispatches launch at once and come back when
    the test lets them: ``log`` keeps the order of ("launch", i) and
    ("collect", i), ``.result()`` of dispatch i waits for ``gates[i]``
    (or for nothing once ``open_all`` is set) and raises if i is in
    ``fail``.  ``chained=n``: the test opens no gate; dispatch i is let
    back when dispatch i + 1 has been launched, the n-th at once.  No
    timing: the order is what the events make it."""

    def __init__(self, fail=(), chained: int = 0):
        super().__init__()
        self.fail = set(fail)
        self.chained = chained
        self.cv = threading.Condition()
        self.log: list = []
        self.gates: list = []
        self.open_all = False
        self.on_device = self.most_on_device = 0

    def _begin(self, operand, compute):
        with self.cv:
            i = len(self.gates)
            gate = threading.Event()
            if self.open_all or i + 1 == self.chained:
                gate.set()
            if self.chained and i:
                self.gates[i - 1].set()
            self.gates.append(gate)
            self.seen.append(operand)
            self.log.append(("launch", i))
            self.on_device += 1
            self.most_on_device = max(self.most_on_device, self.on_device)
            self.cv.notify_all()

        def result():
            assert gate.wait(60)
            with self.cv:
                self.log.append(("collect", i))
                self.on_device -= 1
            if i in self.fail:
                raise RuntimeError("device_put failed: device vanished")
            return compute()
        return _Begun(result)

    def encode_batch_begin(self, b):
        return self._begin(
            b, lambda: np.stack([CPU.encode_array(x) for x in b]))

    def rebuild_batch_begin(self, s, mats):
        return self._begin(s, lambda: [CPU.reconstruct_rows(s[i], mats[i])
                                       for i in range(s.shape[0])])

    def launched(self, n: int) -> None:
        """Wait until ``n`` dispatches have been launched."""
        with self.cv:
            assert self.cv.wait_for(lambda: len(self.gates) >= n, 60), \
                self.log

    def release_all(self) -> None:
        with self.cv:
            self.open_all = True
            for g in self.gates:
                g.set()


@pytest.mark.parametrize("batches", [2, 3, 9])
def test_lone_pipeline_keeps_two_jobs_in_the_queue_and_dispatches_one(
        tmp_path, batches):
    """A lone seal's window of two, since PR 36 on the DEVICE: job N+1 is
    launched while N is uncollected (until then it sat in the queue for
    the length of N's dispatch), and still every dispatch carries ONE
    job, as a view (``traffic/single.json`` warms only the B = 1
    program): the caller's next job is not submitted before its last one
    was launched (``_Job.taken``)."""
    from seaweedfs_tpu.parallel import streaming
    from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
    from seaweedfs_tpu.storage.erasure_coding import layout
    lb, sb = 640, 160
    sbase, pbase = str(tmp_path / "s"), str(tmp_path / "p")
    dat = np.random.default_rng(batches).integers(
        0, 256, batches * sb * K - 7, dtype=np.uint8).tobytes()
    for base in (sbase, pbase):
        with open(base + ".dat", "wb") as f:
            f.write(dat)
    ecenc.write_ec_files(sbase, CPU, lb, sb, batch_size=sb)
    # every dispatch but the last comes back only when the next has been
    # launched: the device is as slow as it takes for the caller's next
    # job to be on it too
    held = _OnDevice(chained=batches)
    sched = EcBatchScheduler(mesh_coder=held)
    stats: dict = {}
    try:
        streaming.pipelined_encode_file(pbase, BatchCoder(sched), lb, sb,
                                        batch_size=sb, stats=stats)
        # a dispatch is counted after its futures are set
        while sched.stats()["mesh_batches"] < batches:
            time.sleep(0.001)
        st = sched.stats()
    finally:
        sched.stop()
    assert stats["batches"] == batches
    assert stats["overlapped"] == batches - 1
    assert st["max_coalesced"] == 1
    assert st["lone_dispatches"] == st["mesh_batches"] == batches
    assert st["batches_total"] == batches
    assert st["overlapped_dispatches"] == batches - 1
    assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0
    assert [b.shape[0] for b in held.seen] == [1] * batches
    for i in range(TOTAL):
        ext = layout.shard_ext(i)
        assert open(pbase + ext, "rb").read() == \
            open(sbase + ext, "rb").read()


@pytest.mark.parametrize("kind", ["encode", "rebuild"])
def test_lone_job_reaches_the_coder_uncopied(kind):
    """At B = 1 the coder's operand is a view of the job's own buffer
    (submit left it contiguous and on a ladder rung), and the rows are
    bit-identical to what the stacked path (B = 2) returns."""
    rec = _Recorder()
    gated = _Gated(rec)
    gated.gate.set()
    sched = EcBatchScheduler(mesh_coder=gated)
    data = _batch(1, COLUMN_LADDER[0], seed=7)[0]   # on a rung: no pad
    mat = CPU.rebuild_matrix(list(range(1, TOTAL)), [0])
    submit = (lambda: sched.submit_encode(data)) if kind == "encode" \
        else (lambda: sched.submit_rebuild(data, mat))
    try:
        lone = submit().result(timeout=30)
        assert rec.shapes == [(1, K, COLUMN_LADDER[0])]
        assert np.shares_memory(rec.seen[0], data)
        # the same job twice, behind a held dispatch: stacked
        gated.gate.clear()
        gated.entered.clear()
        plug = gated.plug(sched)
        pair = [submit(), submit()]
        gated.gate.set()
        plug.result(timeout=30)
        for f in pair:
            assert np.array_equal(f.result(timeout=30), lone)
        assert rec.shapes[-1] == (2, K, COLUMN_LADDER[0])
        assert not np.shares_memory(rec.seen[-1], data)
        st = sched.stats()
        assert st["lone_dispatches"] == 2 and st["max_coalesced"] == 2
        assert st["stage_n"]["stack"] == 3      # the stage is kept
    finally:
        gated.gate.set()
        sched.stop()
    want = CPU.encode_array(data) if kind == "encode" \
        else CPU.reconstruct_rows(data, mat)
    assert np.array_equal(lone, want)


@pytest.mark.parametrize("cmd", ["volume", "server"])
def test_cli_has_no_batch_window_flag(cmd, capsys):
    from seaweedfs_tpu.cli import main
    with pytest.raises(SystemExit) as e:
        main([cmd, "-ecBatcher", "-ecBatchWindowMs", "5"])
    assert e.value.code == 2
    assert "-ecBatchWindowMs" in capsys.readouterr().err


def test_constructors_have_no_batch_window_argument():
    import inspect

    from seaweedfs_tpu.server.volume_server import VolumeServer
    assert "ec_batcher" in inspect.signature(VolumeServer).parameters
    assert "ec_batch_window_s" not in \
        inspect.signature(VolumeServer).parameters
    assert "window_s" not in inspect.signature(EcBatchScheduler).parameters


def test_scheduler_pads_odd_columns():
    sched = EcBatchScheduler()
    try:
        d = _batch(1, 997, seed=3)[0]
        assert np.array_equal(sched.encode(d), CPU.encode_array(d))
    finally:
        sched.stop()


def test_scheduler_orders_by_qos_class():
    """An interactive job submitted AFTER a background job dispatches
    first (distinct ladder rungs -> distinct dispatch groups, so group
    order is observable); both queue behind a held dispatch, so they
    leave in one batch."""
    rec = _Recorder()
    gated = _Gated(rec)
    sched = EcBatchScheduler(mesh_coder=gated)
    try:
        plug = gated.plug(sched)
        with class_scope(BACKGROUND):
            f_bg = sched.submit_encode(
                _batch(1, COLUMN_LADDER[0] + 4, seed=4)[0])
        with class_scope(INTERACTIVE):
            f_int = sched.submit_encode(_batch(1, 8, seed=5)[0])
        gated.gate.set()
        plug.result(timeout=30)
        f_bg.result(timeout=30)
        f_int.result(timeout=30)
        # after the plug: interactive first, padded up to its ladder rung
        assert [s[2] for s in rec.shapes[1:]] == list(COLUMN_LADDER[:2]), \
            rec.shapes
        assert sched.stats()["max_coalesced"] == 2
    finally:
        gated.gate.set()
        sched.stop()


class _Boom(_AtLaunch):
    n_devices = 8

    def encode_batch(self, b):
        raise RuntimeError("device_put failed: device vanished")

    def rebuild_batch(self, s, m):
        raise RuntimeError("device_put failed: device vanished")


def test_mid_run_device_loss_drains_through_cpu():
    """THE satellite: backend raises on dispatch -> every queued job
    drains through the CPU fallback bit-identically, coder_fallbacks
    increments, the reason is classified, the on_fallback observer
    fires, and the mesh is benched for the cooldown."""
    reasons = []
    sched = EcBatchScheduler(mesh_coder=_Boom(), cooldown_s=60.0,
                             on_fallback=reasons.append)
    try:
        datas = [_batch(1, 1000, seed=10 + i)[0] for i in range(6)]
        futs = [sched.submit_encode(d) for d in datas]
        for d, f in zip(datas, futs):
            assert np.array_equal(f.result(timeout=30),
                                  CPU.encode_array(d))
        assert sched.coder_fallbacks >= 1
        assert sched.fallback_reason == "device_put"
        assert reasons and reasons[0] == "device_put"
        # benched: later work routes straight to CPU without re-raising
        d = _batch(1, 512, seed=20)[0]
        assert np.array_equal(sched.encode(d), CPU.encode_array(d))
        st = sched.stats()
        assert st["mesh_healthy"] is False
        assert st["cpu_batches"] >= 2
        # rebuild drains too
        shards = CPU.encode([d[i].tobytes() for i in range(K)])
        full = [np.frombuffer(s, dtype=np.uint8) for s in shards]
        present = [j for j in range(TOTAL) if j != 0]
        mat = CPU.rebuild_matrix(present, [0])
        rec = sched.rebuild(np.stack([full[j]
                                      for j in sorted(present)[:K]]), mat)
        assert np.array_equal(rec[0], full[0])
    finally:
        sched.stop()


def test_stop_drains_queued_jobs_through_cpu():
    """No submitted future is ever abandoned: jobs still queued at
    stop() complete via the CPU path."""
    gated = _Gated(_Recorder())
    sched = EcBatchScheduler(mesh_coder=gated)
    d1, d2 = _batch(2, 256, seed=6)
    f1 = sched.submit_encode(d1)
    assert gated.entered.wait(30)   # the dispatcher is held inside f1
    f2 = sched.submit_encode(d2)
    gated.gate.set()
    sched.stop()
    assert np.array_equal(f1.result(timeout=10), CPU.encode_array(d1))
    assert np.array_equal(f2.result(timeout=10), CPU.encode_array(d2))


def test_batch_coder_facade_is_a_drop_in_coder():
    sched = EcBatchScheduler()
    try:
        bc = BatchCoder(sched)
        rng = np.random.default_rng(8)
        shards = [rng.integers(0, 256, 500, dtype=np.uint8).tobytes()
                  for _ in range(K)]
        full = bc.encode(shards)
        assert [bytes(s) for s in full] == \
            [bytes(s) for s in CPU.encode(shards)]
        holes = [s if i not in (1, 11) else None
                 for i, s in enumerate(full)]
        assert [bytes(s) for s in bc.reconstruct(holes)] == \
            [bytes(s) for s in full]
        assert bc.verify(full)
    finally:
        sched.stop()


# ------------------------------- two dispatches on the device (PR 36)

def _held_by_the_launcher(sched, n_queued: int = 0) -> None:
    """Wait until the launching thread holds a job (it is waiting for a
    place on the device) with ``n_queued`` more behind it in the queue."""
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        st = sched.stats()
        if st["queued"] == n_queued and sched._loop_pub[3] == 1:
            return
        time.sleep(0.001)
    raise AssertionError(sched.stats())


@pytest.mark.parametrize("kind", ["encode", "rebuild"])
def test_a_job_behind_an_uncollected_dispatch_is_launched_at_once(kind):
    """Dispatch N+1 is LAUNCHED while N is uncollected; never more than
    two are on the device; what queues up while both places are taken
    rides ONE next dispatch; results come back in launch order,
    bit-identical to the host coder; ``overlapped_dispatches`` counts."""
    dev = _OnDevice()
    sched = EcBatchScheduler(mesh_coder=dev)
    mat = CPU.rebuild_matrix(list(range(1, TOTAL)), [0])
    datas = [_batch(1, 1000, seed=60 + i)[0] for i in range(5)]
    if kind == "encode":
        submit, want = sched.submit_encode, CPU.encode_array
    else:
        def submit(d):
            return sched.submit_rebuild(d, mat)

        def want(d):
            return CPU.reconstruct_rows(d, mat)
    try:
        f0 = submit(datas[0])
        dev.launched(1)
        f1 = submit(datas[1])
        dev.launched(2)               # ... while dispatch 0 is uncollected
        assert dev.log == [("launch", 0), ("launch", 1)]
        rest = [submit(d) for d in datas[2:]]
        _held_by_the_launcher(sched, n_queued=2)
        assert len(dev.gates) == 2 and not f0.done()
        dev.gates[0].set()
        assert np.array_equal(f0.result(timeout=60), want(datas[0]))
        dev.launched(3)               # the three that waited, in ONE
        assert dev.seen[2].shape[0] == 3
        assert not f1.done()          # handed over in launch order
        dev.gates[1].set()
        assert np.array_equal(f1.result(timeout=60), want(datas[1]))
        assert not any(f.done() for f in rest)
        dev.gates[2].set()
        for d, f in zip(datas[2:], rest):
            assert np.array_equal(f.result(timeout=60), want(d))
        while sched.stats()["mesh_batches"] < 3:
            time.sleep(0.001)
        st = sched.stats()
    finally:
        dev.release_all()
        sched.stop()
    assert dev.log == [("launch", 0), ("launch", 1), ("collect", 0),
                       ("launch", 2), ("collect", 1), ("collect", 2)]
    assert dev.most_on_device == 2
    assert st["jobs_total"] == 5 and st["mesh_batches"] == 3
    assert st["lone_dispatches"] == 2 and st["max_coalesced"] == 3
    assert st["overlapped_dispatches"] == 2
    assert st["by_spec"]["rs-10-4"]["overlapped_dispatches"] == 2
    assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0
    # submit -> launch: the job that found a place free did not wait for
    # the dispatch before it (60 s of gate would show)
    (_labels, counts, _total, _ex), = st["wait_hist"]["series"]
    assert sum(counts) == 5


@pytest.mark.parametrize("fails_at", ["collect", "launch"])
def test_a_failure_with_a_second_dispatch_in_flight_drains_both(fails_at):
    """The fallback ladder per dispatch: the dispatch that failed (at its
    collect, or at its launch), the one in flight BEHIND it and what was
    queued all resolve through the CPU coder bit-identically; one
    failure is counted once, the observer hears once, the mesh is
    benched."""
    dev = _OnDevice(fail={0} if fails_at == "collect" else ())
    reasons = []
    sched = EcBatchScheduler(mesh_coder=dev, cooldown_s=60.0,
                             on_fallback=reasons.append)
    datas = [_batch(1, 1000, seed=70 + i)[0] for i in range(4)]
    try:
        futs = [sched.submit_encode(datas[0])]
        dev.launched(1)
        if fails_at == "launch":
            def boom(b):
                raise RuntimeError("device_put failed: device vanished")
            dev.encode_batch_begin = boom
        futs.append(sched.submit_encode(datas[1]))
        if fails_at == "collect":
            dev.launched(2)
            futs += [sched.submit_encode(d) for d in datas[2:]]
            _held_by_the_launcher(sched, n_queued=1)
            dev.gates[0].set()
        else:
            # the launch of dispatch 1 raised beside dispatch 0 in flight
            assert np.array_equal(futs[1].result(timeout=60),
                                  CPU.encode_array(datas[1]))
            futs += [sched.submit_encode(d) for d in datas[2:]]
            dev.gates[0].set()
        for d, f in zip(datas, futs):
            assert np.array_equal(f.result(timeout=60),
                                  CPU.encode_array(d))
        st = sched.stats()
    finally:
        dev.release_all()
        sched.stop()
    assert st["coder_fallbacks"] == 1 and reasons == ["device_put"]
    assert st["fallback_reason"] == "device_put"
    assert st["mesh_healthy"] is False
    if fails_at == "collect":
        # 0 (failed), 1 (in flight behind it: not asked of the device),
        # then 2 + 3 in one, on the launching thread
        assert st["mesh_batches"] == 0 and st["cpu_batches"] == 3
        assert ("collect", 1) not in dev.log
    else:
        # 1 (its launch raised) and 2, 3 (or 2 + 3) on the host; 0, whose
        # collect had begun, came back from the device
        assert st["mesh_batches"] == 1 and st["cpu_batches"] in (2, 3)
        assert dev.log == [("launch", 0), ("collect", 0)]
    assert st["overlapped_dispatches"] == 0
    assert st["by_spec"]["rs-10-4"]["cpu_dispatches"] == st["cpu_batches"]


def test_stop_with_two_in_flight_resolves_every_future():
    dev = _OnDevice()
    sched = EcBatchScheduler(mesh_coder=dev)
    datas = [_batch(1, 512, seed=80 + i)[0] for i in range(4)]
    futs = [sched.submit_encode(datas[0])]
    dev.launched(1)
    futs.append(sched.submit_encode(datas[1]))
    dev.launched(2)
    futs += [sched.submit_encode(d) for d in datas[2:]]
    _held_by_the_launcher(sched, n_queued=1)
    stopper = threading.Thread(target=sched.stop)
    stopper.start()
    try:
        while not sched._stopped:
            time.sleep(0.001)
        assert not any(f.done() for f in futs)
    finally:
        dev.release_all()
    stopper.join(timeout=30)
    assert not stopper.is_alive()
    assert not sched._thread.is_alive()
    assert not sched._collector.is_alive()
    for d, f in zip(datas, futs):
        assert np.array_equal(f.result(timeout=1), CPU.encode_array(d))
    with pytest.raises(RuntimeError):
        sched.submit_encode(datas[0])


class _Counted:
    """A real mesh coder that counts how many of its dispatches are on
    the device at once."""

    def __init__(self, inner):
        self.inner = inner
        self.n_devices = inner.n_devices
        self.lock = threading.Lock()
        self.on_device = self.most_on_device = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def _counted(self, pending):
        with self.lock:
            self.on_device += 1
            self.most_on_device = max(self.most_on_device, self.on_device)

        def result():
            try:
                return pending.result()
            finally:
                with self.lock:
                    self.on_device -= 1
        return _Begun(result)

    def encode_batch_begin(self, b):
        return self._counted(self.inner.encode_batch_begin(b))

    def rebuild_batch_begin(self, s, mats):
        return self._counted(self.inner.rebuild_batch_begin(s, mats))


def test_many_submitters_two_threads_no_update_is_lost():
    """More submitting threads than cores, a shortened switch interval:
    every result is its own job's, bit-identical; the counters the two
    threads of the scheduler keep add up; never more than two dispatches
    on the device."""
    import sys
    counted = _Counted(MeshCoder(DEFAULT_SCHEME, n_devices=1))
    sched = EcBatchScheduler(mesh_coder=counted)
    coder = BatchCoder(sched)
    mat = CPU.rebuild_matrix(list(range(1, TOTAL)), [0])
    n_threads, n_each = 24, 12
    wrong: list = []

    def work(t):
        rng = np.random.default_rng(900 + t)
        for i in range(n_each):
            d = rng.integers(0, 256, (K, 2048), dtype=np.uint8)
            if (t + i) % 2:
                ok = np.array_equal(coder.encode_array(d),
                                    CPU.encode_array(d))
            else:
                ok = np.array_equal(coder.reconstruct_rows(d, mat),
                                    CPU.reconstruct_rows(d, mat))
            if not ok:
                wrong.append((t, i))
    coder.encode_array(_batch(1, 2048)[0])          # compile outside
    coder.reconstruct_rows(_batch(1, 2048)[0], mat)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=240)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(was)
        sched.stop()
    st = sched.stats()
    n = n_threads * n_each + 2
    assert wrong == []
    assert st["jobs_total"] == n == st["by_spec"]["rs-10-4"]["jobs"]
    assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0
    assert st["mesh_batches"] == st["by_spec"]["rs-10-4"]["mesh_dispatches"]
    assert st["mesh_batches"] == sum(
        r["mesh_dispatches"] for r in st["by_rung"].values())
    assert st["stage_n"]["launch"] == st["stage_n"]["fetch"] \
        == st["stage_n"]["demux"] == st["mesh_batches"]
    assert 0 < st["overlapped_dispatches"] < st["mesh_batches"]
    assert counted.most_on_device == 2 and counted.on_device == 0


def test_a_replaced_one_step_form_is_what_the_scheduler_runs(monkeypatch):
    """benchmark/tests/faulty_volume.py plants its faults by replacing
    ``MeshCoder.encode_batch`` / ``rebuild_batch`` on the class: the
    two-step forms run a replaced one-step form whole, so a fault planted
    there still reaches every job the scheduler dispatches."""
    real_enc, real_reb = MeshCoder.encode_batch, MeshCoder.rebuild_batch

    def encode_batch(self, batch):
        out = np.array(real_enc(self, batch))
        out[0, 0, 0] ^= 1
        return out

    def rebuild_batch(self, srcdata, mats):
        recs = [np.array(r) for r in real_reb(self, srcdata, mats)]
        recs[0][0, 0] ^= 1
        return recs
    data = _batch(1, 4096, seed=8)[0]
    mat = CPU.rebuild_matrix(list(range(1, TOTAL)), [0])
    sched = EcBatchScheduler(mesh_coder=MeshCoder(DEFAULT_SCHEME))
    try:
        sound = sched.encode(data), sched.rebuild(data, mat)
        monkeypatch.setattr(MeshCoder, "encode_batch", encode_batch)
        monkeypatch.setattr(MeshCoder, "rebuild_batch", rebuild_batch)
        planted = sched.encode(data), sched.rebuild(data, mat)
    finally:
        sched.stop()
    for good, bad in zip(sound, planted):
        assert good[0, 0] ^ 1 == bad[0, 0]
        assert np.array_equal(good.ravel()[1:], bad.ravel()[1:])
    assert np.array_equal(sound[0], CPU.encode_array(data))


# ------------------------------------------- mixed-code batch drain

def test_mixed_rs_lrc_batch_drain_bit_identical():
    """THE satellite: RS and LRC jobs submitted into ONE scheduler in
    one drain, every future demuxing bit-identical
    per-job rows — RS encodes ride the native parity path, LRC encodes
    the matrix-carrying path, and an LRC group-local rebuild (5 source
    rows, not k) is fitted to the geometry's k-wide apply program by
    the mesh coder (zero rows added): on the device, no fallback."""
    from seaweedfs_tpu.ops.lrc import LrcCoder

    lrc = LrcCoder()
    sched = EcBatchScheduler()
    try:
        rs_data = [_batch(1, 1024, seed=30 + i)[0] for i in range(3)]
        lrc_data = [_batch(1, 1024, seed=40 + i)[0] for i in range(3)]
        futs = []
        for rd, ld in zip(rs_data, lrc_data):
            futs.append(("rs", rd, sched.submit_encode(rd)))
            futs.append(("lrc", ld,
                         sched.submit_encode(ld, mat=lrc._parity)))
        # an LRC single-shard local repair rides the same drain
        shards = lrc.encode([lrc_data[0][i].tobytes() for i in range(K)])
        full = [np.frombuffer(s, dtype=np.uint8) for s in shards]
        src_sids, mat = lrc.plan_rebuild(
            [s for s in range(TOTAL) if s != 2], [2])
        assert len(src_sids) == 5  # group-local: 5 reads, not k=10
        rf = sched.submit_rebuild(
            np.stack([full[s] for s in src_sids]), mat)
        for fam, d, f in futs:
            want = CPU.encode_array(d) if fam == "rs" \
                else lrc.encode_array(d)
            assert np.array_equal(f.result(timeout=30), want), fam
        assert np.array_equal(rf.result(timeout=30)[0], full[2])
        st = sched.stats()
        assert st["jobs_total"] == 7
        assert st["coder_fallbacks"] == 0  # narrow rebuild != mesh fault
        assert st["mesh_healthy"] is True
        assert st["cpu_batches"] == 0
        assert st["by_spec"]["rs-10-4"]["rows"] == {"5": 1, "10": 6}
    finally:
        sched.stop()


def test_mixed_drain_survives_mesh_loss_via_cpu():
    """Mixed batch + mesh failure: both families drain through the CPU
    fallback bit-identically."""
    from seaweedfs_tpu.ops.lrc import LrcCoder

    lrc = LrcCoder()
    sched = EcBatchScheduler(mesh_coder=_Boom())
    try:
        rd = _batch(1, 776, seed=50)[0]
        ld = _batch(1, 776, seed=51)[0]
        f1 = sched.submit_encode(rd)
        f2 = sched.submit_encode(ld, mat=lrc._parity)
        assert np.array_equal(f1.result(timeout=30), CPU.encode_array(rd))
        assert np.array_equal(f2.result(timeout=30), lrc.encode_array(ld))
        assert sched.coder_fallbacks >= 1
    finally:
        sched.stop()


def test_lrc_batch_coder_facade_shares_scheduler():
    """One scheduler serves two BatchCoder facades — RS and LRC — each
    encoding under its own family and reconstructing via its own plan."""
    from seaweedfs_tpu.models.coder import LrcScheme
    from seaweedfs_tpu.ops.lrc import LrcCoder

    lrc = LrcCoder()
    sched = EcBatchScheduler()
    try:
        rs_bc = BatchCoder(sched)
        lrc_bc = BatchCoder(sched, LrcScheme())
        assert lrc_bc.scheme.total_shards == TOTAL
        rng = np.random.default_rng(52)
        shards = [rng.integers(0, 256, 600, dtype=np.uint8).tobytes()
                  for _ in range(K)]
        assert [bytes(s) for s in rs_bc.encode(shards)] == \
            [bytes(s) for s in CPU.encode(shards)]
        full = lrc_bc.encode(shards)
        assert [bytes(s) for s in full] == \
            [bytes(s) for s in lrc.encode(shards)]
        # a single-shard hole reconstructs through the shared scheduler
        # (plan-driven sources, not first-k-of-present)
        holes = [s if i != 7 else None for i, s in enumerate(full)]
        assert [bytes(s) for s in lrc_bc.reconstruct(holes)] == \
            [bytes(s) for s in full]
    finally:
        sched.stop()


# ------------------------------------- repair-queue wave coalescing

def test_repair_queue_coalesces_dispatch_waves():
    from seaweedfs_tpu.scrub.repair_queue import RepairQueue
    from seaweedfs_tpu.utils.metrics import Registry

    class _Topo:
        lock = threading.Lock()

        def all_nodes(self):
            return []

    class _Master:
        metrics = Registry()
        topo = _Topo()

    ran = []
    done = threading.Event()
    rq = RepairQueue(_Master(), max_concurrent=2,
                     coalesce_window_s=30.0)
    rq._repair = lambda task: (ran.append(task.vid), done.set(),
                               0)[-1]
    rq.submit(1, reason="t")
    time.sleep(0.1)
    assert rq.status()["active"] == 0  # held for siblings
    assert ran == []
    rq.submit(2, reason="t")  # full wave -> immediate dispatch
    assert done.wait(5)
    deadline = time.time() + 5
    while time.time() < deadline and len(ran) < 2:
        time.sleep(0.02)
    assert sorted(ran) == [1, 2]
    assert rq.dispatch_waves == 1 and rq.last_wave_size == 2
    assert rq.status()["coalesce_window_s"] == 30.0


def test_repair_queue_window_zero_keeps_immediate_dispatch():
    from seaweedfs_tpu.scrub.repair_queue import RepairQueue
    from seaweedfs_tpu.utils.metrics import Registry

    class _Topo:
        lock = threading.Lock()

        def all_nodes(self):
            return []

    class _Master:
        metrics = Registry()
        topo = _Topo()

    done = threading.Event()
    rq = RepairQueue(_Master(), max_concurrent=2)
    rq._repair = lambda task: (done.set(), 0)[-1]
    rq.submit(7, reason="t")
    assert done.wait(5)
    assert rq.dispatch_waves == 1 and rq.last_wave_size == 1


def test_repair_queue_aged_task_escapes_partial_wave():
    """A lone task must not wait forever for siblings: once it has
    waited out the window, tick() dispatches it alone."""
    from seaweedfs_tpu.scrub.repair_queue import RepairQueue
    from seaweedfs_tpu.utils.metrics import Registry

    class _Topo:
        lock = threading.Lock()
        ec_shard_map = {}

        def all_nodes(self):
            return []

    class _Master:
        metrics = Registry()
        topo = _Topo()

    done = threading.Event()
    rq = RepairQueue(_Master(), max_concurrent=2,
                     coalesce_window_s=0.15)
    rq._repair = lambda task: (done.set(), 0)[-1]
    rq.submit(9, reason="t")
    assert not done.wait(0.05)  # young: held
    time.sleep(0.15)
    rq.tick()
    assert done.wait(5)


# ------------------------------------- stages, counters (PR 25)

def _tree(tr):
    spans = tr.snapshot(limit=0)["spans"]
    by_id = {s["span_id"]: s for s in spans}
    return spans, {s["span_id"]: by_id[s["parent_id"]]["name"]
                   for s in spans if s["parent_id"] in by_id}


def test_sampled_jobs_yield_the_stage_tree():
    from seaweedfs_tpu.utils import tracing
    sched = EcBatchScheduler()
    coder = BatchCoder(sched)
    tr = tracing.Tracer(node="t", sample_rate=1.0)
    data = _batch(1, 5000, seed=3)[0]
    try:
        for name, call in (
                ("seal", lambda: coder.encode_into(
                    data, np.empty((M, 5000), dtype=np.uint8))),
                ("read", lambda: coder.reconstruct_rows(
                    data, CPU.rebuild_matrix(list(range(1, TOTAL)), [0])))):
            root = tr.root_span(name, sampled=True)
            with tracing.span_scope(root):
                with tracing.stage("caller") as caller:
                    out = call()
            root.finish()
            assert out.shape[1] == 5000
    finally:
        sched.stop()
    spans, parent = _tree(tr)
    for req, kind in (("seal", "encode"), ("read", "rebuild")):
        root = next(s for s in spans if s["name"] == req)
        mine = [s for s in spans if s["trace_id"] == root["trace_id"]]
        names = sorted(s["name"] for s in mine)
        assert names == sorted(
            [req, "caller", "ec.batch.submit", "ec.batch.wait",
             "ec.batch.dispatch", "ec.batch.result", "ec.batch.stack",
             "ec.mesh.pad", "ec.mesh.launch", "ec.mesh.fetch",
             "ec.mesh.unpack", "ec.batch.demux"])
        for s in mine:
            under = parent.get(s["span_id"])
            if s["name"] in ("ec.batch.submit", "ec.batch.result",
                             "ec.batch.wait", "ec.batch.dispatch"):
                # caller-thread stages and the dispatcher's two hang off
                # the span that was ambient at submit
                assert under == "caller", (s["name"], under)
            elif s["name"].startswith("ec."):
                assert under == "ec.batch.dispatch", (s["name"], under)
        disp = next(s for s in mine if s["name"] == "ec.batch.dispatch")
        assert disp["annotations"]["kind"] == kind
        assert disp["annotations"]["shape"] == [1, K, COLUMN_LADDER[0]]
        wait = next(s for s in mine if s["name"] == "ec.batch.wait")
        result = next(s for s in mine if s["name"] == "ec.batch.result")
        inner = sum(s["duration_ms"] for s in mine
                    if parent.get(s["span_id"]) == "ec.batch.dispatch")
        assert inner <= disp["duration_ms"] + 0.01
        # the caller waits out the job's wait and its dispatch
        assert result["duration_ms"] + 1.0 >= \
            wait["duration_ms"] + disp["duration_ms"] - 1.0


def test_two_sampled_jobs_in_one_dispatch_share_it_by_id():
    from seaweedfs_tpu.utils import tracing
    gated = _Gated(MeshCoder(DEFAULT_SCHEME))
    sched = EcBatchScheduler(mesh_coder=gated)
    tr = tracing.Tracer(node="t", sample_rate=1.0)
    roots = [tr.root_span(f"r{i}", sampled=True) for i in range(2)]
    try:
        futs = [gated.plug(sched)]     # unsampled; holds the two together
        for root in roots:
            with tracing.span_scope(root):
                futs.append(sched.submit_encode(_batch(1, 4096)[0]))
        gated.gate.set()
        for f in futs:
            f.result(timeout=60)
    finally:
        gated.gate.set()
        sched.stop()
    for root in roots:
        root.finish()
    spans, parent = _tree(tr)
    disp = [s for s in spans if s["name"] == "ec.batch.dispatch"]
    assert sorted(parent[s["span_id"]] for s in disp) == ["r0", "r1"]
    lead = next(s for s in disp if "shape" in s["annotations"])
    other = next(s for s in disp if s is not lead)
    assert lead["annotations"]["shape"][0] == 2
    assert other["annotations"] == {"dispatch_id": lead["span_id"],
                                    "jobs": 2, "spec": "rs-10-4", "rows": 10}
    assert lead["annotations"]["spec"] == "rs-10-4"
    assert other["duration_ms"] == pytest.approx(lead["duration_ms"],
                                                 abs=0.5)
    # the stages hang off the one dispatch span, once
    assert sum(1 for s in spans if s["name"] == "ec.mesh.fetch") == 1


def test_loop_and_stage_counters_account_for_the_wall():
    sched = EcBatchScheduler()
    coder = BatchCoder(sched)
    n = 100_000
    data = _batch(1, n, seed=5)[0]
    mat = CPU.rebuild_matrix(list(range(2, TOTAL)), [0, 1])
    try:
        coder.encode_array(data)                 # compile outside
        coder.reconstruct_rows(data, mat)
        # a dispatch is counted after its futures are set: wait for the
        # dispatcher to have counted the two above
        while sched.stats()["lone_dispatches"] < 2:
            time.sleep(0.001)
        a, t0 = sched.stats(), time.monotonic()
        for _ in range(6):
            coder.encode_array(data)
            time.sleep(0.03)
        for _ in range(4):
            coder.reconstruct_rows(data, mat)
        time.sleep(0.25)      # a long idle, still running at the read
        b, t1 = sched.stats(), time.monotonic()
    finally:
        sched.stop()
    d = {k: b["loop_s"][k] - a["loop_s"][k] for k in b["loop_s"]}
    assert set(d) == {"idle", "hold", "dispatch"}
    assert sum(d.values()) == pytest.approx(t1 - t0, rel=0.02, abs=0.005)
    # lone jobs: hold is the drain of an empty queue, not a timed wait
    # (10 x 5 ms until PR 26)
    assert d["hold"] < 10 * 0.002
    assert b["lone_dispatches"] - a["lone_dispatches"] == 10
    assert d["idle"] >= 6 * 0.03 + 0.25 - 0.1
    stage = {k: b["stage_s"][k] - a["stage_s"][k] for k in b["stage_s"]}
    count = {k: b["stage_n"][k] - a["stage_n"][k] for k in b["stage_n"]}
    assert list(stage) == ["submit", "stack", "pad", "launch", "fetch",
                           "unpack", "demux", "result"]
    assert set(count.values()) == {10}
    # loop_s is the LAUNCHING thread's time: its dispatch part is a
    # dispatch's first half (the second is the collector's)
    launched = sum(stage[k] for k in ("stack", "pad", "launch"))
    assert 0.6 * d["dispatch"] <= launched <= d["dispatch"]
    # the caller waits for hold + dispatch + the collect
    assert stage["result"] >= d["dispatch"] + sum(
        stage[k] for k in ("fetch", "unpack", "demux"))
    by = {k: {f: b["by_kind"][k][f] - a["by_kind"][k][f]
              for f in b["by_kind"][k]} for k in b["by_kind"]}
    rung = COLUMN_LADDER[0]
    assert by["encode"] == {"jobs": 6, "bytes_in": 6 * K * n,
                            "bytes_padded": 6 * K * rung,
                            "bytes_out": 6 * M * n}
    assert by["rebuild"] == {"jobs": 4, "bytes_in": 4 * K * n,
                             "bytes_padded": 4 * K * rung,
                             "bytes_out": 4 * 2 * n}
    # the keys the benchmark and the smoke read are as they were
    for key in ("jobs_total", "batches_total", "mesh_batches",
                "cpu_batches", "coder_fallbacks", "programs_compiled",
                "max_coalesced", "wait_hist", "size_hist"):
        assert key in b
    assert b["jobs_total"] - a["jobs_total"] == 10
    assert sum(sum(c) for _l, c, _s, _e in b["wait_hist"]["series"]) \
        == b["jobs_total"]


def test_unsampled_jobs_allocate_no_span_and_take_no_tracer_lock(
        monkeypatch):
    from seaweedfs_tpu.utils import tracing
    made = []
    real_child = tracing.Span.child
    monkeypatch.setattr(tracing.Span, "child", lambda self, *a, **kw: (
        made.append(a), real_child(self, *a, **kw))[1])

    class Lock:
        taken = 0

        def __enter__(self):
            Lock.taken += 1

        def __exit__(self, *a):
            pass
    tr = tracing.Tracer(node="t", sample_rate=0.0)
    tr._lock = Lock()
    sched = EcBatchScheduler()
    coder = BatchCoder(sched)
    data = _batch(1, 4096)[0]
    root = tr.root_span("req")
    assert root.sampled is False
    try:
        with tracing.span_scope(root):
            coder.encode_array(data)
            coder.reconstruct_rows(
                data, CPU.rebuild_matrix(list(range(1, TOTAL)), [0]))
    finally:
        sched.stop()
    # (before the root is finished: a request slower than slow_ms is
    # kept by the tail keep, which does take the lock)
    assert made == [] and Lock.taken == 0
    assert root.annotations is None
    root.finish()
    assert sched.stats()["stage_n"]["result"] == 2   # counted all the same


# ------------------------------------------ volume-server seam (e2e)

def test_volume_server_ec_batcher_end_to_end(tmp_path):
    import time as _time

    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import ShellContext
    from seaweedfs_tpu.utils.httpd import http_call, http_json

    master = MasterServer(volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer([str(tmp_path / "v0")], master.url,
                      scrub_interval_s=0, ec_batcher=True)
    try:
        vs.start()
        assert vs.ec_batcher is not None
        deadline = _time.time() + 5
        while _time.time() < deadline:
            topo = ShellContext(master.url).topology()
            if sum(len(r["nodes"]) for dc in topo["data_centers"]
                   for r in dc["racks"]) == 1:
                break
            _time.sleep(0.05)
        mc = MasterClient(master.url, cache_ttl=0.0)
        rng = np.random.default_rng(9)
        payload = rng.integers(0, 256, 200_000, dtype=np.uint8).tobytes()
        up = operation.upload_data(mc, payload)
        sh = ShellContext(master.url)
        sh.lock()
        assert sh.ec_encode(), "no volumes encoded"
        # the EC work went through the scheduler...
        st = http_json("GET", f"http://{vs.url}/admin/ec/batcher")
        assert st["enabled"] and st["jobs_total"] >= 1
        assert st["coder_fallbacks"] == 0
        # ...and the needle still reads back from the EC volume
        status, body, _ = http_call("GET", f"http://{vs.url}/{up.fid}")
        assert status == 200 and body == payload
    finally:
        vs.stop()
        master.stop()


# ------------------------------------------- device-scaling contract

def test_scaling_measurement_well_formed_and_bit_identical():
    from tools.mesh_profile import measure_scaling

    sc = measure_scaling([1, 2], batch=4, n_cols=16 * 1024, iters=1)
    assert sc["bit_identical"] is True
    assert [r["devices"] for r in sc["rows"]] == [1, 2]
    assert all(r["encode_mbps"] > 0 and r["rebuild_mbps"] > 0
               for r in sc["rows"])
    assert sc["encode_scaling_1_to_2"] is not None
    assert sc["rebuild_scaling_1_to_2"] is not None


@pytest.mark.slow
def test_device_scaling_floor_1_to_2():
    """The acceptance floor: >=1.6x encode/rebuild going 1->2 devices.
    Only real accelerator devices can scale wall-clock (tier-1's
    virtual CPU devices share one core), so the floor binds on TPU
    backends with >=2 devices and records-but-skips elsewhere."""
    from tools.mesh_profile import measure_scaling

    if mesh_mod.default_backend() != "tpu" or mesh_mod.device_count() < 2:
        pytest.skip("scaling floor binds only on real multi-device "
                    "hardware (virtual devices share one core)")
    sc = measure_scaling([1, 2], batch=16, n_cols=256 * 1024, iters=3)
    assert sc["bit_identical"] is True
    assert sc["encode_scaling_1_to_2"] >= 1.6, sc
    assert sc["rebuild_scaling_1_to_2"] >= 1.6, sc
