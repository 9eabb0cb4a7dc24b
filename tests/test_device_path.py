"""The device path cannot land on the CPU unseen, compiles a bounded set
of shapes, and caches them where it is told to (parallel/mesh.py,
parallel/batcher.py, ops/rs_mesh.py)."""

import os

import numpy as np
import pytest

from seaweedfs_tpu.models.coder import DEFAULT_SCHEME, make_coder
from seaweedfs_tpu.ops.rs_cpu import CpuCoder
from seaweedfs_tpu.parallel import mesh as mesh_mod
from seaweedfs_tpu.parallel.batcher import (COLUMN_LADDER,
                                            MAX_DISPATCH_COLUMNS,
                                            EcBatchScheduler,
                                            bucket_columns, shape_buckets)

CPU = CpuCoder(DEFAULT_SCHEME)
K = DEFAULT_SCHEME.data_shards
TOTAL = DEFAULT_SCHEME.total_shards


# ------------------------------------------------ start-up gate + report

def test_device_coder_refuses_an_unasked_for_cpu(monkeypatch):
    """JAX fell through to the CPU backend and nobody named it: a
    scheduler (-ecBatcher) and a device coder (-coder jax) both refuse
    to start, with the reason."""
    monkeypatch.setattr(mesh_mod, "cpu_requested", lambda: False)
    with pytest.raises(RuntimeError, match="only the CPU backend"):
        EcBatchScheduler()
    for name in ("jax", "mesh"):
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            make_coder(name)
    make_coder("cpu")  # a host coder is nobody's business


def test_cpu_asked_for_by_name_is_accepted_and_reported():
    assert mesh_mod.cpu_requested()  # conftest names the platform
    sched = EcBatchScheduler()
    try:
        n = mesh_mod.device_count()
        want = {"platform": "cpu", "device_kind": "cpu", "count": n}
        st = sched.stats()
        assert st["device"] == want == sched.device
        assert st["mesh_devices"] == n
        assert st["programs_compiled"] == 0
        assert st["fallback_reason"] is None
        assert make_coder("jax").device_report() == \
            {"platform": "cpu", "device_kind": "cpu", "count": 1}
    finally:
        sched.stop()


def test_volume_server_status_carries_the_coder_device(tmp_path):
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils.httpd import http_json
    master = MasterServer()
    master.start()
    vs = VolumeServer([str(tmp_path)], master.url, ec_batcher=True,
                      scrub_interval_s=0)
    vs.start()
    os.makedirs(tmp_path / "h")
    host = VolumeServer([str(tmp_path / "h")], master.url,
                        scrub_interval_s=0)
    host.start()
    try:
        dev = http_json("GET", f"http://{vs.url}/status")["EcDevice"]
        assert dev == {"platform": "cpu", "device_kind": "cpu",
                       "count": mesh_mod.device_count()}
        assert http_json(
            "GET", f"http://{vs.url}/admin/ec/batcher")["device"] == dev
        # the default (host) coder dispatches to no device
        assert http_json(
            "GET", f"http://{host.url}/status")["EcDevice"] is None
    finally:
        host.stop()
        vs.stop()
        master.stop()


# ------------------------------------------------- compile-cache placement

@pytest.fixture
def fresh_cache_state(monkeypatch):
    """ensure_compile_cache decides once per process; let a test decide
    again without touching this process's real jax config."""
    monkeypatch.setattr(mesh_mod, "_cache_done", False)
    monkeypatch.setattr(mesh_mod, "_cache_dir", None)
    updates = []
    monkeypatch.setattr(mesh_mod.jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    return updates


def test_compile_cache_env_var_wins_and_nothing_is_set(
        fresh_cache_state, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert mesh_mod.ensure_compile_cache() == str(tmp_path / "c")
    assert fresh_cache_state == []  # JAX reads the variable itself
    assert mesh_mod.ensure_compile_cache() == str(tmp_path / "c")


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(
        fresh_cache_state, monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert mesh_mod._CACHE_DIR_DEFAULT == os.path.join(repo, ".jax_cache")
    monkeypatch.setattr(mesh_mod, "_CACHE_DIR_DEFAULT",
                        str(tmp_path / ".jax_cache"))
    got = mesh_mod.ensure_compile_cache()
    assert got == str(tmp_path / ".jax_cache") and os.path.isdir(got)
    assert fresh_cache_state == [("jax_compilation_cache_dir", got)]


def test_compile_cache_unwritable_dir_goes_on_without(
        fresh_cache_state, monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.setattr(mesh_mod, "_CACHE_DIR_DEFAULT",
                        str(blocker / ".jax_cache"))
    assert mesh_mod.ensure_compile_cache() is None
    assert fresh_cache_state == []


# ------------------------------------------------------- bounded shapes

def test_bucket_columns_ladder():
    assert [bucket_columns(n) for n in (0, 1, COLUMN_LADDER[0])] == \
        [COLUMN_LADDER[0]] * 3
    assert bucket_columns(COLUMN_LADDER[0] + 1) == COLUMN_LADDER[1]
    top = COLUMN_LADDER[-1]
    assert bucket_columns(top) == top
    assert bucket_columns(top + 1) == 2 * top  # multiples beyond the top
    for n_dev in (1, 4, 8):
        for b, n in shape_buckets(64, n_dev):
            assert b % n_dev == 0 and (b // n_dev) & (b // n_dev - 1) == 0
            assert b == n_dev or b * n <= MAX_DISPATCH_COLUMNS


@pytest.fixture(scope="module")
def sched():
    s = EcBatchScheduler()
    yield s
    s.stop()


@pytest.mark.parametrize("n", [1, 5, 4096, 65537, 1 << 18, (1 << 18) + 1,
                               1 << 20])
def test_any_needle_size_is_bit_identical_on_a_ladder_shape(sched, n):
    """Encode and a 3-shard rebuild of a job n columns wide — a degraded
    read of an n-byte needle interval — equal CpuCoder's bytes, and the
    only shapes that reach the mesh are ladder rungs."""
    rng = np.random.default_rng(n)
    data = rng.integers(0, 256, (K, n), dtype=np.uint8)
    parity = sched.encode(data)
    assert np.array_equal(parity, CPU.encode_array(data))
    full = np.concatenate([data, parity])
    lost = [0, 3, 11]
    present = [s for s in range(TOTAL) if s not in lost]
    mat = CPU.rebuild_matrix(present, lost)
    rec = sched.rebuild(full[present[:K]], mat)
    assert rec.shape == (3, n) and np.array_equal(rec, full[lost])
    st = sched.stats()
    assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0
    widths = {shape[-1] * 4 for shape in sched._mesh.programs}
    assert widths <= set(COLUMN_LADDER[:2]), sched._mesh.programs
    # seven needle sizes, at most (encode, apply) x two rungs compiled
    assert st["programs_compiled"] <= 4


# ------------------------------- names, compiles, the trace exporter

def test_the_two_programs_lower_under_their_own_names():
    """A device trace names a module after the jitted function: the
    benchmark's per-program metrics select ``jit_ec_encode`` and
    ``jit_ec_apply`` by prefix."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import rs_mesh
    mesh = mesh_mod.batch_mesh(1)
    words = jax.ShapeDtypeStruct((1, K, 1024), jnp.uint32)
    coeff = jax.ShapeDtypeStruct((1, 4, K), jnp.uint32)
    enc = rs_mesh.batch_encode_fn(DEFAULT_SCHEME, mesh).lower(words)
    app = rs_mesh.batch_apply_fn(DEFAULT_SCHEME, mesh).lower(words, coeff)
    assert "module @jit_ec_encode" in enc.as_text()
    assert "module @jit_ec_apply" in app.as_text()


def test_backend_compiles_counts_compiles_not_first_uses():
    from seaweedfs_tpu.ops.rs_mesh import MeshCoder
    # a mesh of its own (3 of the virtual devices): no other test of the
    # process can have compiled its programs
    sched = EcBatchScheduler(mesh_coder=MeshCoder(n_devices=3))
    data = np.zeros((K, 4096), dtype=np.uint8)
    try:
        c0 = sched.stats()["backend_compiles"]
        assert c0 is not None
        sched.encode(data)
        c1 = sched.stats()
        sched.encode(data)
        c2 = sched.stats()
    finally:
        sched.stop()
    assert c1["backend_compiles"] == c0 + 1
    assert c2["backend_compiles"] == c1["backend_compiles"]
    assert c1["backend_compile_s"] > 0
    assert c2["programs_compiled"] == c1["programs_compiled"] == 1
    # a second coder over the same mesh: a first use, nothing compiled
    again = EcBatchScheduler(mesh_coder=MeshCoder(n_devices=3))
    try:
        again.encode(data)
        c3 = again.stats()
    finally:
        again.stop()
    assert c3["programs_compiled"] == 1
    assert c3["backend_compiles"] == c2["backend_compiles"]


def test_admin_ec_trace_exports_device_and_host_stages(tmp_path):
    import threading

    import jax

    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils.httpd import http_call, http_json
    master = MasterServer()
    master.start()
    vs = VolumeServer([str(tmp_path / "v")], master.url, ec_batcher=True,
                      scrub_interval_s=0)
    os.makedirs(tmp_path / "h")
    host = VolumeServer([str(tmp_path / "h")], master.url,
                        scrub_interval_s=0)
    vs.start()
    host.start()
    stop = threading.Event()

    def work():
        data = np.ones((K, 4096), dtype=np.uint8)
        while not stop.is_set():
            vs.store.coder.encode_array(data)   # the BatchCoder facade
    vs.ec_batcher.encode(np.ones((K, 4096), dtype=np.uint8))  # compile
    worker = threading.Thread(target=work)
    worker.start()
    url = f"http://{vs.url}/admin/ec/trace"
    try:
        # long enough for whole dispatches to begin AND end inside it
        # with the suite's other workers competing for the cores
        out = http_json("POST", url, {"seconds": 3.0,
                                      "dir": str(tmp_path / "trace")})
        for bad in ({"seconds": 31, "dir": "x"}, {"seconds": 1},
                    {"seconds": 0, "dir": "x"}):
            assert http_call("POST", url, json_body=bad)[0] == 400
        # one profile per process: a second one while the first runs
        first = threading.Thread(target=http_json, args=(
            "POST", url, {"seconds": 1.0, "dir": str(tmp_path / "t2")}))
        first.start()
        import time
        time.sleep(0.3)
        assert http_call("POST", url, json_body={
            "seconds": 0.1, "dir": str(tmp_path / "t3")})[0] == 409
        first.join(30)
        assert not first.is_alive()
        # without a device coder there is nothing to trace
        assert http_call("POST", f"http://{host.url}/admin/ec/trace",
                         json_body={"seconds": 0.1, "dir": "x"})[0] == 404
    finally:
        stop.set()
        worker.join(30)
        host.stop()
        vs.stop()
        master.stop()
    assert out["dir"] == str(tmp_path / "trace") and out["seconds"] == 3.0
    assert len(out["xplane"]) == 1 and out["xplane"][0].endswith(
        ".xplane.pb")
    assert out["xplane_bytes"] == os.path.getsize(out["xplane"][0]) > 0
    data = jax.profiler.ProfileData.from_file(out["xplane"][0])
    names = {e.name for plane in data.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events}
    assert {"ec.batch.dispatch", "ec.batch.hold", "ec.batch.stack",
            "ec.mesh.launch", "ec.mesh.fetch", "ec.batch.submit",
            "ec.batch.result"} <= names
