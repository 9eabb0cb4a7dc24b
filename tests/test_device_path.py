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
    for name in ("jax", "pallas", "mesh"):
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


def test_interpret_mode_is_decided_by_name(monkeypatch):
    from seaweedfs_tpu.ops import rs_jax
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(rs_jax.jax, "default_backend",
                            lambda b=backend: b)
        assert rs_jax.interpret_mode() is want
    monkeypatch.setattr(rs_jax.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        rs_jax.interpret_mode()


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
    s = EcBatchScheduler(window_s=0.001)
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
