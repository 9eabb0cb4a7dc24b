"""Hot-needle record cache: bit-identity, invalidation, single-flight,
byte-budget eviction — the correctness contract of
storage/needle_cache.py and its Store/VolumeServer wiring."""

import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.models.coder import make_coder
from seaweedfs_tpu.storage.erasure_coding import layout
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.needle_cache import NeedleCache, _ENTRY_OVERHEAD
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.volume import DeletedError, NotFoundError


def _fill(store, vid, n_files=12, seed=0, size=2000):
    rng = np.random.default_rng(seed)
    payloads = {}
    store.add_volume(vid)
    for i in range(n_files):
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        nid = i + 1
        payloads[nid] = data
        n = Needle(id=nid, cookie=0xABC0 + i, data=data,
                   name=f"f{i}.bin".encode())
        n.set_flags_from_fields()
        store.write_volume_needle(vid, n)
    return payloads


def _degraded_ec_store(tmp_path, n_files=8, victims=(0, 3, 7, 11)):
    store = Store([str(tmp_path / "d1")], coder=make_coder("cpu"))
    payloads = _fill(store, 1, n_files=n_files, seed=7)
    base = store.generate_ec_shards(1)
    store.delete_volume(1)
    store.mount_ec_shards("", 1, list(range(14)))
    store.unmount_ec_shards(1, list(victims))
    for sid in victims:
        os.remove(base + layout.shard_ext(sid))
    return store, payloads


# ---- cache unit behavior ----

def test_byte_budget_eviction_order():
    blob = b"x" * 1000
    cost = len(blob) + _ENTRY_OVERHEAD
    cache = NeedleCache(capacity_bytes=3 * cost, max_item_frac=1)
    for nid in (1, 2, 3):
        assert cache.offer(1, nid, blob, 1000, 2)
    assert cache.stats()["items"] == 3
    # touch 1 -> LRU order is now 2, 3, 1
    assert cache.get(1, 1) is not None
    assert cache.offer(1, 4, blob, 1000, 2)
    st = cache.stats()
    assert st["evictions"] == 1
    assert cache.get(1, 2) is None      # oldest untouched went first
    assert cache.get(1, 1) is not None  # refreshed entry survived
    assert cache.get(1, 3) is not None
    assert cache.get(1, 4) is not None
    assert st["bytes"] <= cache.capacity_bytes


def test_item_cap_and_sketch_admission():
    blob = b"y" * 1000
    cost = len(blob) + _ENTRY_OVERHEAD
    hot = {"est": (0, 0)}
    cache = NeedleCache(capacity_bytes=2 * cost, max_item_frac=1,
                        hot_fn=lambda vid, nid: hot["est"],
                        admit_min=2)
    # over the per-item cap: rejected outright
    assert not cache.offer(1, 9, b"z" * (2 * cost + 1), 1, 2)
    # free space: admitted without consulting the sketch
    assert cache.offer(1, 1, blob, 1000, 2)
    assert cache.offer(1, 2, blob, 1000, 2)
    # full + cold newcomer (lower bound 0): rejected, no eviction
    assert not cache.offer(1, 3, blob, 1000, 2)
    assert cache.stats()["evictions"] == 0
    # full + hot newcomer: evicts LRU and lands
    hot["est"] = (5, 1)
    assert cache.offer(1, 4, blob, 1000, 2)
    assert cache.get(1, 1) is None
    # forced (reconstructed) entries skip the sketch even when cold
    hot["est"] = (0, 0)
    assert cache.offer(1, 5, blob, 1000, 2, force=True)


def test_flight_exception_propagates_to_waiters():
    cache = NeedleCache(capacity_bytes=1 << 20)
    gate = threading.Event()
    errors = []

    def loader():
        gate.wait(5.0)
        raise NotFoundError("boom")

    def read():
        try:
            cache.get_or_load(1, 1, loader)
        except NotFoundError as e:
            errors.append(str(e))

    threads = [threading.Thread(target=read) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    gate.set()
    for t in threads:
        t.join(5.0)
    assert errors == ["boom"] * 4
    # a failed flight leaves nothing behind: the next reader reloads
    assert cache.get_or_load(1, 1, lambda: (b"ok", 2, 2, False)) \
        == (b"ok", 2, 2)


def test_invalidation_blocks_stale_admission():
    """A load in flight across an invalidation must not re-admit the
    bytes it read before the delete landed."""
    cache = NeedleCache(capacity_bytes=1 << 20)
    loaded = threading.Event()
    release = threading.Event()

    def loader():
        loaded.set()
        release.wait(5.0)
        return b"stale", 5, 2, False

    t = threading.Thread(
        target=lambda: cache.get_or_load(1, 7, loader))
    t.start()
    assert loaded.wait(5.0)
    cache.invalidate(1, 7)       # delete lands mid-load
    release.set()
    t.join(5.0)
    assert cache.get(1, 7) is None
    assert cache.stats()["items"] == 0


# ---- healthy read path through Store ----

def test_healthy_bit_identity_and_mutation_safety(tmp_path):
    store = Store([str(tmp_path / "h")])
    payloads = _fill(store, 3, n_files=6, seed=1)
    store.needle_cache = NeedleCache(capacity_bytes=8 << 20)
    v = store.find_volume(3)
    for nid, data in payloads.items():
        n1 = store.read_volume_needle(3, nid, cookie=0xABC0 + nid - 1)
        assert n1.data == data
        # handler-style in-place mutation of a served needle must not
        # leak into the cache
        n1.data = b"mutated"
        n2 = store.read_volume_needle(3, nid)
        assert n2.data == data
        assert n2.data == v.read_needle(nid).data
    st = store.needle_cache.stats()
    assert st["hits"] >= len(payloads)
    assert st["misses"] == len(payloads)
    # wrong cookie still rejected on the cached path
    from seaweedfs_tpu.storage.volume import CookieMismatchError
    with pytest.raises(CookieMismatchError):
        store.read_volume_needle(3, 1, cookie=0xDEAD)
    store.close()


def test_invalidate_on_delete_and_overwrite(tmp_path):
    store = Store([str(tmp_path / "i")])
    payloads = _fill(store, 4, n_files=3, seed=2)
    store.needle_cache = NeedleCache(capacity_bytes=8 << 20)
    for nid in payloads:
        store.read_volume_needle(4, nid)  # warm the cache
    # delete: the cached entry must not survive
    store.delete_volume_needle(4, 1)
    with pytest.raises((NotFoundError, DeletedError)):
        store.read_volume_needle(4, 1)
    # overwrite: readers see the new generation, not the cached one
    n = Needle(id=2, cookie=0xABC1, data=b"generation-two")
    n.set_flags_from_fields()
    store.write_volume_needle(4, n)
    assert store.read_volume_needle(4, 2).data == b"generation-two"
    assert store.read_volume_needle(4, 2).data == b"generation-two"
    store.close()


# ---- degraded EC path ----

def test_degraded_bit_identity_and_warm_hits(tmp_path):
    store, payloads = _degraded_ec_store(tmp_path)
    store.needle_cache = NeedleCache(capacity_bytes=8 << 20)
    reconstructs = {"n": 0}
    # a degraded read of plain RS asks the coder for one row
    real = store.coder.reconstruct_rows

    def counting(rows, mat):
        reconstructs["n"] += 1
        return real(rows, mat)

    store.coder.reconstruct_rows = counting
    for nid, data in payloads.items():
        assert store.read_ec_shard_needle(1, nid).data == data
    cold = reconstructs["n"]
    assert cold > 0  # the degraded ladder really ran
    for nid, data in payloads.items():
        assert store.read_ec_shard_needle(1, nid).data == data
    assert reconstructs["n"] == cold  # warm reads decode nothing
    st = store.needle_cache.stats()
    assert st["hits"] >= len(payloads)
    store.close()


def test_single_flight_32_concurrent_cold_readers(tmp_path):
    store, payloads = _degraded_ec_store(tmp_path, n_files=4)
    store.needle_cache = NeedleCache(capacity_bytes=8 << 20)
    nid, data = 2, payloads[2]
    decodes = {"n": 0}
    real = store.coder.reconstruct_rows

    def slow_decode(rows, mat):
        decodes["n"] += 1
        time.sleep(0.2)  # hold the flight open so waiters pile up
        return real(rows, mat)

    store.coder.reconstruct_rows = slow_decode
    start = threading.Barrier(32)
    results, errors = [], []

    def read():
        start.wait(10.0)
        try:
            results.append(store.read_ec_shard_needle(1, nid).data)
        except Exception as e:  # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=read) for _ in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    assert not errors
    assert results == [data] * 32
    st = store.needle_cache.stats()
    assert st["misses"] == 1                   # one leader loaded
    assert st["hits"] + st["coalesced"] == 31  # nobody else decoded
    assert st["coalesced"] > 0                 # waiters really parked
    assert decodes["n"] <= 2  # one load's worth of interval decodes
    store.close()


def test_ec_range_read_caches_reconstruction(tmp_path):
    store, payloads = _degraded_ec_store(tmp_path, n_files=6)
    store.needle_cache = NeedleCache(capacity_bytes=8 << 20)
    reconstructs = {"n": 0}
    # a degraded read of plain RS asks the coder for one row
    real = store.coder.reconstruct_rows

    def counting(rows, mat):
        reconstructs["n"] += 1
        return real(rows, mat)

    store.coder.reconstruct_rows = counting
    # find a needle whose range read actually needs recovery
    # (remote_shard_reader is None, so any missing-local interval does)
    for nid, data in payloads.items():
        got = store.read_ec_needle_data_range(1, nid, 10, 100)
        assert got == data[10:110]
    if reconstructs["n"] == 0:
        pytest.skip("no sampled range crossed a missing shard")
    cold = reconstructs["n"]
    for nid, data in payloads.items():
        assert store.read_ec_needle_data_range(1, nid, 500, 64) \
            == data[500:564]
    # every range that decoded once now slices the cached record
    assert reconstructs["n"] == cold
    store.close()


def test_ec_delete_invalidates(tmp_path):
    store, payloads = _degraded_ec_store(tmp_path, n_files=4)
    store.needle_cache = NeedleCache(capacity_bytes=8 << 20)
    assert store.read_ec_shard_needle(1, 3).data == payloads[3]
    store.delete_ec_shard_needle(1, 3)
    with pytest.raises((NotFoundError, DeletedError)):
        store.read_ec_shard_needle(1, 3)
    store.close()


# ---- vacuum invalidation through the server admin plane ----

def test_vacuum_invalidation_via_server(tmp_path):
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils.httpd import http_call, http_json

    master = MasterServer()
    master.start()
    vs = VolumeServer([str(tmp_path / "v")], master.url, qos=False)
    vs.start()
    try:
        from seaweedfs_tpu.client import operation
        from seaweedfs_tpu.client.wdclient import MasterClient
        mc = MasterClient(master.url)
        keep = operation.upload_data(mc, b"K" * 4096, name="keep.bin")
        drop = operation.upload_data(mc, b"D" * 4096, name="drop.bin")
        # warm the cache on both
        for res in (keep, drop):
            status, body, _ = http_call(
                "GET", f"http://{res.url}/{res.fid}")
            assert status == 200
        assert vs.store.needle_cache.stats()["items"] >= 2
        # delete one and vacuum the volume
        status, _, _ = http_call(
            "DELETE", f"http://{drop.url}/{drop.fid}")
        assert status in (200, 202, 204)
        vid = int(drop.fid.split(",")[0])
        http_json("POST", f"http://{vs.url}/admin/vacuum",
                  {"volume_id": vid})
        # compaction rewrote offsets; the cache must have been dropped
        # and the survivor must still read bit-identically
        status, body, _ = http_call(
            "GET", f"http://{keep.url}/{keep.fid}")
        assert status == 200 and body == b"K" * 4096
        status, _, _ = http_call("GET", f"http://{drop.url}/{drop.fid}")
        assert status == 404
        # /admin/cache surfaces the counters
        snap = http_json("GET", f"http://{vs.url}/admin/cache")
        assert snap["enabled"] and "hits" in snap
        # runtime resize down to zero clears the budget
        out = http_json("POST", f"http://{vs.url}/admin/cache",
                        {"capacity_bytes": 0})
        assert out["bytes"] == 0 and out["items"] == 0
    finally:
        vs.stop(graceful=False)
        master.stop()
