"""The host work of a read of an EC volume: the .ecx index searched in
memory without a lock (EcVolume), and a rebuilt read that gathers its
survivors once into the job's operand and asks the coder for one row
(Store._recover_one_row)."""

import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, LrcScheme, RSScheme,
                                        make_coder)
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.erasure_coding import layout
from seaweedfs_tpu.native import rs_native
from seaweedfs_tpu.storage.erasure_coding.ec_volume import (
    EcVolume, NotFoundError, iterate_ecj_file, read_shards_into,
    search_needle_from_sorted_index)
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store

RS63 = RSScheme(6, 3)
KiB, MiB = 1 << 10, 1 << 20


# ------------------------------------------------ the index in memory

def _seeded_index(directory, vid, n, seed=28):
    """A sorted .ecx of n entries: (keys, path)."""
    rng = np.random.default_rng(seed + n)
    keys = sorted({int(x) for x in rng.integers(1, 1 << 62, 2 * n + 8)})[:n]
    path = os.path.join(str(directory), f"{vid}.ecx")
    with open(path, "wb") as f:
        for i, key in enumerate(keys):
            size = t.TOMBSTONE_FILE_SIZE if i % 11 == 5 \
                else int(rng.integers(1, 1 << 20))
            f.write(t.pack_entry(key, 1 + 3 * i, size))
    return keys, path


def _search_file(path, key):
    with open(path, "rb") as f:
        return search_needle_from_sorted_index(f, os.path.getsize(path), key)


@pytest.mark.parametrize("n", [1, 2, 3, 5000])
def test_lookup_in_memory_equals_search_of_the_file(tmp_path, n):
    keys, path = _seeded_index(tmp_path, 9, n)
    ev = EcVolume(str(tmp_path), "", 9)
    try:
        wanted = {keys[0], keys[-1], *keys[::97]}
        for key in sorted(wanted):
            off_units, size = _search_file(path, key)
            assert ev.find_needle_from_ecx(key) == (
                t.offset_to_actual(off_units), size)
        absent = [keys[0] - 1, keys[-1] + 1,
                  next(k + 1 for k in keys if k + 1 not in keys)]
        for key in absent:
            with pytest.raises(NotFoundError):
                _search_file(path, key)
            with pytest.raises(NotFoundError):
                ev.find_needle_from_ecx(key)
        assert ev.stats["ecx_lookups"] == len(wanted) + len(absent)
        assert ev.stats["ecx_file_searches"] == 0
    finally:
        ev.close()


def test_lookup_takes_no_lock(tmp_path):
    keys, _ = _seeded_index(tmp_path, 9, 500)
    ev = EcVolume(str(tmp_path), "", 9)
    got = []
    try:
        with ev._ecx_lock:      # a delete in progress, say
            th = threading.Thread(
                target=lambda: got.append(ev.find_needle_from_ecx(keys[7])))
            th.start()
            th.join(timeout=5)
            assert not th.is_alive(), "the read side waited for _ecx_lock"
        assert got and got[0][0] == t.offset_to_actual(1 + 3 * 7)
    finally:
        ev.close()


def test_delete_is_seen_by_every_lookup_the_file_the_journal_and_a_remount(
        tmp_path):
    keys, path = _seeded_index(tmp_path, 9, 500)
    victim = keys[123]
    assert not t.size_is_deleted(_search_file(path, victim)[1])
    ev = EcVolume(str(tmp_path), "", 9)
    try:
        ev.delete_needle(victim)
        ev.delete_needle(keys[-1] + 1)      # absent: nothing happens
        seen = []

        def look():
            for _ in range(50):
                seen.append(ev.find_needle_from_ecx(victim)[1])

        threads = [threading.Thread(target=look) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(seen) == 400
        assert all(t.size_is_deleted(s) for s in seen)
        # the file on disk, read by another handle while still mounted
        off_units, size = _search_file(path, victim)
        assert size == t.TOMBSTONE_FILE_SIZE
        assert off_units == 1 + 3 * 123          # only the size changed
        assert list(iterate_ecj_file(ev.base_file_name)) == [victim]
        neighbour = ev.find_needle_from_ecx(keys[124])
        assert neighbour == (t.offset_to_actual(1 + 3 * 124),
                             _search_file(path, keys[124])[1])
    finally:
        ev.close()
    again = EcVolume(str(tmp_path), "", 9)
    try:
        assert again.find_needle_from_ecx(victim)[1] == t.TOMBSTONE_FILE_SIZE
        assert again.find_needle_from_ecx(keys[124]) == neighbour
    finally:
        again.close()


def test_lookups_race_deletes_and_never_see_a_torn_entry(tmp_path):
    """More threads than cores at a short switch interval: a lookup
    returns the entry as written or its tombstone, never anything else,
    and the tombstone from the moment delete_needle has returned."""
    import sys
    keys, path = _seeded_index(tmp_path, 9, 2000)
    ev = EcVolume(str(tmp_path), "", 9)
    before = {k: ev.find_needle_from_ecx(k) for k in keys}
    victims = [k for k in keys[::3] if not t.size_is_deleted(before[k][1])]
    deleted = set()             # keys whose delete_needle has RETURNED
    wrong, stop = [], threading.Event()

    def reader(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            key = keys[int(rng.integers(len(keys)))]
            gone = key in deleted
            off, size = ev.find_needle_from_ecx(key)
            if off != before[key][0] or size not in (
                    before[key][1], t.TOMBSTONE_FILE_SIZE) or (
                    gone and size != t.TOMBSTONE_FILE_SIZE):
                wrong.append((key, off, size, gone))

    def deleter(mine):
        for key in mine:
            ev.delete_needle(key)
            deleted.add(key)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        n = 2 * (os.cpu_count() or 4)
        threads = [threading.Thread(target=reader, args=(i,))
                   for i in range(n)]
        threads += [threading.Thread(target=deleter, args=(victims[i::2],))
                    for i in range(2)]
        for th in threads:
            th.start()
        for th in threads[n:]:
            th.join(timeout=60)
        stop.set()
        for th in threads[:n]:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
    assert not wrong, wrong[:3]
    assert sorted(iterate_ecj_file(ev.base_file_name)) == sorted(victims)
    ev.close()
    for key in keys:            # the file agrees, entry by entry
        want = (before[key][0], t.TOMBSTONE_FILE_SIZE
                if key in deleted else before[key][1])
        off_units, size = _search_file(path, key)
        assert (t.offset_to_actual(off_units), size) == want


def test_a_volume_without_an_ecx_is_not_found(tmp_path):
    ev = EcVolume(str(tmp_path), "", 9)
    try:
        assert ev.ecx_file is None
        with pytest.raises(NotFoundError):
            ev.find_needle_from_ecx(1)
        with pytest.raises(NotFoundError):
            ev.delete_needle(1)
    finally:
        ev.close()
    _seeded_index(tmp_path, 9, 3)
    ev = EcVolume(str(tmp_path), "", 9)
    ev.close()                              # unmounted: no index, no file
    with pytest.raises(NotFoundError):
        ev.find_needle_from_ecx(1)


def test_an_index_that_did_not_load_searches_the_file_and_says_so(tmp_path):
    keys, path = _seeded_index(tmp_path, 9, 64)
    stats = {"ecx_lookups": 0, "ecx_file_searches": 0}
    ev = EcVolume(str(tmp_path), "", 9, stats=stats)
    try:
        ev._ecx_index = None                # as after a MemoryError at mount
        key = keys[10]
        off_units, size = _search_file(path, key)
        assert ev.find_needle_from_ecx(key) == (
            t.offset_to_actual(off_units), size)
        ev.delete_needle(key)
        assert ev.find_needle_from_ecx(key)[1] == t.TOMBSTONE_FILE_SIZE
        assert _search_file(path, key)[1] == t.TOMBSTONE_FILE_SIZE
        assert stats == {"ecx_lookups": 0, "ecx_file_searches": 2}
    finally:
        ev.close()


# ------------------------------------------------------ the rebuilt read

KINDS = {"8KiB": 8 * KiB, "64KiB": 64 * KiB, "crossing": 300 * KiB}
CODES = {"rs-10-4": DEFAULT_SCHEME, "rs-6-3": RS63, "lrc": LrcScheme()}


def _sealed_store(directory, code, coder=None, vid=5):
    """A store whose one volume, sealed under `code`, holds 8 KiB, 64 KiB
    and 300 KiB records in every data shard's first block and one 300 KiB
    record across the end of the first block row.  Returns (store,
    {needle id: (kind, payload)})."""
    scheme = CODES[code]
    store = Store([str(directory)], coder=coder or make_coder("cpu"))
    vol = store.add_volume(vid)
    rng = np.random.default_rng(len(code))
    records = {}
    row = scheme.data_shards * layout.SMALL_BLOCK_SIZE

    def put(kind):
        nid = len(records) + 1
        data = rng.bytes(KINDS[kind])
        n = Needle(id=nid, cookie=nid, data=data)
        n.set_flags_from_fields()
        store.write_volume_needle(vid, n)
        records[nid] = (kind, data)

    while vol.content_size() < row + 100 * KiB:
        for kind in KINDS:
            put(kind)
            if 0 < row - vol.content_size() < 290 * KiB:
                put("crossing")
    store.generate_ec_shards(vid, code="" if scheme == DEFAULT_SCHEME
                             else code)
    store.delete_volume(vid)
    store.mount_ec_shards("", vid, list(range(scheme.total_shards)))
    assert store.find_ec_volume(vid).scheme == scheme
    return store, records


def _shards_of(ev, nid):
    """[(shard id, row of blocks)] of the intervals of a needle."""
    intervals, _, _ = ev.locate_needle(nid)
    return [(iv.to_shard_id_and_offset(data_shards=ev.data_shards)[0],
             iv.block_index // ev.data_shards) for iv in intervals]


@pytest.fixture(scope="module", params=list(CODES))
def sealed(request, tmp_path_factory):
    store, records = _sealed_store(
        tmp_path_factory.mktemp(request.param), request.param)
    yield request.param, store, records
    store.close()


@pytest.mark.parametrize("kind", list(KINDS))
def test_rebuilt_read_is_the_healthy_reads_bytes(sealed, kind):
    code, store, records = sealed
    vid, scheme = 5, CODES[code]
    ev = store.find_ec_volume(vid)
    mine = {nid: data for nid, (kd, data) in records.items() if kd == kind}
    where = {nid: _shards_of(ev, nid) for nid in mine}
    if kind == "crossing":
        # the record that ends one block row and begins the next
        mine = {nid: d for nid, d in mine.items()
                if len({r for _, r in where[nid]}) > 1}
        assert len(mine) == 1
        (nid,) = mine
        assert [s for s, _ in where[nid]] == [scheme.data_shards - 1, 0]
    healthy = {nid: store.read_ec_shard_needle(vid, nid, cookie=nid).data
               for nid in mine}
    assert healthy == mine
    rebuilt_under = set()
    for sid in range(scheme.data_shards):
        store.unmount_ec_shards(vid, [sid])
        try:
            touching = [nid for nid in mine
                        if sid in {s for s, _ in where[nid]}]
            for nid in touching[:2] or list(mine)[:1]:
                before = dict(store.ec_read_stats)
                got = store.read_ec_shard_needle(vid, nid, cookie=nid).data
                assert got == healthy[nid], (code, kind, sid, nid)
                rs = store.ec_read_stats
                rebuilt = rs["intervals_recovered"] \
                    - before["intervals_recovered"]
                assert rebuilt == sum(
                    1 for s, _ in where[nid] if s == sid)
                # every rebuilt interval was gathered straight into the
                # rows of its job (RS: the one-row path; LRC: the plan's)
                assert rs["survivor_gathers"] - before["survivor_gathers"] \
                    == rebuilt
                if rebuilt:
                    rebuilt_under.add(sid)
        finally:
            store.mount_ec_shards("", vid, [sid])
    want = {scheme.data_shards - 1, 0} if kind == "crossing" \
        else set(range(scheme.data_shards))
    assert rebuilt_under == want
    rs = store.ec_read_stats
    assert rs["survivor_gathers"] == rs["intervals_recovered"] > 0
    assert rs["ecx_file_searches"] == 0 and rs["ecx_lookups"] > 0
    if type(scheme) is RSScheme:
        # one kept row per (scheme, the k shards read, the shard wanted)
        assert all(m.shape == (1, scheme.data_shards)
                   for m in store._rebuild_rows.values())
        assert {key[2] for key in store._rebuild_rows} >= rebuilt_under


@pytest.mark.parametrize("code", ["rs-10-4", "rs-6-3"])
def test_the_job_reaches_the_scheduler_on_a_rung_with_one_row(
        tmp_path, monkeypatch, code):
    from seaweedfs_tpu.ops.rs_mesh import MeshCoder
    from seaweedfs_tpu.parallel.batcher import (BatchCoder, EcBatchScheduler,
                                                bucket_columns)
    scheme = CODES[code]
    jobs = []
    inner = MeshCoder.rebuild_batch

    def rebuild_batch(self, srcdata, mats):
        jobs.append((self.scheme, srcdata.shape,
                     [np.asarray(m).shape for m in mats]))
        return inner(self, srcdata, mats)

    sched = EcBatchScheduler()
    try:
        store, records = _sealed_store(tmp_path, code, BatchCoder(sched))
        vid, lost = 5, 1
        ev = store.find_ec_volume(vid)
        store.unmount_ec_shards(vid, [lost])
        monkeypatch.setattr(MeshCoder, "rebuild_batch", rebuild_batch)
        before = sched.stats()["by_kind"]["rebuild"]
        read = 0
        for want in KINDS:
            nid = next(n for n, (kd, _) in records.items() if kd == want
                       and lost in {s for s, _ in _shards_of(ev, n)})
            assert store.read_ec_shard_needle(vid, nid, cookie=nid).data \
                == records[nid][1]
            read += 1
        st = sched.stats()
        after = st["by_kind"]["rebuild"]
        assert after["jobs"] - before["jobs"] == len(jobs) >= read
        assert after["bytes_padded"] - before["bytes_padded"] \
            == after["bytes_in"] - before["bytes_in"] > 0
        assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0
        k = scheme.data_shards
        for job_scheme, shape, mats in jobs:
            assert job_scheme == scheme
            assert shape[:2] == (1, k)
            assert shape[2] == bucket_columns(shape[2])     # on a rung
            assert mats == [(1, k)]                         # one row
        rs = store.ec_read_stats
        assert rs["survivor_gathers"] == rs["intervals_recovered"] \
            == len(jobs)
        store.close()
    finally:
        sched.stop()



# ------------------------------------- the survivors gathered in one call

def _without_the_library(monkeypatch):
    monkeypatch.setattr(rs_native, "available", lambda: False)


@pytest.mark.parametrize("library", ["native", "absent"])
def test_read_shards_into_fills_every_row_short_only_at_the_end(
        sealed, monkeypatch, library):
    """One foreign call for all the shards, or a preadv a shard where
    the library is absent: the same rows, the same counts."""
    if library == "absent":
        _without_the_library(monkeypatch)
    elif not rs_native.available():
        pytest.skip("no native library on this machine")
    code, store, _records = sealed
    ev = store.find_ec_volume(5)
    sids = sorted(ev.shards)[:4]
    shards = [ev.shards[s] for s in sids]
    shards[1] = None                       # a row that is the caller's
    size, end = 3000, shards[0].shard_size
    for offset, want in [(0, size), (12345, size), (end - 1000, 1000),
                         (end, 0)]:
        rows = np.full((4, 4096), 7, dtype=np.uint8)
        got = read_shards_into(shards, offset, rows, size)
        assert got == [want, 0, want, want], (code, offset)
        for r, shard in enumerate(shards):
            if shard is None:
                assert (rows[r] == 7).all()
                continue
            assert rows[r, :want].tobytes() == shard.read_at(offset, size)
            assert (rows[r, want:] == 7).all()


def test_pread_rows_refuses_what_does_not_fit_and_reports_a_failed_read(
        tmp_path):
    if not rs_native.available():
        pytest.skip("no native library on this machine")
    path = tmp_path / "f"
    path.write_bytes(b"x" * 100)
    fd = os.open(path, os.O_RDONLY)
    try:
        rows = np.zeros((2, 64), dtype=np.uint8)
        assert rs_native.pread_rows([fd, -1], 90, rows, 64) == [10, 0]
        for bad in [dict(fds=[fd]), dict(size=65), dict(offset=-1),
                    dict(rows=rows.astype(np.uint16)),
                    dict(rows=rows[:, ::2]), dict(rows=rows[0])]:
            kw = {**dict(fds=[fd, -1], offset=0, rows=rows, size=8), **bad}
            with pytest.raises(ValueError):
                rs_native.pread_rows(kw["fds"], kw["offset"], kw["rows"],
                                     kw["size"])
        frozen = rows.copy()
        frozen.flags.writeable = False
        with pytest.raises(ValueError):
            rs_native.pread_rows([fd, -1], 0, frozen, 8)
    finally:
        os.close(fd)
    with pytest.raises(OSError):
        rs_native.pread_rows([fd, -1], 0, rows, 8)   # a closed descriptor


@pytest.mark.parametrize("library", ["native", "absent"])
def test_a_rebuilt_interval_gathers_its_survivors_in_one_call(
        sealed, monkeypatch, library):
    """The mounted survivors of a rebuilt interval (k for plain RS, the
    plan's group for LRC) are read by ONE call of the library (the
    interpreter lock given away once, not once a shard), and without
    the library the read is the same bytes."""
    code, store, records = sealed
    scheme = CODES[code]
    calls = []
    if library == "absent":
        _without_the_library(monkeypatch)
    elif not rs_native.available():
        pytest.skip("no native library on this machine")
    else:
        real = rs_native.pread_rows

        def counted(fds, offset, rows, size):
            calls.append(sum(1 for fd in fds if fd >= 0))
            return real(fds, offset, rows, size)
        monkeypatch.setattr(rs_native, "pread_rows", counted)
    vid, lost = 5, 2
    ev = store.find_ec_volume(vid)
    nid = next(n for n, (kd, _) in records.items() if kd == "64KiB"
               and lost in {s for s, _ in _shards_of(ev, n)})
    store.unmount_ec_shards(vid, [lost])
    try:
        before = store.ec_read_stats["intervals_recovered"]
        assert store.read_ec_shard_needle(vid, nid, cookie=nid).data \
            == records[nid][1]
        rebuilt = store.ec_read_stats["intervals_recovered"] - before
    finally:
        store.mount_ec_shards("", vid, [lost])
    assert rebuilt >= 1
    if library == "native":
        assert len(calls) == rebuilt
        if type(scheme) is RSScheme:
            assert calls == [scheme.data_shards] * rebuilt
        else:
            assert all(1 < n < scheme.data_shards for n in calls)
