"""Reads of the filer's chunk needles (4 MiB and 2 MiB) from a sealed
RS(10,4) volume with a shard gone: the deployment ``warp-chunks-holder-
down`` (benchmark/configs), at small counts and the REAL block size of
1 MiB, through the store's ``BatchCoder`` on the CPU backend.

The reference of a read is independent of the EC path: the record's
bytes cut from the ``.dat`` by its ``.idx`` entry, and for a rebuilt
block ``benchmark/reference.py``'s ``recover_matrix`` + ``apply_matrix``
over the survivors' spans of the shard files.
"""

import json
import os
import shutil
import struct
import threading

import numpy as np
import pytest

from benchmark import reference
from seaweedfs_tpu.models.coder import DEFAULT_SCHEME
from seaweedfs_tpu.ops.rs_mesh import MeshCoder
from seaweedfs_tpu.parallel.batcher import (MAX_DISPATCH_COLUMNS, BatchCoder,
                                            EcBatchScheduler)
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.erasure_coding import layout
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.needle_cache import NeedleCache
from seaweedfs_tpu.storage.store import Store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MiB = 1 << 20
K, M = DEFAULT_SCHEME.data_shards, DEFAULT_SCHEME.parity_shards
VID = 7
# 42 MiB of chunk needles in this order (MiB of data each); with the
# few dozen bytes a record adds, needle i starts just past the sum of
# those before it.  Blocks of 1 MiB, rows of ten: needle 2 (blocks 8-11)
# and needle 5 (18-21) cross the end of a row; the last (40-41) lies in
# the zero-filled fifth row
SIZES_MIB = (4, 4, 4, 2, 4, 4, 2, 4, 2, 4, 4, 2, 2)
PARITY_SHARD = 12
LOST = (3, 0, 9, PARITY_SHARD)


class _Sealed:
    """The sealed volume, its scheduler and the reference's view of it."""

    def __init__(self, directory: str):
        self.sched = EcBatchScheduler(
            mesh_coder=MeshCoder(DEFAULT_SCHEME, n_devices=1))
        self.store = Store([directory], coder=BatchCoder(self.sched))
        self.store.needle_cache = NeedleCache(64 << 20)
        self.store.add_volume(VID)
        rng = np.random.default_rng(31)
        self.payload = {}
        for i, mib in enumerate(SIZES_MIB):
            nid = i + 1
            n = Needle(id=nid, cookie=nid, data=rng.bytes(mib * MiB))
            n.set_flags_from_fields()
            self.store.write_volume_needle(VID, n)
            self.payload[nid] = n.data
        self.store.generate_ec_shards(VID)
        # the reference's side: the .dat and its .idx, kept aside
        base = os.path.join(directory, str(VID))
        self.ref_dir = os.path.join(directory, "ref")
        os.mkdir(self.ref_dir)
        for ext in (".dat", ".idx"):
            shutil.copy(base + ext, self.ref_dir)
        self.base = base
        self.store.delete_volume(VID)
        self.store.mount_ec_shards("", VID,
                                   list(range(DEFAULT_SCHEME.total_shards)))
        self.ev = self.store.find_ec_volume(VID)
        self.entries = self._idx_entries()

    def _idx_entries(self) -> dict[int, tuple[int, int]]:
        """{needle id: (byte offset, record bytes)} from the .idx."""
        raw = open(os.path.join(self.ref_dir, f"{VID}.idx"), "rb").read()
        out = {}
        for off in range(0, len(raw), 16):
            key, units, size = struct.unpack_from(">QIi", raw, off)
            out[key] = (units * 8, t.get_actual_size(size, self.ev.version))
        return out

    def record_from_dat(self, nid: int) -> bytes:
        offset, length = self.entries[nid]
        with open(os.path.join(self.ref_dir, f"{VID}.dat"), "rb") as f:
            f.seek(offset)
            return f.read(length)

    def blocks(self, nid: int) -> list[tuple[int, int, int]]:
        """[(block index, offset in the block, bytes)] of a record."""
        offset, length = self.entries[nid]
        out = []
        while length:
            b, inner = divmod(offset, MiB)
            n = min(length, MiB - inner)
            out.append((b, inner, n))
            offset, length = offset + n, length - n
        return out

    def reference_block(self, block: int, lost: int) -> bytes:
        """Block ``block`` of the .dat (a block of shard ``lost``), from
        the first ten OTHER shard files' bytes of the same row."""
        row, sid = divmod(block, K)
        assert sid == lost
        present = [s for s in range(K + M) if s != lost]
        src = sorted(present)[:K]
        rows = np.empty((K, MiB), dtype=np.uint8)
        for r, s in enumerate(src):
            with open(self.base + layout.shard_ext(s), "rb") as f:
                f.seek(row * MiB)
                rows[r] = np.frombuffer(f.read(MiB), dtype=np.uint8)
        mat = reference.recover_matrix(K, M, present, [lost])
        return reference.apply_matrix(mat, rows)[0].tobytes()

    def lose(self, sid: int) -> None:
        self.store.unmount_ec_shards(VID, [sid])    # clears its records

    def restore(self, sid: int) -> None:
        self.store.mount_ec_shards("", VID, [sid])

    def close(self) -> None:
        self.store.close()
        self.sched.stop()


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    s = _Sealed(str(tmp_path_factory.mktemp("chunks")))
    yield s
    s.close()


def _kinds(sealed, lost: int) -> dict[str, list[int]]:
    """Needles by where their block of shard ``lost`` lies in them."""
    out: dict[str, list[int]] = {}
    for nid in sealed.entries:
        blocks = sealed.blocks(nid)
        for i, (b, _inner, _n) in enumerate(blocks):
            if b % K != lost:
                continue
            kind = "first" if i == 0 else "tail" if i == len(blocks) - 1 \
                else "last-but-tail" if i == len(blocks) - 2 else "inner"
            out.setdefault(kind, []).append(nid)
        rows = {b // K for b, _i, _n in blocks[:-1]}
        if len(rows) > 1:
            out.setdefault("crosses-a-row", []).append(nid)
        if blocks[0][0] // K == 4:
            out.setdefault("last-row", []).append(nid)
    return out


def test_the_layout_is_the_one_the_cases_need(sealed):
    assert len(sealed.entries) == len(SIZES_MIB)
    for nid, mib in enumerate(SIZES_MIB, 1):
        blocks = sealed.blocks(nid)
        # a chunk touches one block more than its MiB: 5 or 3 intervals
        assert len(blocks) == mib + 1
        intervals, _off, _size = sealed.ev.locate_needle(nid)
        assert [iv.size for iv in intervals] == [n for _b, _i, n in blocks]
    # the shard files hold five rows; the fifth is zero past 2 MiB + a bit
    assert os.path.getsize(sealed.base + ".ec00") == 5 * MiB
    with open(sealed.base + ".ec05", "rb") as f:
        f.seek(4 * MiB)
        assert f.read(MiB) == bytes(MiB)
    kinds = {lost: _kinds(sealed, lost) for lost in LOST[:3]}
    assert kinds[0]["first"] and kinds[0]["inner"]
    assert kinds[9]["inner"] and kinds[3]["last-but-tail"]
    assert kinds[0]["crosses-a-row"] == [3, 6]
    assert kinds[0]["last-row"] == [13]


@pytest.mark.parametrize("lost", LOST)
def test_every_chunk_reads_as_the_dat_has_it_with_a_shard_gone(sealed, lost):
    store = sealed.store
    sealed.lose(lost)
    try:
        before = dict(store.ec_read_stats)
        jobs_before = sealed.sched.stats()["jobs_total"]
        want_rebuilt = want_bytes = 0
        for nid, payload in sealed.payload.items():
            n = store.read_ec_shard_needle(VID, nid, cookie=nid)
            assert n.data == payload, (lost, nid)
            blob = store.needle_cache.get(VID, nid)[0]
            assert blob == sealed.record_from_dat(nid), (lost, nid)
            for b, inner, size in sealed.blocks(nid):
                if b % K != lost:
                    continue
                want_rebuilt += 1
                want_bytes += size
                # the rebuilt block, by the plain reference from the
                # survivors' files, is what the .dat holds there and what
                # the store put into the record
                whole = sealed.reference_block(b, lost)
                offset = sealed.entries[nid][0]
                at = b * MiB + inner - offset
                assert whole[inner:inner + size] == blob[at:at + size]
        rs = store.ec_read_stats
        d = {k: rs[k] - before[k] for k in rs}
        assert d["records_loaded"] == len(SIZES_MIB)
        assert d["record_intervals"] == sum(SIZES_MIB) + len(SIZES_MIB)
        assert d["record_bytes"] == sum(
            n for _o, n in sealed.entries.values())
        assert d["intervals_recovered"] == d["survivor_gathers"] \
            == want_rebuilt
        assert d["recovered_bytes"] == want_bytes
        assert d["survivor_bytes"] == K * want_bytes
        st = sealed.sched.stats()
        assert st["jobs_total"] - jobs_before == want_rebuilt
        if lost == PARITY_SHARD:
            assert want_rebuilt == 0     # a parity shard: nothing rebuilt
        else:
            assert want_rebuilt >= 4 and want_bytes >= 4 * MiB - 64
        assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0
    finally:
        sealed.restore(lost)


@pytest.mark.parametrize("lost,kind", [
    (0, "first"), (0, "inner"), (9, "inner"), (3, "last-but-tail"),
    (0, "tail"), (0, "crosses-a-row"), (9, "crosses-a-row"),
    (0, "last-row")])
def test_a_chunk_whose_lost_block_lies_so(sealed, lost, kind):
    """One needle of each kind, alone: its record is the .dat's bytes,
    its one rebuilt interval a job on the rung its bytes give."""
    store = sealed.store
    nid = _kinds(sealed, lost)[kind][0]
    mine = [(b, n) for b, _i, n in sealed.blocks(nid) if b % K == lost]
    sealed.lose(lost)
    try:
        before = dict(store.ec_read_stats)
        rungs = sealed.sched.stats()["by_rung"]
        n = store.read_ec_shard_needle(VID, nid, cookie=nid)
        assert n.data == sealed.payload[nid]
        assert store.needle_cache.get(VID, nid)[0] \
            == sealed.record_from_dat(nid)
        rs = store.ec_read_stats
        assert rs["intervals_recovered"] - before["intervals_recovered"] \
            == len(mine) == 1
        assert rs["recovered_bytes"] - before["recovered_bytes"] \
            == mine[0][1]
        after = sealed.sched.stats()["by_rung"]
        rung = str(MiB if mine[0][1] > 256 << 10 else 256 << 10)
        assert after[rung]["jobs"] \
            - rungs.get(rung, {"jobs": 0})["jobs"] == 1
        # a whole block but for the first needle's (the superblock's 8 B)
        # and a tail's few dozen bytes
        if kind in ("inner", "last-but-tail"):
            assert mine[0][1] == MiB
        if kind == "tail":
            assert mine[0][1] < 1024
    finally:
        sealed.restore(lost)


def test_the_counters_follow_the_layouts_arithmetic(sealed):
    """Lost shard 3, every chunk read once from a cold cache: a record is
    joined from 55 / 13 intervals on average, the four blocks of shard 3
    (3, 13, 23, 33) each lie whole in one needle, so four records are
    rebuilt and each rebuilt interval is exactly one block."""
    store = sealed.store
    sealed.lose(3)
    try:
        before = dict(store.ec_read_stats)
        for nid in sealed.payload:
            store.read_ec_shard_needle(VID, nid, cookie=nid)
        d = {k: store.ec_read_stats[k] - before[k] for k in before}
        assert d["record_intervals"] / d["records_loaded"] \
            == pytest.approx(55 / 13)
        assert d["intervals_recovered"] == 4
        assert d["recovered_bytes"] == 4 * MiB
        assert d["intervals_local"] == 55 - 4
        rebuilt = [nid for nid in sealed.payload if any(
            b % K == 3 for b, _i, _n in sealed.blocks(nid))]
        assert len(rebuilt) == 4
        # a second pass is served from the needle cache: nothing is read
        again = dict(store.ec_read_stats)
        hits = store.needle_cache.stats()["hits"]
        for nid in sealed.payload:
            assert store.read_ec_shard_needle(
                VID, nid, cookie=nid).data == sealed.payload[nid]
        assert store.needle_cache.stats()["hits"] - hits == len(SIZES_MIB)
        for key in ("records_loaded", "intervals_local",
                    "intervals_recovered", "recovered_bytes"):
            assert store.ec_read_stats[key] == again[key], key
    finally:
        sealed.restore(3)


def test_a_full_cache_keeps_the_rebuilt_chunk_and_not_the_healthy_one(
        sealed):
    """The cell's 64 MiB cache: a 4 MiB record is under its largest item
    (capacity / 8); once it is full a healthy record that the sketch does
    not know is refused and a rebuilt one admitted by force."""
    store = sealed.store
    cache = NeedleCache(64 << 20, hot_fn=lambda vid, nid: (0, 0))
    assert t.get_actual_size(4 * MiB + 64, 3) < cache.max_item_bytes()
    was, store.needle_cache = store.needle_cache, cache
    sealed.lose(3)
    try:
        healthy = [nid for nid, mib in enumerate(SIZES_MIB, 1) if mib == 4
                   and all(b % K != 3 for b, _i, _n in sealed.blocks(nid))]
        rebuilt = [nid for nid, mib in enumerate(SIZES_MIB, 1) if mib == 4
                   and any(b % K == 3 for b, _i, _n in sealed.blocks(nid))]
        nid = healthy[0]
        store.read_ec_shard_needle(VID, nid, cookie=nid)
        assert cache.contains(VID, nid) and cache.stats()["rejects"] == 0
        # fill it to the brim with records of other volumes
        filler = bytes(4 * MiB)
        i = 0
        while cache.offer(99, i, filler, len(filler), 3):
            i += 1
        assert cache.stats()["bytes"] + len(filler) > cache.capacity_bytes
        other = healthy[1]
        store.read_ec_shard_needle(VID, other, cookie=other)
        assert not cache.contains(VID, other)       # not proven hot
        evictions = cache.stats()["evictions"]
        forced = rebuilt[0]
        store.read_ec_shard_needle(VID, forced, cookie=forced)
        assert cache.contains(VID, forced)
        assert cache.stats()["evictions"] > evictions
        # a hit reads no interval
        before = dict(store.ec_read_stats)
        assert store.read_ec_shard_needle(
            VID, forced, cookie=forced).data == sealed.payload[forced]
        assert store.ec_read_stats["intervals_local"] \
            == before["intervals_local"]
        assert store.ec_read_stats["records_loaded"] \
            == before["records_loaded"]
    finally:
        store.needle_cache = was
        sealed.restore(3)


class _Gated:
    """A mesh coder whose launches wait for a gate (tests/
    test_mesh_batcher.py): what is submitted meanwhile queues up."""

    def __init__(self, inner):
        self.inner = inner
        self.n_devices = inner.n_devices
        self.gate = threading.Event()
        self.entered = threading.Event()
        self.batches = []

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def rebuild_batch_begin(self, s, mats):
        self.entered.set()
        assert self.gate.wait(120)
        self.batches.append(s.shape)
        return self.inner.rebuild_batch_begin(s, mats)


def test_twenty_readers_rebuilding_a_block_each_at_once():
    """20 threads submit a 10 x 1 MiB rebuild job each while a dispatch
    is held: every row equals the reference's, the group of 20 is cut
    into dispatches of at most MAX_DISPATCH_COLUMNS // 1 MiB = 4 jobs,
    nothing runs on the host coder, and the counters add up."""
    gated = _Gated(MeshCoder(DEFAULT_SCHEME, n_devices=1))
    sched = EcBatchScheduler(mesh_coder=gated)
    coder = BatchCoder(sched)
    cap = MAX_DISPATCH_COLUMNS // MiB
    assert cap == 4
    n_threads = 20
    rng = np.random.default_rng(3100)
    operands, mats, got = [], [], [None] * (n_threads + 1)
    for i in range(n_threads + 1):
        rows = coder.job_rows(MiB)
        assert rows.shape == (K, MiB) and not rows.any()
        rows[:] = rng.integers(0, 256, rows.shape, dtype=np.uint8)
        lost = i % (K + M)
        present = [s for s in range(K + M) if s != lost]
        operands.append(rows)
        mats.append((present, lost))

    def rebuild(i):
        present, lost = mats[i]
        mat = np.asarray(coder.rebuild_matrix(present, [lost]),
                         dtype=np.uint8)
        got[i] = coder.reconstruct_rows(operands[i], mat)

    threads = [threading.Thread(target=rebuild, args=(i,))
               for i in range(n_threads + 1)]
    try:
        threads[0].start()                  # the plug: held at the gate
        assert gated.entered.wait(120)
        for th in threads[1:]:
            th.start()
        deadline = 120.0
        while sched.stats()["queued"] < n_threads and deadline > 0:
            threading.Event().wait(0.01)
            deadline -= 0.01
        assert sched.stats()["queued"] == n_threads
        gated.gate.set()
        for th in threads:
            th.join(timeout=300)
            assert not th.is_alive()
    finally:
        gated.gate.set()
        sched.stop()
    for i, (present, lost) in enumerate(mats):
        want = reference.apply_matrix(
            reference.recover_matrix(K, M, present, [lost]), operands[i])
        assert got[i].shape == (1, MiB)
        assert np.array_equal(got[i], want), i
    st = sched.stats()
    assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0
    assert st["jobs_total"] == n_threads + 1
    assert st["max_coalesced"] == n_threads     # one drain of the queue...
    assert st["cap_splits"] == 1                # ...cut by the column cap
    rung = st["by_rung"][str(MiB)]
    assert st["by_rung"] == {str(MiB): rung}
    assert rung == {"jobs": n_threads + 1,
                    "mesh_dispatches": 1 + n_threads // cap,
                    "max_coalesced": cap}
    assert st["mesh_batches"] == rung["mesh_dispatches"]
    assert st["lone_dispatches"] == 1
    assert [s[0] for s in gated.batches] == [1] + [cap] * (n_threads // cap)
    assert all(s[1:] == (K, MiB) for s in gated.batches)
    # what the cell warms is what such a queue dispatches: B = 1, 2, 4
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "degraded1.chunks.json")) as f:
        warm = json.load(f)["warm"]["apply"]
    assert [1, MiB] in warm and [cap, MiB] in warm
    assert all(b <= cap and n == MiB for b, n in warm)


@pytest.mark.parametrize("seed", [1, 2147483659, 3141592653])
def test_the_cells_fill_puts_every_lost_block_whole_inside_one_needle(seed):
    """benchmark/configs/warp-chunks-holder-down.json states it and
    ``kernel_roofline.read``'s bytes rest on it: in the fill's own
    seeded order (benchmark/loadgen.fill) the 306 chunk needles tile
    1,020 blocks, a needle touches 3 or 5 intervals, and each of the 102
    blocks of lost shard 3 lies whole inside exactly one needle: one
    read in three is rebuilt, every rebuilt interval is 1,048,576 B."""
    from benchmark import loadgen
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "degraded1.chunks.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "warp-chunks-holder-down.json")) as f:
        config = json.load(f)
    (lost,) = config["lost_shards"]
    sizes = [c["bytes"] for c in traffic["fill"] for _ in range(c["count"])]
    for vid in (1, 2):
        plan = [int(s) for s in
                loadgen.rng_for(seed, 1, vid).permutation(sizes)]
        offset = 8                              # the superblock
        touched = []
        rebuilt = {}
        for pos, size in enumerate(plan):
            # a record: header, data size, data, flags, checksum,
            # timestamp, padded to 8 (no name, no mime: loadgen.fill)
            length = t.get_actual_size(4 + size + 1, 3)
            first, last = offset // MiB, (offset + length - 1) // MiB
            touched.append(last - first + 1)
            for b in range(first, last + 1):
                if b % K != lost:
                    continue
                lo = max(offset, b * MiB)
                hi = min(offset + length, (b + 1) * MiB)
                rebuilt.setdefault(pos, []).append(hi - lo)
            offset += length
        assert offset < 1021 * MiB and offset // MiB == 1020
        assert sorted(set(touched)) == [3, 5]
        assert sum(touched) / len(touched) == pytest.approx(4.333, abs=1e-3)
        assert len(rebuilt) == 102 == len(plan) // 3
        assert all(v == [MiB] for v in rebuilt.values())
    assert json.load(open(os.path.join(
        REPO, "benchmark", "metrics", "kernel_roofline.read.json")))[
        "params"]["bytes_per_count"] == (K + 1) * MiB


# ------------------------------------ the same through the CLI servers

@pytest.fixture(scope="module")
def served():
    """CLI master + CLI ``volume -ecBatcher`` behind the benchmark's
    wrapper: 40 MiB of chunk needles filled, sealed and degraded by the
    benchmark's own ``fill`` and ``prepare`` under the cell's
    configuration file."""
    from benchmark import loadgen
    from benchmark.harness import Cluster
    with open(os.path.join(REPO, "benchmark", "configs",
                           "warp-chunks-holder-down.json")) as f:
        config = json.load(f)
    cluster = Cluster()
    try:
        # one CPU device, like one chip
        cluster.start({"encode": [], "apply": []},
                      config["volume_size_limit_mb"],
                      config["servers"]["max_volumes"],
                      {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
        corpus = loadgen.fill(cluster, {"volumes": 1, "fill": [
            {"bytes": 4 * MiB, "count": 8}, {"bytes": 2 * MiB, "count": 4}]},
            31)
        loadgen.prepare(cluster, config, corpus)
        yield cluster, corpus
    except BaseException:
        cluster.print_log_tails()
        raise
    finally:
        cluster.stop()
        cluster.cleanup()


def test_the_servers_show_the_new_counters_after_a_run(served):
    """Every chunk read once by six readers through ``client.operation.
    read_data``: ``shard_stat``'s ``read_stats`` follow the layout (12
    records of 52 intervals; the four blocks of shard 3 rebuilt, 1 MiB
    each), ``/admin/ec/batcher`` says which rung they rode, ``/metrics``
    carries both."""
    import hashlib
    from concurrent.futures import ThreadPoolExecutor
    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.utils.httpd import http_call
    cluster, corpus = served
    (vid,) = corpus.vids
    stat_url = cluster.volume + f"/admin/ec/shard_stat?volumeId={vid}"
    before = cluster.http("GET", stat_url)["read_stats"]
    mc = MasterClient(cluster.master)

    def read(fid):
        data = operation.read_data(mc, fid)
        return hashlib.sha256(data).hexdigest() == corpus.objects[fid][0]

    with ThreadPoolExecutor(max_workers=6) as pool:
        assert all(pool.map(read, corpus.fids()))
    after = cluster.http("GET", stat_url)["read_stats"]
    d = {k: after[k] - before[k] for k in after}
    assert d["records_loaded"] == 12
    assert d["record_intervals"] == 8 * 5 + 4 * 3
    assert d["record_bytes"] == sum(
        t.get_actual_size(4 + size + 1, 3)
        for _digest, size in corpus.objects.values())
    assert d["intervals_recovered"] == 4 and d["recovered_bytes"] == 4 * MiB
    assert d["survivor_bytes"] == K * 4 * MiB
    b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
    assert b["cpu_batches"] == 0 and b["coder_fallbacks"] == 0
    rung = b["by_rung"][str(MiB)]
    # the seal's 5 encode jobs of a row each and the 4 rebuilds
    assert rung["jobs"] == 5 + 4
    assert 1 <= rung["max_coalesced"] <= MAX_DISPATCH_COLUMNS // MiB
    assert sum(r["jobs"] for r in b["by_rung"].values()) == b["jobs_total"]
    assert sum(r["mesh_dispatches"] for r in b["by_rung"].values()) \
        == b["mesh_batches"]
    assert b["cap_splits"] == 0
    _st, text, _ = http_call("GET", f"http://{cluster.volume}/metrics")
    lines = text.decode().splitlines()
    rung_lines = [ln for ln in lines if "ec_batch_rung{" in ln]
    assert any(f'rung="{MiB}"' in ln and 'stat="jobs"' in ln
               and float(ln.rsplit(" ", 1)[1]) == 9
               for ln in rung_lines), rung_lines
    assert any("volumeServer_ec_batch_cap_splits " in ln
               and float(ln.rsplit(" ", 1)[1]) == 0 for ln in lines
               if not ln.startswith("#"))
    # PR 36: how often the window of two dispatches engaged
    assert 0 <= b["overlapped_dispatches"] < b["mesh_batches"]
    assert any("volumeServer_ec_batch_overlapped " in ln
               and float(ln.rsplit(" ", 1)[1]) == b["overlapped_dispatches"]
               for ln in lines if not ln.startswith("#"))
    # a second pass is the needle cache's: nothing is loaded again
    with ThreadPoolExecutor(max_workers=6) as pool:
        assert all(pool.map(read, corpus.fids()))
    again = cluster.http("GET", stat_url)["read_stats"]
    assert again["records_loaded"] == after["records_loaded"]
    assert again["intervals_recovered"] == after["intervals_recovered"]
