"""A code geometry is a property of the VOLUME: RS(6,3) — HDFS's
RS-6-3-1024k policy — sealed, served and rebuilt as a 9-shard volume
beside RS(10,4) volumes, through the batch scheduler.

(a) the parity matrix and a sealed tiny volume against the benchmark's
    plain reference; (b) one scheduler under two geometries from several
    threads; (c) a 9-shard volume through the CLI servers: encode, mount,
    healthy and degraded reads, a fourth loss refused, rebuild, decode;
(d) the one parser from a request's ``code`` to a scheme.
"""

import hashlib
import json
import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, CodeSpecError,
                                        LrcScheme, RSScheme, code_spec_name,
                                        parse_code_spec)
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.rs_cpu import CpuCoder
from seaweedfs_tpu.parallel.batcher import BatchCoder, EcBatchScheduler

RS63 = RSScheme(6, 3)


# ------------------------------------------------------ (d) the parser

@pytest.mark.parametrize("spec,want", [
    ("rs-6-3", RS63), ("RS-6-3", RS63), ("rs-12-4", RSScheme(12, 4)),
    ("rs", DEFAULT_SCHEME), ("", DEFAULT_SCHEME), (None, DEFAULT_SCHEME),
    ("lrc", LrcScheme()),
])
def test_code_spec_parses(spec, want):
    got = parse_code_spec(spec)
    assert got == want and type(got) is type(want)


def test_code_spec_default_is_the_servers_own():
    assert parse_code_spec("", RS63) == RS63
    assert parse_code_spec("rs", RS63) == RS63
    assert parse_code_spec("rs-10-4", RS63) == DEFAULT_SCHEME


@pytest.mark.parametrize("spec", ["pallas", "cpu", "cpu-mt", "mesh", "lrc-mt",
                                  "rs-0-3", "rs-6-0", "rs-200-100",
                                  "rs-30-3", "rs-6", "rs-6-3-1", "rs--6-3",
                                  "rs-a-b", "6-3"])
def test_code_spec_refuses(spec):
    with pytest.raises(CodeSpecError):
        parse_code_spec(spec)


@pytest.mark.parametrize("scheme", [RS63, DEFAULT_SCHEME, LrcScheme()])
def test_code_spec_name_round_trips(scheme):
    name = code_spec_name(scheme)
    assert name == {RS63: "rs-6-3", DEFAULT_SCHEME: "rs-10-4"}.get(
        scheme, "lrc-10-2-2")
    if type(scheme) is RSScheme:
        assert parse_code_spec(name) == scheme


# ---------------------------------- (a) against the benchmark's reference

def test_parity_matrix_6_3_is_the_references():
    from benchmark import reference
    ours = np.asarray(gf256.parity_matrix(6, 3), dtype=np.uint8)
    theirs = np.asarray(reference.parity_matrix(6, 3), dtype=np.uint8)
    assert ours.shape == (3, 6)
    assert np.array_equal(ours, theirs)


def _tiny_store(tmp_path, coder):
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path)], coder=coder)
    store.add_volume(7)
    rng = np.random.default_rng(63)
    for key in range(1, 30):
        n = Needle(cookie=key, id=key,
                   data=rng.bytes(int(rng.integers(200, 9000))))
        store.write_volume_needle(7, n)
    return store


def test_store_seals_rs6_3_through_the_scheduler(tmp_path):
    from benchmark import reference
    sched = EcBatchScheduler()
    try:
        store = _tiny_store(tmp_path, BatchCoder(sched))
        with pytest.raises(CodeSpecError):
            store.generate_ec_shards(7, code="pallas")
        base = store.generate_ec_shards(7, code="rs-6-3")
        files = [f"{base}.ec{s:02d}" for s in range(9)]
        assert all(os.path.exists(p) for p in files)
        assert not os.path.exists(f"{base}.ec09")
        with open(base + ".vif") as f:
            assert json.load(f)["code"] == {
                "family": "rs", "data_shards": 6, "parity_shards": 3}
        assert reference.differing_shard_files(
            base + ".dat", files, 6, 3, 1 << 30, 1 << 20) == []
        st = sched.stats()
        spec = st["by_spec"]["rs-6-3"]
        assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0
        assert spec["jobs"] >= 1
        assert spec["mesh_dispatches"] == spec["jobs"]   # one at a time
        assert spec["cpu_dispatches"] == 0 and spec["programs"] >= 1
        assert "rs-10-4" not in st["by_spec"]
        # the store hands every scheme, of whatever family, a facade
        # over ONE scheduler
        c63 = store.coder_for_scheme(RS63)
        assert isinstance(c63, BatchCoder) and c63.scheduler is sched
        assert store.coder_for_scheme(RS63) is c63
        clrc = store.coder_for_scheme(LrcScheme())
        assert isinstance(clrc, BatchCoder) and clrc.scheduler is sched
        assert clrc.scheme == LrcScheme() and clrc is not c63
        store.close()
    finally:
        sched.stop()


# --------------------------- (b) one scheduler, two geometries, threads

class _Watch:
    """Wraps a MeshCoder class so that every dispatch is recorded as
    (scheme, kind, rows per lane)."""

    def __init__(self):
        self.seen = []
        self.lock = threading.Lock()

    def install(self, monkeypatch):
        from seaweedfs_tpu.ops.rs_mesh import MeshCoder
        enc, reb = MeshCoder.encode_batch, MeshCoder.rebuild_batch
        watch = self

        def encode_batch(self, batch):
            with watch.lock:
                watch.seen.append((self.scheme, "encode", batch.shape))
            return enc(self, batch)

        def rebuild_batch(self, srcdata, mats):
            with watch.lock:
                watch.seen.append((self.scheme, "rebuild", srcdata.shape))
            return reb(self, srcdata, mats)

        monkeypatch.setattr(MeshCoder, "encode_batch", encode_batch)
        monkeypatch.setattr(MeshCoder, "rebuild_batch", rebuild_batch)


def test_two_geometries_share_a_scheduler_never_a_dispatch(monkeypatch):
    watch = _Watch()
    watch.install(monkeypatch)
    sched = EcBatchScheduler()
    coders = {s: BatchCoder(sched, s) for s in (DEFAULT_SCHEME, RS63)}
    hosts = {s: CpuCoder(s) for s in coders}
    errors, done = [], []

    def worker(idx: int) -> None:
        rng = np.random.default_rng(600 + idx)
        try:
            for it in range(6):
                scheme = (DEFAULT_SCHEME, RS63)[(idx + it) % 2]
                k, total = scheme.data_shards, scheme.total_shards
                n = int(rng.choice([256, 1000, 4096]))
                data = rng.integers(0, 256, (k, n), dtype=np.uint8)
                # plain table arithmetic (ops/gf256.py) is the yardstick
                want = gf256.gf_matmul(gf256.parity_matrix(
                    k, scheme.parity_shards), data)
                assert np.array_equal(hosts[scheme].encode_array(data),
                                      want)
                got = coders[scheme].encode_array(data)
                assert got.shape == want.shape and \
                    np.array_equal(got, want), ("encode", scheme)
                full = np.concatenate([data, want])
                lost = sorted(int(x) for x in rng.choice(
                    total, size=int(rng.integers(1, total - k + 1)),
                    replace=False))
                present = [s for s in range(total) if s not in lost]
                mat = coders[scheme].rebuild_matrix(present, lost)
                rec = coders[scheme].reconstruct_rows(
                    full[present[:k]], mat)
                assert np.array_equal(rec, full[lost]), ("rebuild", scheme)
                done.append((scheme, 2))
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        assert errors == []
        st = sched.stats()
    finally:
        sched.stop()
    assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0
    assert st["jobs_total"] == 6 * 6 * 2
    by_spec = st["by_spec"]
    assert set(by_spec) == {"rs-10-4", "rs-6-3"}
    assert sum(v["jobs"] for v in by_spec.values()) == st["jobs_total"]
    assert sum(v["mesh_dispatches"] for v in by_spec.values()) \
        == st["mesh_batches"]
    for scheme in coders:
        mine = sum(n for s, n in done if s == scheme)
        assert by_spec[code_spec_name(scheme)]["jobs"] == mine
        assert by_spec[code_spec_name(scheme)]["cpu_dispatches"] == 0
    assert sum(v["programs"] for v in by_spec.values()) \
        == st["programs_compiled"]
    assert sum(v["bytes_out"] for v in by_spec.values()) \
        == sum(v["bytes_out"] for v in st["by_kind"].values())
    # no dispatch mixed geometries: each went to its own scheme's coder
    # with that scheme's row count
    assert len(watch.seen) == st["mesh_batches"]
    for scheme, _kind, shape in watch.seen:
        assert shape[1] == scheme.data_shards
    assert {s for s, _k, _sh in watch.seen} == set(coders)


def test_a_job_without_a_scheme_is_the_schedulers_own():
    sched = EcBatchScheduler(RS63)
    try:
        data = np.random.default_rng(1).integers(0, 256, (6, 512),
                                                 dtype=np.uint8)
        assert np.array_equal(sched.encode(data),
                              CpuCoder(RS63).encode_array(data))
        wide = np.zeros((10, 512), dtype=np.uint8)
        assert np.array_equal(sched.encode(wide, scheme=DEFAULT_SCHEME),
                              np.zeros((4, 512), dtype=np.uint8))
        st = sched.stats()
        assert st["cpu_batches"] == 0
        assert {k: v["jobs"] for k, v in st["by_spec"].items()} == {
            "rs-6-3": 1, "rs-10-4": 1}
    finally:
        sched.stop()


# ------------------------- (c) a 9-shard volume through the CLI servers

@pytest.fixture(scope="module")
def served():
    """CLI master + CLI ``volume -ecBatcher`` (behind the benchmark's
    wrapper, which adds nothing to a request), two filled volumes."""
    from benchmark import loadgen
    from benchmark.harness import Cluster
    cluster = Cluster()
    try:
        # one CPU device, like one chip
        cluster.start({"encode": [], "apply": []}, 64, 8,
                      {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
        corpus = loadgen.fill(cluster, {
            "volumes": 2, "fill": [{"bytes": 3000, "count": 40},
                                   {"bytes": 70000, "count": 25}]}, 63)
        yield cluster, corpus
    except BaseException:
        cluster.print_log_tails()
        raise
    finally:
        cluster.stop()
        cluster.cleanup()


def _sha(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _mounted(cluster, vid: int) -> set:
    st = cluster.http("GET", cluster.volume + "/status")
    bits = 0
    for e in st.get("ec_shards", []):
        if e["id"] == vid:
            bits |= e["ec_index_bits"]
    return {s for s in range(bits.bit_length()) if bits & (1 << s)}


def _read_all(cluster, corpus, vid: int) -> tuple[int, int, list]:
    """(right, wrong, errors) over every object of the volume, the
    needle cache cleared first."""
    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    cluster.http("POST", cluster.volume + "/admin/cache", {"clear": True})
    mc = MasterClient(cluster.master, cache_ttl=0.0)
    right = wrong = 0
    errors = []
    for fid, (digest, _size) in sorted(corpus.objects.items()):
        if not fid.startswith(f"{vid},"):
            continue
        try:
            data = operation.read_data(mc, fid)
        except Exception as e:  # noqa: BLE001 — counted
            errors.append(f"{fid}: {type(e).__name__}: {e}")
            continue
        if hashlib.sha256(data).hexdigest() == digest:
            right += 1
        else:
            wrong += 1
    return right, wrong, errors


def _lose(cluster, vid: int, sid: int, delete: bool = True) -> None:
    cluster.http("POST", cluster.volume + "/admin/ec/unmount",
                 {"volume_id": vid, "shard_ids": [sid]})
    if delete:
        os.remove(os.path.join(cluster.voldir, f"{vid}.ec{sid:02d}"))


def test_nine_shard_volume_beside_a_fourteen_shard_one(served):
    from benchmark import reference
    from seaweedfs_tpu.shell.commands import ShellContext
    from seaweedfs_tpu.utils.httpd import http_call
    cluster, corpus = served
    v63, v104 = corpus.vids
    n_objects = len(corpus.objects) // 2
    base = os.path.join(cluster.voldir, str(v63))
    for vid in corpus.vids:
        cluster.http("POST", cluster.volume + "/admin/mark_readonly",
                     {"volume_id": vid, "read_only": True})
    status, dat, _ = http_call(
        "GET", f"http://{cluster.volume}/admin/volume_file?volumeId={v63}"
        "&ext=.dat", timeout=60)
    assert status == 200
    dat_sha = hashlib.sha256(dat).hexdigest()
    dat_copy = os.path.join(cluster.workdir, "v63.dat")
    with open(dat_copy, "wb") as f:
        f.write(dat)

    sh = ShellContext(cluster.master, use_grpc=False)
    sh.lock()
    try:
        with pytest.raises(CodeSpecError):
            sh.ec_encode(vid=v63, code="pallas")
        out = sh.ec_encode(vid=v63, code="rs-6-3")
        assert out[0]["placement"] == {cluster.volume: list(range(9))}
        sh.ec_encode(vid=v104)

        # nine files and a .vif that says so; fourteen beside them
        assert [s for s in range(16)
                if os.path.exists(f"{base}.ec{s:02d}")] == list(range(9))
        with open(base + ".vif") as f:
            assert json.load(f)["code"] == {
                "family": "rs", "data_shards": 6, "parity_shards": 3}
        assert _mounted(cluster, v63) == set(range(9))
        assert _mounted(cluster, v104) == set(range(14))
        assert reference.differing_shard_files(
            dat_copy, [f"{base}.ec{s:02d}" for s in range(9)], 6, 3,
            1 << 30, 1 << 20) == []
        # the master knows the geometry (heartbeat -> /dir/status)
        geo = {e["id"]: (e.get("data_shards"), e.get("total_shards"))
               for dc in sh.topology()["data_centers"]
               for r in dc["racks"] for n in r["nodes"]
               for e in n["ec_shards"]}
        assert geo == {v63: (6, 9), v104: (10, 14)}
        assert sh.ec_rebuild(apply=False) == []    # nothing is missing
        stat = cluster.http("GET", cluster.volume
                            + f"/admin/ec/shard_stat?volumeId={v63}")
        assert stat["shards"] == list(range(9))
        assert stat["code"]["data_shards"] == 6

        # healthy reads of both volumes
        assert _read_all(cluster, corpus, v63) == (n_objects, 0, [])
        assert _read_all(cluster, corpus, v104) == (n_objects, 0, [])

        # one, two, three shards gone (a data shard each time first):
        # every object still reads back, rebuilt on the device queue
        shas = {s: _sha(f"{base}.ec{s:02d}") for s in (0, 4, 7)}
        for gone, sid in enumerate((0, 4, 7), start=1):
            _lose(cluster, v63, sid)
            assert _mounted(cluster, v63) == \
                set(range(9)) - set((0, 4, 7)[:gone])
            assert _read_all(cluster, corpus, v63) == (n_objects, 0, [])
        # the 14-shard volume beside it, one shard gone
        _lose(cluster, v104, 3)
        assert _read_all(cluster, corpus, v104) == (n_objects, 0, [])
        b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
        assert b["cpu_batches"] == 0 and b["coder_fallbacks"] == 0
        assert b["by_spec"]["rs-6-3"]["jobs"] > 0
        assert b["by_spec"]["rs-10-4"]["jobs"] > 0
        assert b["by_spec"]["rs-6-3"]["cpu_dispatches"] == 0
        jobs_before = b["by_spec"]["rs-6-3"]["jobs"]
        _st, text, _ = http_call("GET",
                                 f"http://{cluster.volume}/metrics")
        assert any("ec_batch_spec" in ln and 'spec="rs-6-3"' in ln
                   and 'stat="jobs"' in ln
                   for ln in text.decode().splitlines())

        # a fourth loss (unmounted, the file kept): five survivors
        # cannot give six; reads that need a lost shard are REFUSED,
        # none comes back wrong
        _lose(cluster, v63, 1, delete=False)
        right, wrong, errors = _read_all(cluster, corpus, v63)
        assert wrong == 0 and errors and right + len(errors) == n_objects
        plans = sh.ec_rebuild(apply=False)
        assert [p for p in plans if p["vid"] == v63][0]["error"] \
            .startswith("unrepairable")
        cluster.http("POST", cluster.volume + "/admin/ec/mount",
                     {"volume_id": v63, "shard_ids": [1]})

        # ec.rebuild brings back exactly the three (and the other
        # volume's one), byte for byte, on the device
        plans = {p["vid"]: p for p in sh.ec_rebuild()}
        assert plans[v63]["missing"] == [0, 4, 7]
        assert sorted(plans[v63]["rebuilt"]) == [0, 4, 7]
        assert plans[v104]["missing"] == [3]
        assert _mounted(cluster, v63) == set(range(9))
        assert _mounted(cluster, v104) == set(range(14))
        assert {s: _sha(f"{base}.ec{s:02d}") for s in shas} == shas
        assert not os.path.exists(f"{base}.ec09")
        b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
        assert b["cpu_batches"] == 0 and b["coder_fallbacks"] == 0
        assert b["by_spec"]["rs-6-3"]["jobs"] > jobs_before
        assert _read_all(cluster, corpus, v63) == (n_objects, 0, [])

        # ec.decode: back to a .dat equal to the original
        assert not os.path.exists(base + ".dat")
        assert sh.ec_decode(vid=v63)["dat_size"] == len(dat)
        assert _sha(base + ".dat") == dat_sha
        assert _mounted(cluster, v63) == set()
        assert _read_all(cluster, corpus, v63) == (n_objects, 0, [])
        assert _read_all(cluster, corpus, v104) == (n_objects, 0, [])
    finally:
        sh.unlock()


# ------------------- the master's side: topology, planners, repair scan

def _hb(name: str, port: int, ec: list) -> dict:
    return {"ip": name, "port": port, "max_volume_count": 8,
            "volumes": [], "ec_shards": ec}


def _nine(bits: int) -> dict:
    return {"id": 5, "ec_index_bits": bits, "data_shards": 6,
            "total_shards": 9}


def test_topology_keeps_a_volumes_own_shard_count():
    from seaweedfs_tpu.cluster.topology import Topology
    topo = Topology()
    topo.sync_data_node_registration(_hb("a", 1, [_nine(0b000011111)]))
    topo.sync_data_node_registration(
        _hb("b", 2, [_nine(0b111100000),
                     {"id": 6, "ec_index_bits": 0b11}]))
    assert len(topo.lookup_ec_shards(5)) == 9
    assert len(topo.lookup_ec_shards(6)) == 14      # none stated: RS(10,4)
    assert topo.ec_volume_geometry(5) == (6, 9)
    assert topo.ec_volume_geometry(6) == (10, 14)
    info = {e["id"]: e for dc in topo.to_info()["data_centers"]
            for r in dc["racks"] for n in r["nodes"] if n["id"] == "b:2"
            for e in n["ec_shards"]}
    assert (info[5]["data_shards"], info[5]["total_shards"]) == (6, 9)
    assert "total_shards" not in info[6]
    # deltas carry it too; the last holder leaving forgets it
    nb = topo.find_node("b:2")
    topo.incremental_sync(nb, {"deleted_ec_shards":
                               [{"id": 5, "ec_index_bits": 1 << 8}]})
    assert topo.lookup_ec_shards(5)[8] == []
    topo.incremental_sync(nb, {"new_ec_shards": [_nine(1 << 8)]})
    assert [n.id for n in topo.lookup_ec_shards(5)[8]] == ["b:2"]
    for node in (topo.find_node("a:1"), nb):
        topo.unregister_data_node(node)
    assert topo.lookup_ec_shards(5) is None
    assert topo.ec_volume_geometry(5) == (10, 14)


def test_repair_scan_leaves_a_whole_nine_shard_volume_alone():
    from seaweedfs_tpu.cluster.topology import Topology
    from seaweedfs_tpu.scrub.repair_queue import RepairQueue
    from seaweedfs_tpu.utils.metrics import Registry

    class _Master:
        metrics = Registry()
        topo = Topology()

    master = _Master()
    master.topo.sync_data_node_registration(
        _hb("a", 1, [_nine(0b111111111)]))
    q = RepairQueue(master, scan_grace_s=0.0)
    q._dispatch = lambda: None
    q._scan()
    assert q.status()["queue"] == []
    node = master.topo.find_node("a:1")
    master.topo.incremental_sync(node, {"deleted_ec_shards":
                                        [{"id": 5, "ec_index_bits": 0b101}]})
    q._scan()
    (task,) = q.status()["queue"]
    assert task["volume_id"] == 5 and task["priority"] == 2


def _dump(nodes: dict) -> dict:
    return {"data_centers": [{"id": "dc", "racks": [{"id": "r", "nodes": [
        {"id": nid, "max_volume_count": 8, "volumes": vols,
         "ec_shards": ec, "rack": "r", "data_center": "dc"}
        for nid, (vols, ec) in nodes.items()]}]}]}


def test_planners_place_rebuild_balance_and_decode_nine_shards():
    from seaweedfs_tpu.shell import ec_plan
    topo = _dump({"a:1": ([{"id": 5}], []), "b:2": ([], []),
                  "c:3": ([], [])})
    plan = ec_plan.plan_ec_encode(topo, 5, scheme=RS63)
    assert [mv.shard_id for mv in plan["moves"]] == list(range(9))
    assert {mv.target for mv in plan["moves"]} == {"a:1", "b:2", "c:3"}
    assert len(ec_plan.plan_ec_encode(topo, 5)["moves"]) == 14

    whole = _dump({"a:1": ([], [_nine(0b000000111)]),
                   "b:2": ([], [_nine(0b000111000)]),
                   "c:3": ([], [_nine(0b111000000)])})
    assert ec_plan.plan_ec_rebuild(whole) == []
    assert ec_plan.plan_ec_balance(whole) == []
    dec = ec_plan.plan_ec_decode(whole, 5)
    assert sorted(dec["all_owners"]) == list(range(9))
    assert len(dec["copies"]) == 6

    degraded = _dump({"a:1": ([], [_nine(0b000000110)]),
                      "b:2": ([], [_nine(0b000111000)]),
                      "c:3": ([], [_nine(0b011000000)])})
    (plan,) = ec_plan.plan_ec_rebuild(degraded)
    assert plan["missing"] == [0, 8]
    lost = _dump({"a:1": ([], [_nine(0b000000110)]),
                  "b:2": ([], [_nine(0b000111000)])})
    assert ec_plan.plan_ec_rebuild(lost)[0]["error"].startswith(
        "unrepairable: only 5")
    # an entry that states no geometry is RS(10,4), as before
    old = _dump({"a:1": ([], [{"id": 9, "ec_index_bits": (1 << 13) - 1}])})
    assert ec_plan.plan_ec_rebuild(old)[0]["missing"] == [13]
