"""A regenerating code on the device path: Clay(10,4,13) (Vajha et al.,
FAST 2018; Ceph's ``clay`` plugin), held to the benchmark's plain
reference (``benchmark/reference_clay.py``), beside Ceph's documented
k=4 m=2 d=5 (alpha 8) for a second geometry.

(a) the parser's ``clay[-<k>-<m>[-<d>]]`` and the ``.vif``; (b) the
parities the program writes against the reference's, through the host
coder, the mesh coder's structured encode and the scheduler's facade,
and a volume's files on small- and large-block rows; (c) the layered
decode of every pattern of up to two lost under (4,2,5), all 14 single
losses and a seeded sample of the four-loss patterns under (10,4,13);
(d) the repair of every shard reads exactly d beta sub-chunks, the
runs the reference names, and the host's and the device program's
repairs equal the reference's; (e) a sealed volume through ``Store``:
whole-block and sub-block reads with shard 3 gone, a layered decode
with two gone, ``ec.rebuild``; (f) the paths that decode whole shards
at one offset refuse the family by name; (g) a volume through the CLI
servers.  Small sizes: 16-byte sub-chunks.  GF(2^8) is exact: every
comparison is byte equality.
"""

import itertools
import json
import os

import numpy as np
import pytest

from benchmark import reference_clay as ref
from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, ClayScheme,
                                        CodeSpecError, SubstripedCodeError,
                                        code_spec_name, make_coder,
                                        parse_code_spec, scheme_from_dict,
                                        scheme_to_dict)
from seaweedfs_tpu.ops import clay
from seaweedfs_tpu.ops.rs_mesh import MeshCoder
from seaweedfs_tpu.parallel.batcher import BatchCoder, EcBatchScheduler

CLAY = ClayScheme(10, 4, 13)
# the tests' own units: 16-byte sub-chunks
SMALL = ClayScheme(10, 4, 13, unit=4096)
CEPH = ClayScheme(4, 2, 5, unit=128)


def _code(scheme: ClayScheme, large_block: int = 1 << 30) -> dict:
    """A configuration's ``code`` block, as the reference reads it."""
    return {"family": "clay", "data_shards": scheme.data_shards,
            "parity_shards": scheme.parity_shards,
            "helpers": scheme.helpers, "gamma": scheme.gamma,
            "small_block_bytes": scheme.unit,
            "large_block_bytes": large_block}


def _rows(seed: int, units: int, scheme=SMALL) -> np.ndarray:
    """Every shard's row of a seeded group of ``units`` units, by the
    reference."""
    data = np.random.default_rng(seed).integers(
        0, 256, (scheme.data_shards, units * scheme.unit), dtype=np.uint8)
    return np.concatenate([data, ref.encode(data, _code(scheme))])


@pytest.fixture(scope="module")
def sched():
    s = EcBatchScheduler(mesh_coder=MeshCoder(DEFAULT_SCHEME, n_devices=1))
    yield s
    s.stop()


# ------------------------------------------------- (a) parser and .vif

@pytest.mark.parametrize("spec,want", [
    ("clay-10-4-13", CLAY), ("CLAY-10-4-13", CLAY), ("clay-10-4", CLAY),
    ("clay", CLAY), ("clay-4-2-5", ClayScheme(4, 2, 5)),
    ("clay-4-2", ClayScheme(4, 2))])
def test_code_spec_parses_the_family(spec, want):
    got = parse_code_spec(spec)
    assert got == want and got != DEFAULT_SCHEME
    assert parse_code_spec(code_spec_name(got)) == got
    assert scheme_from_dict(scheme_to_dict(got)) == got


@pytest.mark.parametrize("spec", ["clay-10-4-12", "clay-10-1", "clay-0-4",
                                  "clay-30-4", "clay-10-4-14", "clay-1-2-3-4"])
def test_code_spec_refuses_what_the_family_cannot_be(spec):
    with pytest.raises(CodeSpecError):
        parse_code_spec(spec)


def test_geometry_and_the_persisted_spec():
    assert (CLAY.q, CLAY.t, CLAY.nu, CLAY.alpha, CLAY.beta) \
        == (4, 4, 2, 256, 64)
    assert (CLAY.sub_bytes, CLAY.repair_columns, CLAY.max_sources) \
        == (4096, 256 << 10, 13)
    assert [CLAY.node_of(s) for s in (0, 9, 10, 13)] == [0, 9, 12, 15]
    assert (CEPH.q, CEPH.t, CEPH.nu, CEPH.alpha) == (2, 3, 0, 8)
    assert scheme_to_dict(CLAY) == {
        "family": "clay", "data_shards": 10, "parity_shards": 4, "d": 13,
        "substripes": 256, "gamma": 2, "unit_bytes": 1 << 20}
    assert code_spec_name(CLAY) == "clay-10-4-13"
    assert make_coder("cpu").for_scheme(CLAY).scheme == CLAY
    assert isinstance(make_coder("clay-mt"), clay.ClayCoder)


# --------------------------------------- (b) the parities and the files

@pytest.mark.parametrize("scheme", [SMALL, CEPH], ids=["10-4-13", "4-2-5"])
def test_host_mesh_and_facade_encode_equal_the_reference(sched, scheme):
    rows = _rows(seed=4201, units=3, scheme=scheme)
    k = scheme.data_shards
    host = make_coder("clay", scheme)
    assert np.array_equal(host.encode_array(rows[:k]), rows[k:])
    mesh = MeshCoder(scheme, n_devices=1)
    assert np.array_equal(mesh.encode_batch(rows[None, :k])[0], rows[k:])
    facade = BatchCoder(sched).for_scheme(scheme)
    assert np.array_equal(facade.encode_array(rows[:k]), rows[k:])
    with pytest.raises(ValueError):
        host.encode_array(rows[:k, :scheme.unit // 2])   # not whole units


@pytest.mark.parametrize("pipelined", [False, True])
def test_volume_files_equal_the_reference_on_large_and_small_rows(
        tmp_path, pipelined):
    """A .dat of two large-block rows (4 units a block) and small-block
    rows after them, sealed by the store's encoders (the pipelined one
    in batches of two units): the 14 files are the reference's."""
    from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
    unit = SMALL.unit
    large = 4 * unit
    base = str(tmp_path / "v")
    rng = np.random.default_rng(4202)
    with open(base + ".dat", "wb") as f:
        f.write(rng.bytes(2 * 10 * large + 3 * 10 * unit + 777))
    ecenc.write_ec_files(base, make_coder("clay", SMALL), large_block=large,
                         small_block=unit, batch_size=2 * unit,
                         pipelined=pipelined)
    files = [f"{base}.ec{s:02d}" for s in range(14)]
    assert ref.differing_files(base + ".dat", files,
                               _code(SMALL, large)) == []


# --------------------------------------------- (c) the layered decode

def test_every_loss_of_up_to_two_decodes_under_the_ceph_geometry():
    rows = _rows(seed=4203, units=2, scheme=CEPH)
    full = [r.tobytes() for r in rows]
    host = make_coder("clay", CEPH)
    n = 0
    for lost in itertools.chain(*(itertools.combinations(range(6), r)
                                  for r in (1, 2))):
        shards = [None if i in lost else full[i] for i in range(6)]
        assert host.reconstruct(shards) == full, lost
        got = clay.decode_units(CEPH, {i: rows[i] for i in range(6)
                                       if i not in lost}, list(lost))
        assert all(np.array_equal(got[i], rows[i]) for i in lost), lost
        n += 1
    assert n == 21
    with pytest.raises(ValueError):
        host.reconstruct([None] * 3 + full[3:])          # three of two


def test_losses_decode_under_clay_10_4_13():
    """All 14 single losses, and a seeded sample of 40 of the 1,001
    four-loss patterns, by the layered decode on the host and through
    the mesh coder's bytes API."""
    rows = _rows(seed=4204, units=2)
    full = [r.tobytes() for r in rows]
    host = make_coder("clay", SMALL)
    mesh = MeshCoder(SMALL, n_devices=1)
    fours = list(itertools.combinations(range(14), 4))
    assert len(fours) == 1001
    rng = np.random.default_rng(4205)
    pats = [(s,) for s in range(14)] + [
        fours[i] for i in rng.choice(len(fours), 40, replace=False)]
    for lost in pats:
        shards = [None if i in lost else full[i] for i in range(14)]
        assert host.reconstruct(shards) == full, lost
    for lost in pats[::6]:
        shards = [None if i in lost else full[i] for i in range(14)]
        assert mesh.reconstruct(shards) == full, lost


# ------------------------------------------------- (d) the repair

@pytest.mark.parametrize("scheme", [SMALL, CEPH], ids=["10-4-13", "4-2-5"])
def test_every_repair_reads_d_beta_sub_chunks_and_gives_the_block(scheme):
    rows = _rows(seed=4206, units=1, scheme=scheme)
    code = _code(scheme)
    host = make_coder("clay", scheme)
    sub = scheme.sub_bytes
    for sid in range(scheme.total_shards):
        plan = clay.repair_plan(scheme, sid)
        assert sorted(plan.helpers) == sorted(
            set(range(scheme.total_shards)) - {sid})
        planes = ref.repair_planes(code, sid)
        # the runs the plan reads are the reference's planes, d beta of
        # them in all
        offs = [plan.first + j * plan.step + b
                for j in range(plan.runs) for b in range(0, plan.run, sub)]
        assert offs == [z * sub for z in planes]
        assert plan.runs * plan.run * len(plan.helpers) \
            == len(ref.repair_reads(code, sid)) * sub \
            == scheme.helpers * scheme.beta * sub
        job = np.stack([clay.runs_of(scheme, plan, rows[h])
                        for h in plan.helpers])
        want = ref.repair(code, sid, {
            h: rows[h].reshape(scheme.alpha, sub)[planes]
            for h in plan.helpers})
        assert np.array_equal(want, rows[sid])
        rec = host.reconstruct_rows(job, clay.repair_mat(scheme, sid))
        assert np.array_equal(clay.units_of(scheme, rec), rows[sid]), sid


@pytest.mark.parametrize("scheme", [SMALL, CEPH], ids=["10-4-13", "4-2-5"])
def test_the_device_repair_program_equals_the_reference(scheme):
    """One dispatch of the structured repair program: a lane per lost
    shard, every shard of the code, two units a lane."""
    rows = _rows(seed=4207, units=2, scheme=scheme)
    code = _code(scheme)
    sub = scheme.sub_bytes
    mesh = MeshCoder(scheme, n_devices=1)
    jobs, mats = [], []
    for sid in range(scheme.total_shards):
        plan = clay.repair_plan(scheme, sid)
        jobs.append(np.stack([clay.runs_of(scheme, plan, rows[h])
                              for h in plan.helpers]))
        mats.append(clay.repair_mat(scheme, sid))
    recs = mesh.rebuild_batch(np.stack(jobs), mats)
    assert mesh.programs == {("apply", 16 if scheme is SMALL else 8,
                              scheme.helpers, jobs[0].shape[1] // 4)}
    for sid, rec in enumerate(recs):
        got = clay.units_of(scheme, rec)
        planes = ref.repair_planes(code, sid)
        for u in range(2):
            unit = slice(u * scheme.unit, (u + 1) * scheme.unit)
            want = ref.repair(code, sid, {
                h: rows[h][unit].reshape(scheme.alpha, sub)[planes]
                for h in range(scheme.total_shards) if h != sid})
            assert np.array_equal(got[unit], want), (sid, u)


def test_bytes_of_a_repair():
    """benchmark/bytes_model_clay.py: 13 x 256 KiB in, 1 MiB out, for
    every shard."""
    from benchmark import bytes_model_clay as bm
    code = _code(CLAY)
    assert {bm.repair_bytes(code, s) for s in range(14)} \
        == {4_456_448} == {13 * (256 << 10) + (1 << 20)}
    with pytest.raises(ValueError):
        bm.repair_bytes(code, 14)


# ------------------------------------------ (e) a volume through Store

def _fill_store(store, vid: int, seed: int) -> dict:
    from seaweedfs_tpu.storage.needle import Needle
    rng = np.random.default_rng(seed)
    sizes = [4 << 20] * 4 + [int(rng.integers(100, 70000))
                             for _ in range(40)]
    rng.shuffle(sizes)
    payloads = {}
    store.add_volume(vid)
    for i, size in enumerate(sizes):
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        payloads[i + 1] = data
        n = Needle(id=i + 1, cookie=0xC1A + i, data=data,
                   name=f"n{i}".encode())
        n.set_flags_from_fields()
        store.write_volume_needle(vid, n)
    return payloads


def test_store_reads_a_lost_block_from_thirteen_runs(tmp_path, sched):
    """Shard 3 gone on a store of the scheduler's facade: each read
    rebuilds its lost intervals on the device from the 13 helpers' runs
    of a quarter block (3.25 MiB a whole block, ``repair_planes``; a
    piece of a block reads the same, ``planes_partial``); shard 9's
    strided runs too; with shards 3 and 11 gone the layered decode on
    the host; ``ec.rebuild`` writes the reference's bytes."""
    from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
    from seaweedfs_tpu.storage.erasure_coding import layout
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path / "d")], coder=BatchCoder(sched))
    try:
        pay = _fill_store(store, 1, seed=4208)
        base = store.generate_ec_shards(1, code="clay-10-4-13")
        with open(base + ".vif") as f:
            assert json.load(f)["code"] == scheme_to_dict(CLAY)
        dat = str(tmp_path / "copy.dat")
        with open(base + ".dat", "rb") as f, open(dat, "wb") as g:
            g.write(f.read())
        store.delete_volume(1)
        store.mount_ec_shards("", 1, list(range(14)))
        ev = store.find_ec_volume(1)
        assert ev.scheme == CLAY
        assert store.collect_heartbeat()["ec_shards"][0]["code"] \
            == "clay-10-4-13"
        files = [base + layout.shard_ext(s) for s in range(14)]
        assert ref.differing_files(dat, files, _code(CLAY)) == []

        rs, rec = store.ec_read_stats, store.ec_recover_stats
        whole = part = 0
        for lost_sid in (3, 9):
            store.unmount_ec_shards(1, [lost_sid])
            os.rename(files[lost_sid], files[lost_sid] + ".away")
            for nid, data in pay.items():
                intervals, _off, _size = ev.locate_needle(nid)
                lost = [iv for iv in intervals
                        if iv.to_shard_id_and_offset()[0] == lost_sid]
                n_whole = sum(1 for iv in lost if iv.size == 1 << 20)
                before = (rs["survivor_reads"], rs["survivor_bytes"],
                          rec["repair_planes"], rec["planes_partial"])
                assert store.read_ec_shard_needle(1, nid).data == data
                reads, nbytes, whole_n, part_n = (
                    rs["survivor_reads"] - before[0],
                    rs["survivor_bytes"] - before[1],
                    rec["repair_planes"] - before[2],
                    rec["planes_partial"] - before[3])
                assert (reads, nbytes) == (13 * len(lost),
                                           13 * (256 << 10) * len(lost))
                assert (whole_n, part_n) == (n_whole, len(lost) - n_whole)
                whole += whole_n
                part += part_n
            os.rename(files[lost_sid] + ".away", files[lost_sid])
            store.mount_ec_shards("", 1, [lost_sid])
        assert whole >= 1 and part >= 1
        assert rec["generic"] == rec["layered"] == 0
        b = sched.stats()
        mine = b["by_spec"]["clay-10-4-13"]
        assert mine["cpu_dispatches"] == 0 and b["coder_fallbacks"] == 0
        assert mine["rows"]["13"] == whole + part
        assert str(256 << 10) in b["by_rung"]

        # shards 3 and 11 gone: the layered decode of whole units
        store.unmount_ec_shards(1, [3, 11])
        os.remove(files[3])
        os.remove(files[11])
        for nid, data in pay.items():
            assert store.read_ec_shard_needle(1, nid).data == data
        assert rec["layered"] >= 1
        assert ecenc.rebuild_ec_files(base, store.coder_for(ev)) == [3, 11]
        assert ref.differing_files(dat, files, _code(CLAY)) == []
    finally:
        store.close()


def test_a_peer_serves_a_helpers_runs(tmp_path):
    """A helper on a peer only: its runs are fetched from the peer (one
    a block for a lost shard of column 0), the repair stays the repair
    plan; with no peer it is the layered decode."""
    from seaweedfs_tpu.storage.erasure_coding import layout
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path / "d")], coder=make_coder("cpu"))
    try:
        pay = _fill_store(store, 1, seed=4209)
        base = store.generate_ec_shards(1, code="clay-10-4-13")
        store.delete_volume(1)
        store.mount_ec_shards("", 1, list(range(14)))
        files = [base + layout.shard_ext(s) for s in range(14)]
        with open(files[12], "rb") as f:
            held = f.read()
        store.unmount_ec_shards(1, [3, 12])
        os.remove(files[3])
        fetched = []

        def peer(vid, sid, off, size):
            fetched.append((sid, off % (1 << 20), size))
            return held[off:off + size] if sid == 12 else None
        store.remote_shard_reader = peer
        rec = store.ec_recover_stats
        for nid, data in pay.items():
            assert store.read_ec_shard_needle(1, nid).data == data
        assert rec["repair_planes"] + rec["planes_partial"] >= 1
        assert rec["layered"] == 0
        # shard 12 = node (0, 3) sends shard 3's planes z_0 = 3: one run
        assert {(sid, off, n) for sid, off, n in fetched if sid == 12} \
            == {(12, 3 << 18, 1 << 18)}
        store.remote_shard_reader = None
        for nid, data in pay.items():
            assert store.read_ec_shard_needle(1, nid).data == data
        assert rec["layered"] >= 1
    finally:
        store.close()


# -------------------------------------- (f) paths that refuse it by name

def test_whole_shard_plans_refuse_the_family_by_name():
    from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
    with pytest.raises(SubstripedCodeError, match="clay-10-4-13"):
        ecenc.plan_rebuild_sources(make_coder("clay"),
                                   [s for s in range(14) if s != 12], [12])


def test_repair_queue_planner_refuses_the_family_by_name():
    from seaweedfs_tpu.scrub.repair_queue import RepairQueue
    rq = RepairQueue.__new__(RepairQueue)
    rq._shard_stat = lambda *_a: {"code": scheme_to_dict(CLAY)}
    with pytest.raises(SubstripedCodeError, match="clay-10-4-13"):
        rq._plan_sources(7, "", set(range(14)) - {11, 13}, [11, 13],
                         "127.0.0.1:1")


# ---------------------------------------- (g) through the CLI servers

@pytest.fixture(scope="module")
def served():
    """CLI master + CLI ``volume -ecBatcher`` on the CPU backend, one
    filled volume of ~15 MB: chunk needles of 4 MiB and small ones."""
    from benchmark import loadgen
    from benchmark.harness import Cluster
    cluster = Cluster()
    try:
        cluster.start({"encode": [], "apply": []}, 64, 8,
                      {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
        corpus = loadgen.fill(cluster, {
            "volumes": 1, "fill": [{"bytes": 4 << 20, "count": 3},
                                   {"bytes": 3000, "count": 30},
                                   {"bytes": 70000, "count": 30}]}, 4210)
        yield cluster, corpus
    except BaseException:
        cluster.print_log_tails()
        raise
    finally:
        cluster.stop()
        cluster.cleanup()


def test_a_clay_volume_through_the_servers(served):
    from seaweedfs_tpu.shell.commands import ShellContext
    from seaweedfs_tpu.utils.httpd import http_call
    from tests.test_code_geometry import _lose, _mounted, _read_all
    cluster, corpus = served
    (vid,) = corpus.vids
    n_objects = len(corpus.objects)
    base = os.path.join(cluster.voldir, str(vid))
    sh = ShellContext(cluster.master, use_grpc=False)
    sh.lock()
    try:
        # ec.encode: /admin/ec/generate with {"code": "clay-10-4-13"}
        sh.ec_encode(vid=vid, code="clay-10-4-13")
        assert _mounted(cluster, vid) == set(range(14))
        with open(base + ".vif") as f:
            assert json.load(f)["code"]["family"] == "clay"
        b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
        assert set(b["by_spec"]["clay-10-4-13"]["rows"]) == {"10"}

        # shard 3 gone: reads rebuilt on the device, 13 rows a job; a
        # sampled read leaves the plan's and the gather's stages
        _lose(cluster, vid, 3)
        cluster.http("POST", cluster.volume + "/admin/cache",
                     {"clear": True})
        tid = "42c1a42c1a42c1a4"
        for fid in sorted(corpus.objects):
            assert http_call(
                "GET", f"http://{cluster.volume}/{fid}",
                headers={"X-Weed-Trace": f"{tid}:0000beef:1"})[0] == 200
        assert _read_all(cluster, corpus, vid) == (n_objects, 0, [])
        stat = cluster.http("GET", cluster.volume
                            + f"/admin/ec/shard_stat?volumeId={vid}")
        rec = stat["recover_stats"]
        assert rec["repair_planes"] + rec["planes_partial"] >= 1
        assert rec["layered"] == rec["generic"] == 0
        b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
        mine = b["by_spec"]["clay-10-4-13"]
        assert mine["cpu_dispatches"] == 0 and b["cpu_batches"] == 0
        assert b["coder_fallbacks"] == 0 and "13" in mine["rows"]
        spans = cluster.http(
            "GET", cluster.volume
            + f"/debug/traces?trace={tid}&limit=4096")["spans"]
        plans = [s["annotations"] for s in spans
                 if s["name"] == "store.ec.plan"]
        assert plans and all(
            p["strategy"] in ("repair_planes", "planes_partial")
            and len(p["sources"]) == 13 for p in plans)
        gathers = [s["annotations"] for s in spans
                   if s["name"] == "store.ec.survivors"]
        assert gathers and all(g["helpers"] == 13 and g["runs"] == 13
                               and g["bytes"] == 13 * (256 << 10)
                               for g in gathers)
        for name in ("ec.batch.submit", "ec.mesh.launch"):
            mine = [s["annotations"] for s in spans if s["name"] == name
                    and s["annotations"].get("spec") == "clay-10-4-13"]
            assert mine and all(a["rows"] == 13 for a in mine), name
        _st, text, _ = http_call("GET", f"http://{cluster.volume}/metrics")
        assert any("ec_recover_intervals" in ln
                   and 'strategy="repair_planes"' in ln
                   for ln in text.decode().splitlines())

        # the partial-column chain refuses the family by name
        status, body, _ = http_call(
            "POST", f"http://{cluster.volume}/admin/ec/rebuild_partial",
            json.dumps({"volume_id": vid, "missing": [3],
                        "sources": {}}).encode(), timeout=30)
        assert status == 409 and b"clay-10-4-13" in body

        # ec.rebuild of a data shard and a parity, by the layered decode
        _lose(cluster, vid, 12)
        plans = sh.ec_rebuild()
        assert [sorted(p.get("rebuilt", [])) for p in plans] == [[3, 12]]
        assert _mounted(cluster, vid) == set(range(14))
        assert _read_all(cluster, corpus, vid) == (n_objects, 0, [])
    finally:
        sh.unlock()
