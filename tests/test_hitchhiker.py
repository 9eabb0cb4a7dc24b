"""A piggybacked code on the device path: Hitchhiker-XOR over RS(10,4)
(Rashmi et al., SIGCOMM 2014; Hadoop 3's ``hhxor``), held to the
benchmark's plain reference (``benchmark/reference_hitchhiker.py``).

(a) the parser's ``hhxor-<k>-<m>``; (b) the shards the program writes
against the reference's, on small- and large-block rows, through the
host coder, the mesh coder and the scheduler's facade; (c) every loss
pattern of up to 4 of 14 shards, on the host coder (all 1,470) and on
the mesh coder (every pattern of up to two, a seeded sample of three
and four); (d) a whole-block repair reads the 13 / 14 half-blocks the
reference names, a read inside one half 10; (e) the paths that decode
whole shards at one offset refuse the family by name; (f) a volume
through the CLI servers: seal, reads with shard 3 gone and with 3 and
11 gone, ``ec.rebuild`` of a data shard and of a piggybacked parity.
GF(2^8) is exact: every comparison is byte equality.
"""

import itertools
import json
import os

import numpy as np
import pytest

from benchmark import reference_hitchhiker as ref
from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, CodeSpecError,
                                        HitchhikerScheme, SubstripedCodeError,
                                        code_spec_name, make_coder,
                                        parse_code_spec, scheme_from_dict,
                                        scheme_to_dict)
from seaweedfs_tpu.ops import hitchhiker as hh
from seaweedfs_tpu.ops.rs_mesh import MeshCoder
from seaweedfs_tpu.parallel.batcher import BatchCoder, EcBatchScheduler

HHXOR = HitchhikerScheme(10, 4)
K, TOTAL = 10, 14
# the tests' own unit: small enough for every loss pattern in seconds
SMALL = HitchhikerScheme(10, 4, unit=4096)


def _code(scheme: HitchhikerScheme, large_block: int = 1 << 30) -> dict:
    """A configuration's ``code`` block, as the reference reads it."""
    return {**scheme_to_dict(scheme), "small_block_bytes": scheme.unit,
            "large_block_bytes": large_block}


def _rows(seed: int, units: int, scheme=SMALL) -> np.ndarray:
    """All 14 rows of a seeded group of ``units`` units, by the reference."""
    data = np.random.default_rng(seed).integers(
        0, 256, (K, units * scheme.unit), dtype=np.uint8)
    return np.concatenate([data, ref.encode(data, _code(scheme))])


@pytest.fixture(scope="module")
def sched():
    s = EcBatchScheduler(mesh_coder=MeshCoder(DEFAULT_SCHEME, n_devices=1))
    yield s
    s.stop()


# ------------------------------------------------------ (a) the parser

@pytest.mark.parametrize("spec,want", [
    ("hhxor-10-4", HHXOR), ("HHXOR-10-4", HHXOR), ("hhxor", HHXOR),
    ("hhxor-6-3", HitchhikerScheme(6, 3))])
def test_code_spec_parses_the_family(spec, want):
    got = parse_code_spec(spec)
    assert got == want and got != DEFAULT_SCHEME
    assert parse_code_spec(code_spec_name(got)) == got
    assert scheme_from_dict(scheme_to_dict(got)) == got


@pytest.mark.parametrize("spec", ["hhxor-10-1", "hhxor-2-4", "hhxor-0-4",
                                  "hhxor-30-4"])
def test_code_spec_refuses_what_the_family_cannot_be(spec):
    with pytest.raises(CodeSpecError):
        parse_code_spec(spec)


def test_groups_and_the_persisted_spec():
    assert HHXOR.groups == ((0, 1, 2), (3, 4, 5), (6, 7, 8, 9))
    assert scheme_to_dict(HHXOR) == {
        "family": "hhxor", "data_shards": 10, "parity_shards": 4,
        "substripes": 2, "groups": [[0, 1, 2], [3, 4, 5], [6, 7, 8, 9]],
        "unit_bytes": 1 << 20}
    assert make_coder("cpu").for_scheme(HHXOR).scheme == HHXOR
    assert isinstance(make_coder("hhxor-mt"), hh.HitchhikerCoder)


# -------------------------------------- (b) the shards against the reference

def test_host_and_mesh_encode_equal_the_reference(sched):
    rows = _rows(seed=3901, units=3)
    host = make_coder("hhxor", SMALL)
    assert np.array_equal(host.encode_array(rows[:K]), rows[K:])
    mesh = MeshCoder(SMALL, n_devices=1)
    assert np.array_equal(mesh.encode_batch(rows[None, :K])[0], rows[K:])
    facade = BatchCoder(sched).for_scheme(SMALL)
    assert np.array_equal(facade.encode_array(rows[:K]), rows[K:])
    with pytest.raises(ValueError):
        host.encode_array(rows[:K, :100])        # not whole units


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
    rng = np.random.default_rng(3902)
    with open(base + ".dat", "wb") as f:
        f.write(rng.bytes(2 * K * large + 3 * K * unit + 777))
    ecenc.write_ec_files(base, make_coder("hhxor", SMALL), large_block=large,
                         small_block=unit, batch_size=2 * unit,
                         pipelined=pipelined)
    files = [f"{base}.ec{s:02d}" for s in range(TOTAL)]
    assert ref.differing_files(base + ".dat", files,
                               _code(SMALL, large)) == []


# --------------------------------------------- (c) every loss of up to 4

def _patterns():
    for n in range(1, 5):
        yield from itertools.combinations(range(TOTAL), n)


def test_every_loss_of_up_to_four_decodes_on_the_host_coder():
    rows = _rows(seed=3903, units=2)
    full = [r.tobytes() for r in rows]
    host = make_coder("hhxor", SMALL)
    n = 0
    for lost in _patterns():
        shards = [None if i in lost else full[i] for i in range(TOTAL)]
        assert host.reconstruct(shards) == full, lost
        got = ref.decode([None if i in lost else rows[i]
                          for i in range(TOTAL)], _code(SMALL))
        assert all(np.array_equal(g, r) for g, r in zip(got, rows)), lost
        n += 1
    assert n == 1470


def test_losses_decode_on_the_mesh_coder():
    """Every pattern of one and two lost shards (105) and a seeded
    sample of 40 of three and 40 of four, through the apply programs."""
    rows = _rows(seed=3904, units=1)
    full = [r.tobytes() for r in rows]
    mesh = MeshCoder(SMALL, n_devices=1)
    rng = np.random.default_rng(3905)
    pats = [p for p in _patterns() if len(p) <= 2]
    for n in (3, 4):
        every = list(itertools.combinations(range(TOTAL), n))
        pats += [every[i] for i in rng.choice(len(every), 40, replace=False)]
    for lost in pats:
        shards = [None if i in lost else full[i] for i in range(TOTAL)]
        assert mesh.reconstruct(shards) == full, lost


# -------------------------------------------- (d) what a repair reads

@pytest.mark.parametrize("sid", range(K))
def test_a_whole_block_repair_reads_the_half_blocks_the_reference_names(sid):
    others = frozenset(range(TOTAL)) - {sid}
    src, mat = hh.plan_halves(HHXOR, others, ((sid, 0), (sid, 1)))
    want = {(s, "ab"[h]) for s, h in src}
    assert want == set(ref.piggyback_reads(_code(HHXOR), sid))
    assert len(src) == (13 if sid < 6 else 14) and mat.shape == (2, len(src))
    # inside one half: plain RS on the substripe, ten ranges
    for sub in (0, 1):
        src1, _ = hh.plan_halves(HHXOR, others, ((sid, sub),))
        assert len(src1) == K and {h for _s, h in src1} == {sub}


def test_the_repair_matrix_rebuilds_the_block():
    rows = _rows(seed=3906, units=1)
    halves = hh.to_halves(rows, SMALL.unit)
    host = make_coder("hhxor", SMALL)
    for sid in range(K):
        src, mat = hh.plan_halves(SMALL, frozenset(range(TOTAL)) - {sid},
                                  ((sid, 0), (sid, 1)))
        got = host.reconstruct_rows(
            np.stack([halves[2 * s + h] for s, h in src]), mat)
        assert np.array_equal(got, halves[2 * sid:2 * sid + 2])


def _fill_store(store, vid: int, seed: int) -> dict:
    from seaweedfs_tpu.storage.needle import Needle
    rng = np.random.default_rng(seed)
    sizes = [4 << 20] * 3 + [int(rng.integers(100, 70000))
                             for _ in range(30)]
    rng.shuffle(sizes)
    payloads = {}
    store.add_volume(vid)
    for i, size in enumerate(sizes):
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        payloads[i + 1] = data
        n = Needle(id=i + 1, cookie=0xBEEF + i, data=data,
                   name=f"n{i}".encode())
        n.set_flags_from_fields()
        store.write_volume_needle(vid, n)
    return payloads


def test_store_reads_a_lost_block_from_thirteen_half_blocks(tmp_path):
    """Shard 3 gone on a store of the host coder: each read rebuilds its
    lost intervals, a whole block from 13 half-blocks (6.5 MiB, not 10)
    by the piggyback plan, a piece inside one half from 10 half-ranges;
    a scrub of the healthy volume finds nothing; a rebuild of shards 3
    and 12 writes the reference's bytes."""
    from seaweedfs_tpu.scrub import Scrubber
    from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
    from seaweedfs_tpu.storage.erasure_coding import layout
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path / "d")], coder=make_coder("cpu"))
    try:
        pay = _fill_store(store, 1, seed=3907)
        base = store.generate_ec_shards(1, code="hhxor-10-4")
        dat = str(tmp_path / "copy.dat")
        with open(base + ".dat", "rb") as f, open(dat, "wb") as g:
            g.write(f.read())
        store.delete_volume(1)
        store.mount_ec_shards("", 1, list(range(TOTAL)))
        ev = store.find_ec_volume(1)
        assert ev.scheme == HHXOR
        assert store.collect_heartbeat()["ec_shards"][0]["code"] \
            == "hhxor-10-4"
        files = [base + layout.shard_ext(s) for s in range(TOTAL)]
        assert ref.differing_files(dat, files, _code(HHXOR)) == []
        out = Scrubber(store, rate_bytes_per_sec=0).run_once()
        assert out["corruptions"] == [], out

        store.unmount_ec_shards(1, [3])
        os.remove(files[3])
        rs, rec = store.ec_read_stats, store.ec_recover_stats
        whole = part = 0
        for nid, data in pay.items():
            intervals, _off, _size = ev.locate_needle(nid)
            lost = [iv for iv in intervals
                    if iv.to_shard_id_and_offset()[0] == 3]
            n_whole = sum(1 for iv in lost if iv.size == 1 << 20)
            before = (rs["survivor_reads"], rs["survivor_bytes"],
                      rec["piggyback"], rec["substripe"])
            assert store.read_ec_shard_needle(1, nid).data == data
            reads, nbytes, pig, sub = (
                rs["survivor_reads"] - before[0],
                rs["survivor_bytes"] - before[1],
                rec["piggyback"] - before[2], rec["substripe"] - before[3])
            if lost and n_whole == len(lost):
                assert (reads, nbytes, pig, sub) == (
                    13 * n_whole, 13 * (512 << 10) * n_whole, n_whole, 0)
                whole += n_whole
            elif lost and all(
                    (iv.inner_block_offset < 512 << 10)
                    == (iv.inner_block_offset + iv.size <= 512 << 10)
                    for iv in lost):
                assert (reads, pig, sub) == (10 * len(lost), 0, len(lost))
                part += len(lost)
        assert whole >= 1 and part >= 1
        assert rec["generic"] == 0

        # parity 12 (group 2's piggyback) on a peer only: the piggyback
        # plan fetches its b-halves; with no peer, shards 3 and 12 lost,
        # the wider half-row decode
        with open(files[12], "rb") as f:
            held = f.read()
        store.unmount_ec_shards(1, [12])
        os.remove(files[12])
        fetched = []

        def peer(vid, sid, off, size):
            fetched.append((sid, off % (1 << 20), size))
            return held[off:off + size] if sid == 12 else None
        store.remote_shard_reader = peer
        before = rec["piggyback"]
        for nid, data in pay.items():
            assert store.read_ec_shard_needle(1, nid).data == data
        assert rec["piggyback"] - before == whole
        assert {(sid, off) for sid, off, _n in fetched
                if _n == 512 << 10} == {(12, 512 << 10)}
        store.remote_shard_reader = None
        for nid, data in pay.items():
            assert store.read_ec_shard_needle(1, nid).data == data
        stats: dict = {}
        assert ecenc.rebuild_ec_files(base, store.coder_for(ev),
                                      stats=stats) == [3, 12]
        assert ref.differing_files(dat, files, _code(HHXOR)) == []
    finally:
        store.close()


# ---------------------------------- (e) paths that refuse it by name

def test_whole_shard_plans_refuse_the_family_by_name():
    from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
    host = make_coder("hhxor")
    with pytest.raises(SubstripedCodeError, match="hhxor-10-4"):
        ecenc.plan_rebuild_sources(host, [s for s in range(TOTAL)
                                          if s != 12], [12])


def test_repair_queue_planner_refuses_the_family_by_name():
    from seaweedfs_tpu.scrub.repair_queue import RepairQueue
    rq = RepairQueue.__new__(RepairQueue)
    rq._shard_stat = lambda *_a: {"code": scheme_to_dict(HHXOR)}
    with pytest.raises(SubstripedCodeError, match="hhxor-10-4"):
        rq._plan_sources(7, "", set(range(TOTAL)) - {11, 13}, [11, 13],
                         "127.0.0.1:1")


def test_topology_keeps_the_code_a_holder_states():
    """The counts of a Hitchhiker-XOR volume are RS(10,4)'s: the master
    knows the family only from the code its holder states (a gRPC
    heartbeat states neither: ROADMAP M2)."""
    from seaweedfs_tpu.cluster.topology import Topology
    topo = Topology()
    topo.sync_data_node_registration({
        "ip": "127.0.0.1", "port": 18080, "max_volume_count": 8,
        "volumes": [], "ec_shards": [
            {"id": 7, "collection": "", "ec_index_bits": (1 << 14) - 1
             - (1 << 13), "data_shards": 10, "total_shards": 14,
             "code": "hhxor-10-4"},
            {"id": 8, "collection": "", "ec_index_bits": (1 << 14) - 1}]})
    assert topo.ec_volume_geometry(7) == topo.ec_volume_geometry(8) \
        == (10, 14)
    assert topo.ec_volume_code(7) == "hhxor-10-4"
    assert topo.ec_volume_code(8) is None


# ---------------------------------------- (f) through the CLI servers

@pytest.fixture(scope="module")
def served():
    """CLI master + CLI ``volume -ecBatcher`` on the CPU backend, one
    filled volume of ~22 MB: chunk needles of 4 and 2 MiB and small
    ones, so that some lost intervals are whole blocks and some lie
    inside one half."""
    from benchmark import loadgen
    from benchmark.harness import Cluster
    cluster = Cluster()
    try:
        cluster.start({"encode": [], "apply": []}, 64, 8,
                      {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
        corpus = loadgen.fill(cluster, {
            "volumes": 1, "fill": [{"bytes": 4 << 20, "count": 3},
                                   {"bytes": 2 << 20, "count": 3},
                                   {"bytes": 3000, "count": 40},
                                   {"bytes": 70000, "count": 40}]}, 3908)
        yield cluster, corpus
    except BaseException:
        cluster.print_log_tails()
        raise
    finally:
        cluster.stop()
        cluster.cleanup()


def _stat(cluster, vid: int) -> dict:
    return cluster.http("GET", cluster.volume
                        + f"/admin/ec/shard_stat?volumeId={vid}")


def test_a_piggybacked_volume_through_the_servers(served):
    from seaweedfs_tpu.shell.commands import ShellContext
    from seaweedfs_tpu.utils.httpd import http_call
    from tests.test_code_geometry import _lose, _mounted, _read_all
    cluster, corpus = served
    (vid,) = corpus.vids
    n_objects = len(corpus.objects)
    base = os.path.join(cluster.voldir, str(vid))
    cluster.http("POST", cluster.volume + "/admin/mark_readonly",
                 {"volume_id": vid, "read_only": True})
    status, dat, _ = http_call(
        "GET", f"http://{cluster.volume}/admin/volume_file?volumeId={vid}"
        "&ext=.dat", timeout=60)
    assert status == 200 and len(dat) > 20 << 20
    dat_copy = os.path.join(cluster.workdir, "vhh.dat")
    with open(dat_copy, "wb") as f:
        f.write(dat)
    files = [f"{base}.ec{s:02d}" for s in range(TOTAL)]

    sh = ShellContext(cluster.master, use_grpc=False)
    sh.lock()
    try:
        sh.ec_encode(vid=vid, code="hhxor-10-4")
        with open(base + ".vif") as f:
            assert json.load(f)["code"]["family"] == "hhxor"
        assert ref.differing_files(dat_copy, files, _code(HHXOR)) == []
        assert _mounted(cluster, vid) == set(range(TOTAL))
        b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
        sealed = b["by_spec"]["hhxor-10-4"]
        assert sealed["jobs"] >= 1 and sealed["cpu_dispatches"] == 0
        assert set(sealed["rows"]) == {"10"}

        # shard 3 gone: whole blocks by the piggyback plan (13 half-rows
        # a job), pieces inside one half by the substripe plan (10)
        s0 = _stat(cluster, vid)
        _lose(cluster, vid, 3)
        assert _read_all(cluster, corpus, vid) == (n_objects, 0, [])
        s1 = _stat(cluster, vid)
        rec = {k: s1["recover_stats"][k] - s0["recover_stats"][k]
               for k in s1["recover_stats"]}
        assert rec["piggyback"] >= 1 and rec["substripe"] >= 1
        assert rec["generic"] == rec["local"] == rec["global"] == 0
        b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
        mine = b["by_spec"]["hhxor-10-4"]
        assert mine["cpu_dispatches"] == 0 and b["cpu_batches"] == 0
        assert b["coder_fallbacks"] == 0
        assert {"10", "13"} <= set(mine["rows"])
        assert "524288" in b["by_rung"]          # the half-block rung

        # 3 and 11 gone (11 carries group 1's piggyback, not 3's)
        _lose(cluster, vid, 11)
        assert _read_all(cluster, corpus, vid) == (n_objects, 0, [])
        # the partial-column chain refuses the family by name
        status, body, _ = http_call(
            "POST", f"http://{cluster.volume}/admin/ec/rebuild_partial",
            json.dumps({"volume_id": vid, "missing": [3, 11],
                        "sources": {}}).encode(), timeout=30)
        assert status == 409 and b"hhxor-10-4" in body

        # ec.rebuild of a data shard and of two piggybacked parities
        _lose(cluster, vid, 12)
        plans = sh.ec_rebuild()
        assert [sorted(p.get("rebuilt", [])) for p in plans] == [[3, 11, 12]]
        assert ref.differing_files(dat_copy, files, _code(HHXOR)) == []
        assert _mounted(cluster, vid) == set(range(TOTAL))
        assert _read_all(cluster, corpus, vid) == (n_objects, 0, [])
    finally:
        sh.unlock()


def test_bytes_of_a_piggyback_repair():
    """benchmark/bytes_model_hitchhiker.py: 13 half-blocks in and 2 out
    for a shard of the first two groups, 14 in for the third."""
    from benchmark import bytes_model_hitchhiker as bm
    code = _code(HHXOR)
    assert bm.piggyback_repair_bytes(code, 3) == 7_864_320 \
        == (13 + 2) * (512 << 10)
    assert bm.piggyback_repair_bytes(code, 7) == (14 + 2) * (512 << 10)
    with pytest.raises(ValueError):
        bm.piggyback_repair_bytes(code, 11)      # a parity: no such plan
