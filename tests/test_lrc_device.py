"""A code FAMILY on the device path: Azure's LRC(12,2,2) (Huang et al.,
USENIX ATC 2012) sealed, served and repaired through the batch
scheduler, held to the benchmark's plain reference.

(a) the program's generator against the reference's, and the device
    path's encode (MeshCoder, the BatchCoder facade) against the
    reference's encoding, all 16 rows; (b) every loss pattern of up to 4
    of 16 shards: decodable exactly where the reference's rank says so,
    and rebuilt through the scheduler to the rows they were; a single
    loss reads 6 rows, and jobs of 6 and of 12 rows never share a
    dispatch; (c) a 16-shard volume through the CLI servers beside an
    RS(10,4) one: seal, mount, reads with one and with two shards of a
    group gone, rebuild; (d) the parser's ``lrc-<k>-<l>-<g>``.
"""

import hashlib
import itertools
import json
import os

import numpy as np
import pytest

from benchmark import reference
from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, CodeSpecError,
                                        LrcScheme, code_spec_name,
                                        parse_code_spec, scheme_from_dict,
                                        scheme_to_dict)
from seaweedfs_tpu.ops import lrc
from seaweedfs_tpu.ops.rs_mesh import MeshCoder
from seaweedfs_tpu.parallel.batcher import BatchCoder, EcBatchScheduler

AZURE = LrcScheme(12, 2, 2)
K, TOTAL = AZURE.data_shards, AZURE.total_shards


def _code(scheme: LrcScheme) -> dict:
    """A configuration's ``code`` block, as the reference reads it."""
    return {**scheme_to_dict(scheme),
            "parity_shards": scheme.parity_shards,
            "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20}


def _reference_generator(scheme: LrcScheme) -> np.ndarray:
    k = scheme.data_shards
    return np.asarray([[int(i == j) for j in range(k)] for i in range(k)]
                      + reference.code_parity_matrix(_code(scheme)),
                      dtype=np.uint8)


def _reference_rank(rows) -> int:
    """Rank over GF(2^8) by elimination in the reference's own
    arithmetic (``gf_mul``, ``gf_inv``)."""
    rows = [list(map(int, r)) for r in rows]
    rank = 0
    for col in range(len(rows[0])):
        piv = next((i for i in range(rank, len(rows)) if rows[i][col]), None)
        if piv is None:
            continue
        rows[rank], rows[piv] = rows[piv], rows[rank]
        inv = reference.gf_inv(rows[rank][col])
        rows[rank] = [reference.gf_mul(inv, x) for x in rows[rank]]
        for i in range(len(rows)):
            if i != rank and rows[i][col]:
                c = rows[i][col]
                rows[i] = [x ^ reference.gf_mul(c, y)
                           for x, y in zip(rows[i], rows[rank])]
        rank += 1
    return rank


def _volume_rows(seed: int, n: int) -> np.ndarray:
    """All 16 rows of a seeded block-group, by the reference."""
    data = np.random.default_rng(seed).integers(0, 256, (K, n),
                                                dtype=np.uint8)
    parity = reference.apply_matrix(
        reference.code_parity_matrix(_code(AZURE)), data)
    return np.concatenate([data, parity])


def _one_device_scheduler() -> EcBatchScheduler:
    """A scheduler on ONE of the suite's virtual devices, like one chip:
    a lone job is a batch of one lane, not padded to eight."""
    return EcBatchScheduler(mesh_coder=MeshCoder(DEFAULT_SCHEME,
                                                 n_devices=1))


@pytest.fixture(scope="module")
def sched():
    s = _one_device_scheduler()
    yield s
    s.stop()


# ------------------------------------------------------ (d) the parser

@pytest.mark.parametrize("spec,want", [
    ("lrc-12-2-2", AZURE), ("LRC-12-2-2", AZURE),
    ("lrc-10-2-2", LrcScheme(10, 2, 2)), ("lrc", LrcScheme(10, 2, 2)),
    ("lrc-12-3-2", LrcScheme(12, 3, 2)), ("lrc-24-4-4", LrcScheme(24, 4, 4)),
])
def test_code_spec_parses_a_family(spec, want):
    got = parse_code_spec(spec)
    assert got == want and type(got) is LrcScheme
    assert parse_code_spec(code_spec_name(got)) == got
    assert scheme_from_dict(scheme_to_dict(got)) == got


@pytest.mark.parametrize("spec", [
    "lrc-12-5-2",            # groups that do not divide the data shards
    "lrc-10-3-2", "lrc-28-2-3",    # ... and 33 shards: over a volume's 32
    "lrc-30-2-2", "lrc-0-2-2", "lrc-12-0-2", "lrc-12-2-0", "lrc-12-2",
    "lrc-12-2-2-1", "lrc-a-2-2", "lrc--12-2-2", "lrc-mt", "lrc-12_2_2"])
def test_code_spec_refuses_what_no_volume_can_carry(spec):
    with pytest.raises(CodeSpecError):
        parse_code_spec(spec)


def test_lrc_stays_the_stores_default_family_code():
    assert parse_code_spec("lrc") == LrcScheme(10, 2, 2) != AZURE
    assert code_spec_name(AZURE) == "lrc-12-2-2"
    assert AZURE.total_shards == 16 and AZURE.group_size == 6
    assert AZURE.group_members(0) == [0, 1, 2, 3, 4, 5, 12]
    assert AZURE.global_parity_ids() == [14, 15]


# ------------------------------------------- (a) against the reference

@pytest.mark.parametrize("k,l,g", [(10, 2, 2), (12, 2, 2)])
def test_generator_is_the_references(k, l, g):
    scheme = LrcScheme(k, l, g)
    ours = lrc.generator_matrix(scheme)
    assert ours.shape == (k + l + g, k)
    assert np.array_equal(ours, _reference_generator(scheme))
    # a local row is its group's part of an RS row: not ones, so the XOR
    # of a group's survivors is NOT the lost shard
    local = ours[k][:scheme.group_size]
    assert local.all() and len(set(local.tolist())) > 1


def test_mesh_coder_encodes_all_sixteen_rows_as_the_reference():
    rows = _volume_rows(seed=1201, n=4096)
    coder = MeshCoder(AZURE)
    parity = coder.encode_batch(np.stack([rows[:K], rows[:K][::-1]]))
    assert parity.shape == (2, 4, 4096)
    assert np.array_equal(np.concatenate([rows[:K], parity[0]]), rows)
    again = reference.apply_matrix(
        reference.code_parity_matrix(_code(AZURE)), rows[:K][::-1])
    assert np.array_equal(parity[1], again)
    assert coder.apply_widths == (6, 12)
    assert MeshCoder(DEFAULT_SCHEME).apply_widths == (10,)


def test_the_facade_of_a_family_is_a_device_coder(sched):
    rows = _volume_rows(seed=1202, n=5000)
    facade = BatchCoder(sched).for_scheme(AZURE)
    assert isinstance(facade, BatchCoder) and facade.scheduler is sched
    assert facade.scheme == AZURE and facade.device_report() is not None
    before = sched.stats()
    assert np.array_equal(facade.encode_array(rows[:K]), rows[K:])
    full = facade.encode([r.tobytes() for r in rows[:K]])
    assert [bytes(s) for s in full] == [r.tobytes() for r in rows]
    st = sched.stats()
    mine = st["by_spec"]["lrc-12-2-2"]
    assert mine["jobs"] - before["by_spec"].get(
        "lrc-12-2-2", {"jobs": 0})["jobs"] == 2
    assert mine["cpu_dispatches"] == 0 and mine["rows"].get("12", 0) >= 2
    assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0
    # the RS geometry "with the same (k, m)" is nobody's: not asked for
    assert "rs-12-4" not in st["by_spec"]


@pytest.mark.parametrize("scheme,stem", [
    (AZURE, "lrc_12_2_2"), (LrcScheme(10, 2, 2), "lrc_10_2_2"),
    (DEFAULT_SCHEME, "rs_10_4")])
def test_programs_are_named_by_family(scheme, stem):
    """``jit_ec_encode_lrc_12_2_2`` / ``jit_ec_apply_lrc_12_2_2`` in a
    device trace (both widths of a family's apply program); every RS
    program keeps its name letter for letter."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import rs_mesh
    from seaweedfs_tpu.parallel import mesh as mesh_mod
    mesh = mesh_mod.batch_mesh(1)
    k, m = scheme.data_shards, scheme.parity_shards
    words = jax.ShapeDtypeStruct((1, k, 64), jnp.uint32)
    enc = rs_mesh.batch_encode_fn(scheme, mesh).lower(words).as_text()
    assert f"@jit_ec_encode_{stem} " in enc[:200]
    for width in rs_mesh.apply_widths(scheme):
        app = rs_mesh.batch_apply_fn(scheme, mesh, width).lower(
            jax.ShapeDtypeStruct((1, width, 64), jnp.uint32),
            jax.ShapeDtypeStruct((1, m, width), jnp.uint32)).as_text()
        assert f"@jit_ec_apply_{stem} " in app[:200]
    assert rs_mesh.batch_apply_fn(scheme, mesh) \
        is rs_mesh.batch_apply_fn(scheme, mesh, k)


# ------------------------------------- (b) every loss of up to 4 of 16

def _patterns():
    for n in range(1, 5):
        yield from itertools.combinations(range(TOTAL), n)


# four losses, two data shards of EACH group: the (12, 2, 2) topology can
# take them (each local parity one, the globals two), this construction
# over GF(2^8) cannot (the paper builds its own coefficients so that it
# can); the reference's rank says the same, and the program refuses them
NOT_MAXIMALLY_RECOVERABLE = {(0, 3, 8, 11), (0, 3, 9, 10), (1, 2, 8, 11),
                             (1, 2, 9, 10), (2, 4, 8, 10)}


def _topology_decodes(lost) -> bool:
    return sum(max(0, len(set(AZURE.group_members(g)) & set(lost)) - 1)
               for g in range(2)) + len({14, 15} & set(lost)) <= 2


def test_every_loss_pattern_decodes_exactly_where_the_reference_can():
    """2,516 patterns of 1 to 4 of 16 shards: the family's plan exists
    exactly where the reference's generator rows that are left have rank
    12: every loss of up to three (696), and 1,563 of the 1,820 of four
    (86%: the topology's 1,568 less the five above)."""
    gen = _reference_generator(AZURE)
    host = lrc.LrcCoder(AZURE)
    seen = {True: 0, False: 0}
    for lost in _patterns():
        left = [s for s in range(TOTAL) if s not in lost]
        decodable = _reference_rank(gen[left]) == K
        try:
            src, mat = host.plan_rebuild(left, list(lost))
            planned = True
            assert set(src) <= set(left) \
                and mat.shape == (len(lost), len(src))
        except ValueError:
            planned = False
        assert planned == decodable, lost
        assert decodable == (_topology_decodes(lost)
                             and lost not in NOT_MAXIMALLY_RECOVERABLE), lost
        assert decodable or len(lost) == 4
        seen[decodable] += 1
    assert seen == {True: 696 + 1563, False: 257}


def test_every_decodable_loss_is_rebuilt_through_the_scheduler(sched):
    """Each lost DATA row comes back as the data it was, each lost parity
    row as the reference's encoding, through store-like jobs of the
    plan's rows on the scheduler (``BatchCoder.reconstruct_rows``); a
    single loss reads 6 rows (a global parity's: 12)."""
    rows = _volume_rows(seed=1203, n=512)
    facade = BatchCoder(sched, AZURE)
    before = sched.stats()["by_spec"].get("lrc-12-2-2", {}).get("rows", {})
    done = {6: 0, 12: 0}
    for lost in _patterns():
        left = [s for s in range(TOTAL) if s not in lost]
        try:
            src, mat = facade.plan_rebuild(left, list(lost))
        except ValueError:
            continue
        if len(lost) == 1:
            assert len(src) == (12 if lost[0] >= 14 else 6), lost
            if lost[0] < 14:
                group = AZURE.group_members(AZURE.group_of(lost[0]))
                assert sorted(src) == sorted(set(group) - set(lost))
        buf = facade.job_rows(512, len(src))
        assert buf.shape[0] == len(src) and not buf.any()
        buf[:, :512] = rows[src]
        rec = facade.reconstruct_rows(buf, mat)
        assert np.array_equal(rec[:, :512], rows[list(lost)]), lost
        done[len(src)] += 1
    st = sched.stats()
    after = st["by_spec"]["lrc-12-2-2"]["rows"]
    assert after.get("6", 0) - before.get("6", 0) == done[6] == 14
    assert after.get("12", 0) - before.get("12", 0) == done[12] == 2245
    assert st["cpu_batches"] == 0 and st["coder_fallbacks"] == 0


def test_jobs_of_six_rows_and_of_twelve_never_share_a_dispatch(
        monkeypatch):
    """Grouped, not padded: local repairs (6 rows) and global decodes
    (12) queued together leave in dispatches of one row count each."""
    seen = []
    real = MeshCoder.rebuild_batch

    def rebuild_batch(self, srcdata, mats):
        seen.append(srcdata.shape)
        return real(self, srcdata, mats)

    monkeypatch.setattr(MeshCoder, "rebuild_batch", rebuild_batch)
    rows = _volume_rows(seed=1204, n=1024)
    own = _one_device_scheduler()
    try:
        facade = BatchCoder(own, AZURE)
        jobs = []
        for lost in ([3], [3, 4], [7], [6, 9], [0], [1, 2, 15], [13]):
            src, mat = facade.plan_rebuild(
                [s for s in range(TOTAL) if s not in lost], lost)
            jobs.append((lost, own.submit_rebuild(rows[src], mat,
                                                  scheme=AZURE)))
        for lost, fut in jobs:
            assert np.array_equal(fut.result(timeout=120), rows[lost]), lost
        st = own.stats()
    finally:
        own.stop()
    assert st["by_spec"]["lrc-12-2-2"]["rows"] == {"6": 4, "12": 3}
    assert st["cpu_batches"] == 0 and st["mesh_batches"] == len(seen)
    assert {sh[1] for sh in seen} == {6, 12}
    assert sum(sh[0] for sh in seen if sh[1] == 6) == 4
    assert sum(sh[0] for sh in seen if sh[1] == 12) == 3


def test_a_local_repair_written_over_k_rows_rides_the_narrow_program():
    """The harness's warm-up call: shard 0 from the first 12 of shards
    1..15 (``rebuild_matrix``), whose coefficients are zero outside the
    group: the operand is cut to the six rows it reads."""
    rows = _volume_rows(seed=1205, n=256)
    coder = MeshCoder(AZURE, n_devices=1)
    mat = coder.rebuild_matrix(list(range(1, TOTAL)), [0])
    assert mat.shape == (1, K)
    assert np.flatnonzero(mat[0]).tolist() == [0, 1, 2, 3, 4, 11]
    rec = coder.rebuild_batch(np.stack([rows[1:13]] * 2), [mat] * 2)
    assert np.array_equal(rec[0][0], rows[0])
    assert np.array_equal(rec[1][0], rows[0])
    assert coder.programs == {("apply", 2, 6, 64)}
    # two losses in two groups: 12 rows read, the wide program; a width
    # between the two is padded up to it with zero rows
    plan = coder.plan_rebuild([s for s in range(TOTAL) if s not in (0, 6)],
                              [0, 6])
    assert len(plan[0]) == 12
    rec = coder.rebuild_batch(rows[plan[0]][None], [plan[1]])
    assert np.array_equal(rec[0], rows[[0, 6]])
    eight = LrcScheme(12, 3, 2)
    c8 = MeshCoder(eight, n_devices=1)
    r8 = np.random.default_rng(5).integers(0, 256, (12, 64), dtype=np.uint8)
    full8 = np.concatenate([r8, c8.encode_batch(r8[None])[0]])
    src, mat = c8.plan_rebuild([s for s in range(17) if s not in (0, 4)],
                               [0, 4])
    assert len(src) == 8
    assert np.array_equal(c8.rebuild_batch(full8[src][None], [mat])[0],
                          full8[[0, 4]])
    assert ("apply", 1, 12, 16) in c8.programs


@pytest.mark.parametrize("spec", ["rs-10-4", "rs-6-3", "lrc-12-2-2"])
def test_the_two_step_forms_equal_the_one_step_forms(spec):
    """PR 36: ``encode_batch_begin(x).result()`` and
    ``rebuild_batch_begin(...).result()`` are the one-step forms in two
    halves (launch; fetch and unpack), for both RS geometries and for the
    family at BOTH apply widths (a local repair's k / l rows and the
    global decode's k), and several may be launched before the first is
    collected."""
    from seaweedfs_tpu.models.coder import host_coder, parse_code_spec
    scheme = parse_code_spec(spec)
    k, total = scheme.data_shards, scheme.total_shards
    coder = MeshCoder(scheme, n_devices=1)
    rng = np.random.default_rng(3600)
    data = rng.integers(0, 256, (3, k, 512), dtype=np.uint8)
    begun = [coder.encode_batch_begin(data[:b]) for b in (1, 3)]
    for b, d in zip((1, 3), begun):
        assert np.array_equal(d.result(), coder.encode_batch(data[:b]))
    full = np.concatenate([data[0], coder.encode_batch(data[:1])[0]])
    assert np.array_equal(
        full[k:], host_coder(scheme, threaded=False).encode_array(data[0]))
    plan = getattr(coder, "plan_rebuild", None)
    losses = [[1], [1, k - 1]] if plan else [[1]]
    widths = set()
    for lost in losses:
        have = [s for s in range(total) if s not in lost]
        src, mat = plan(have, lost) if plan else \
            (have[:k], coder.rebuild_matrix(have, lost))
        operand = np.stack([full[src]] * 2)
        two = coder.rebuild_batch_begin(operand, [mat] * 2)
        one = coder.rebuild_batch(operand, [mat] * 2)
        for got, want in zip(two.result(), one):
            assert np.array_equal(got, want)
            assert np.array_equal(got, full[lost])
        widths.add(len(src))
    assert widths == set(coder.apply_widths)
    assert coder.stage_n["launch"] == coder.stage_n["fetch"] \
        == coder.stage_n["pad"] == coder.stage_n["unpack"]


# ----------------------- (c) a 16-shard volume through the CLI servers

@pytest.fixture(scope="module")
def served():
    """CLI master + CLI ``volume -ecBatcher`` (behind the benchmark's
    wrapper, which adds nothing to a request), two filled volumes of
    ~6 MB each: data in shards 0-5 of the 16-shard one."""
    from benchmark import loadgen
    from benchmark.harness import Cluster
    cluster = Cluster()
    try:
        cluster.start({"encode": [], "apply": []}, 64, 8,
                      {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
        corpus = loadgen.fill(cluster, {
            "volumes": 2, "fill": [{"bytes": 3000, "count": 40},
                                   {"bytes": 70000, "count": 170}]}, 1222)
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


def _stat(cluster, vid: int) -> dict:
    return cluster.http("GET", cluster.volume
                        + f"/admin/ec/shard_stat?volumeId={vid}")


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def test_sixteen_shard_volume_beside_a_fourteen_shard_one(served):
    from seaweedfs_tpu.shell.commands import ShellContext
    from seaweedfs_tpu.utils.httpd import http_call
    from tests.test_code_geometry import _lose, _mounted, _read_all
    cluster, corpus = served
    vlrc, vrs = corpus.vids
    n_objects = len(corpus.objects) // 2
    base = os.path.join(cluster.voldir, str(vlrc))
    for vid in corpus.vids:
        cluster.http("POST", cluster.volume + "/admin/mark_readonly",
                     {"volume_id": vid, "read_only": True})
    status, dat, _ = http_call(
        "GET", f"http://{cluster.volume}/admin/volume_file?volumeId={vlrc}"
        "&ext=.dat", timeout=60)
    assert status == 200 and len(dat) > 5 << 20
    dat_copy = os.path.join(cluster.workdir, "vlrc.dat")
    with open(dat_copy, "wb") as f:
        f.write(dat)

    sh = ShellContext(cluster.master, use_grpc=False)
    sh.lock()
    try:
        with pytest.raises(CodeSpecError):
            sh.ec_encode(vid=vlrc, code="lrc-12-5-2")
        out = sh.ec_encode(vid=vlrc, code="lrc-12-2-2")
        assert out[0]["placement"] == {cluster.volume: list(range(16))}
        sh.ec_encode(vid=vrs)

        # sixteen files (two past every volume this store has served)
        # equal to the reference's encoding, and a .vif that says so
        files = [f"{base}.ec{s:02d}" for s in range(16)]
        assert all(os.path.exists(p) for p in files)
        assert not os.path.exists(f"{base}.ec16")
        with open(base + ".vif") as f:
            assert json.load(f)["code"] == {
                "family": "lrc", "data_shards": 12, "local_groups": 2,
                "global_parities": 2}
        assert reference.differing_files(dat_copy, files,
                                         _code(AZURE)) == []
        assert _mounted(cluster, vlrc) == set(range(16))
        assert _mounted(cluster, vrs) == set(range(14))
        geo = {e["id"]: (e.get("data_shards"), e.get("total_shards"))
               for dc in sh.topology()["data_centers"]
               for r in dc["racks"] for n in r["nodes"]
               for e in n["ec_shards"]}
        assert geo == {vlrc: (12, 16), vrs: (10, 14)}
        stat = _stat(cluster, vlrc)
        assert stat["shards"] == list(range(16))
        assert stat["code"]["family"] == "lrc"
        assert sh.ec_rebuild(apply=False) == []
        b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
        assert b["cpu_batches"] == 0 and b["mesh_batches"] > 0
        sealed = b["by_spec"]["lrc-12-2-2"]
        assert sealed["jobs"] >= 1 and sealed["cpu_dispatches"] == 0
        assert set(sealed["rows"]) == {"12"}

        assert _read_all(cluster, corpus, vlrc) == (n_objects, 0, [])
        assert _read_all(cluster, corpus, vrs) == (n_objects, 0, [])

        # shard 3 gone: every lost interval from the six survivors of
        # its group, on the device
        shas = {s: _sha(f"{base}.ec{s:02d}") for s in (3, 4)}
        s0 = _stat(cluster, vlrc)
        _lose(cluster, vlrc, 3)
        assert _read_all(cluster, corpus, vlrc) == (n_objects, 0, [])
        s1 = _stat(cluster, vlrc)
        rec = _delta(s1["recover_stats"], s0["recover_stats"])
        reads = _delta(s1["read_stats"], s0["read_stats"])
        assert rec["local"] >= 1 and rec["global"] == rec["generic"] == 0
        assert rec["local"] == reads["intervals_recovered"] \
            == reads["survivor_gathers"]
        assert reads["survivor_reads"] == 6 * rec["local"]
        # shards 3 and 4 gone: two of one group, the global decode from
        # twelve (3's and 4's intervals alike), on the device too
        _lose(cluster, vlrc, 4)
        assert _read_all(cluster, corpus, vlrc) == (n_objects, 0, [])
        s2 = _stat(cluster, vlrc)
        rec = _delta(s2["recover_stats"], s1["recover_stats"])
        reads = _delta(s2["read_stats"], s1["read_stats"])
        assert rec["global"] >= 2 and rec["local"] == rec["generic"] == 0
        assert rec["global"] == reads["survivor_gathers"]
        assert reads["survivor_reads"] == 12 * rec["global"]
        # the RS(10,4) volume beside it, one shard gone
        _lose(cluster, vrs, 3)
        assert _read_all(cluster, corpus, vrs) == (n_objects, 0, [])
        b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
        assert b["cpu_batches"] == 0 and b["coder_fallbacks"] == 0
        mine = b["by_spec"]["lrc-12-2-2"]
        assert mine["cpu_dispatches"] == 0
        assert mine["rows"]["6"] == s1["recover_stats"]["local"] \
            - s0["recover_stats"]["local"]
        assert b["by_spec"]["rs-10-4"]["rows"].keys() == {"10"}
        _st, text, _ = http_call("GET", f"http://{cluster.volume}/metrics")
        lines = [ln for ln in text.decode().splitlines()
                 if "ec_batch_spec" in ln and 'spec="lrc-12-2-2"' in ln]
        assert any('stat="rows.6"' in ln for ln in lines)
        assert any('stat="rows.12"' in ln for ln in lines)

        # a sampled read leaves the plan's stage, told from the coder's
        cluster.http("POST", cluster.volume + "/admin/cache",
                     {"clear": True})
        tid = "35aa35aa35aa35aa"
        large = sorted(fid for fid, (_d, size) in corpus.objects.items()
                       if fid.startswith(f"{vlrc},") and size == 70000)
        for fid in large[:40]:
            assert http_call(
                "GET", f"http://{cluster.volume}/{fid}",
                headers={"X-Weed-Trace": f"{tid}:0000beef:1"})[0] == 200
        spans = cluster.http(
            "GET", cluster.volume
            + f"/debug/traces?trace={tid}&limit=2048")["spans"]
        plans = [s for s in spans if s["name"] == "store.ec.plan"]
        assert plans and all(
            s["annotations"]["strategy"] == "global"
            and len(s["annotations"]["sources"]) == 12 for s in plans)
        for name in ("ec.batch.submit", "ec.batch.dispatch",
                     "ec.mesh.launch", "ec.mesh.fetch"):
            mine = [s for s in spans if s["name"] == name
                    and s["annotations"].get("spec") == "lrc-12-2-2"]
            assert mine and all(s["annotations"]["rows"] == 12
                                for s in mine), name

        # ec.rebuild brings both back byte for byte, on the device
        plans = {p["vid"]: p for p in sh.ec_rebuild()}
        assert plans[vlrc]["missing"] == [3, 4]
        assert sorted(plans[vlrc]["rebuilt"]) == [3, 4]
        assert _mounted(cluster, vlrc) == set(range(16))
        assert {s: _sha(f"{base}.ec{s:02d}") for s in shas} == shas
        b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
        assert b["cpu_batches"] == 0 and b["coder_fallbacks"] == 0
        # whole again: no read is repaired any more
        s3 = _stat(cluster, vlrc)
        assert _read_all(cluster, corpus, vlrc) == (n_objects, 0, [])
        assert _stat(cluster, vlrc)["recover_stats"] \
            == s3["recover_stats"]
    finally:
        sh.unlock()
