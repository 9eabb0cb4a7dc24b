"""A configuration's code reaches every part of the harness (PR 34): the
reference knows a code by its family, ``prepare()`` seals under the asked
code, the wrapper warms the asked scheme's programs or says ``unwarmed``.
Held on the two deployments the harness was opened for, at tiny sizes on
the CPU backend: HDFS RS-6-3-1024k READ with a holder down, and a locally
repairable code (the program's LRC(10,2,2)), sealed and read.  Their
cells are in ``cells-families.json`` beside ``cells.json``; the same
cells at the benchmark's own sizes run on the chip from
``scripts/families_on_chip.sh``.

    python -m pytest benchmark/tests/test_families.py -q
"""

import hashlib
import itertools
import json
import os

import numpy as np
import pytest

from benchmark import loadgen, reference, run
from benchmark.harness import Cluster

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells-families.json")


def lrc(k, l, g):
    return {"family": "lrc", "data_shards": k, "parity_shards": l + g,
            "local_groups": l, "global_parities": g}


LRC = lrc(10, 2, 2)


def generator(code):
    """Identity on top (data shards are the data), the code's parity
    rows below: one row a shard, in shard order."""
    k = code["data_shards"]
    return [[int(i == j) for j in range(k)] for i in range(k)] \
        + reference.code_parity_matrix(code)


def determines_the_data(rows):
    """Whether some k of the rows invert, by the reference's own
    ``mat_inv`` (k = the rows' width)."""
    k = len(rows[0])
    for pick in itertools.combinations(rows, k):
        try:
            reference.mat_inv([list(r) for r in pick])
            return True
        except ValueError:
            pass
    return False


# ---- the reference, by family ----

@pytest.mark.parametrize("k,m", [(10, 4), (6, 3)])
def test_family_rs_gives_the_matrices_it_gave(k, m):
    from seaweedfs_tpu.ops import gf256
    code = reference.rs_code(k, m)
    assert reference.code_parity_matrix(code) == reference.parity_matrix(k, m)
    assert reference.code_parity_matrix({**code, "family": "rs"}) \
        == reference.generator_matrix(k, m)[k:]
    assert np.array_equal(
        np.asarray(reference.code_parity_matrix(code), dtype=np.uint8),
        np.asarray(gf256.parity_matrix(k, m), dtype=np.uint8))
    # a block that names no family is RS, as every block was read
    del code["family"]
    assert reference.code_parity_matrix(code) == reference.parity_matrix(k, m)


@pytest.mark.parametrize("k,l,g", [(10, 2, 2), (12, 2, 2)])
def test_the_lrc_generator_is_the_programs(k, l, g):
    """Tied to ``ops/lrc.generator_matrix`` as ``tests/test_code_geometry``
    ties ``parity_matrix(6, 3)`` to ``gf256``: two derivations, one code
    (the program's LRC(10,2,2), and Azure's topology LRC(12,2,2))."""
    from seaweedfs_tpu.models.coder import LrcScheme
    from seaweedfs_tpu.ops import lrc as program
    mine = np.asarray(generator(lrc(k, l, g)), dtype=np.uint8)
    assert np.array_equal(mine, program.generator_matrix(LrcScheme(k, l, g)))


@pytest.mark.parametrize("k,l,g", [(10, 2, 2), (12, 2, 2), (12, 3, 2)])
def test_a_local_row_is_zero_outside_its_group(k, l, g):
    pm = reference.code_parity_matrix(lrc(k, l, g))
    assert len(pm) == l + g
    size = k // l
    base = reference.parity_matrix(k, g + 1)
    for i in range(l):
        for j in range(k):
            inside = i * size <= j < (i + 1) * size
            assert pm[i][j] == (base[0][j] if inside else 0)
            assert not inside or pm[i][j] != 0
    assert pm[l:] == base[1:]


def test_lrc_10_2_2_decodes_exactly_what_its_topology_allows():
    """Of the reference ALONE: every loss of up to l + g = 4 of the 14
    shards leaves generator rows of full rank exactly where the (10, 2,
    2) topology can decode it: each group's local parity takes one loss
    of its six members on itself, the two globals take two more anywhere.
    1,471 patterns; the rank by the reference's own elimination."""
    gen = generator(LRC)
    groups = [set(range(0, 5)) | {10}, set(range(5, 10)) | {11}]
    seen = {True: 0, False: 0}
    for n in range(5):
        for lost in itertools.combinations(range(14), n):
            beyond = sum(max(0, len(grp & set(lost)) - 1) for grp in groups) \
                + len({12, 13} & set(lost))
            decodable = beyond <= 2
            left = [gen[s] for s in range(14) if s not in lost]
            assert determines_the_data(left) == decodable, lost
            seen[decodable] += 1
    # all 470 of up to three losses, and 861 of the 1,001 of four
    assert seen == {True: 1331, False: 140}


def test_a_lost_data_block_comes_back_from_its_group_alone():
    """What a local repair reads: shard 3 from the four other data
    shards of its group and the group's local parity, by the local row's
    own coefficients (a job of five rows where RS(10,4) gathers ten)."""
    rng = np.random.default_rng(34)
    data = rng.integers(0, 256, size=(10, 64), dtype=np.uint8)
    local = reference.code_parity_matrix(LRC)[0]
    parity = reference.apply_matrix([local], data)[0]
    inv = reference.gf_inv(local[3])
    row = [reference.gf_mul(inv, c) for j, c in enumerate(local[:5])
           if j != 3] + [inv]
    got = reference.apply_matrix(
        [row], np.stack([data[j] for j in (0, 1, 2, 4)] + [parity]))[0]
    assert np.array_equal(got, data[3])


def test_a_code_block_that_contradicts_itself_is_refused():
    with pytest.raises(ValueError):
        reference.code_parity_matrix({**LRC, "parity_shards": 3})
    with pytest.raises(ValueError):
        reference.code_parity_matrix({**LRC, "family": "clay"})
    with pytest.raises(ValueError):
        reference.code_parity_matrix(lrc(10, 3, 2))


def test_shard_files_under_lrc(tmp_path):
    """The file-level comparison under a family: fourteen files written
    from the reference's own rows agree, and one flipped byte in a local
    parity is found by ``differing_files`` and ``expected_spans``."""
    code = {**LRC, "large_block_bytes": 1 << 20, "small_block_bytes": 4096}
    size = 3 * 40960 + 777
    body = np.random.default_rng(7).integers(0, 256, size, dtype=np.uint8)
    dat = tmp_path / "1.dat"
    dat.write_bytes(body.tobytes())
    pm = reference.code_parity_matrix(code)
    files = [bytearray() for _ in range(14)]
    for off, block in reference.encode_rows(size, 10, 1 << 20, 4096):
        rows = np.zeros((10, block), dtype=np.uint8)
        for i in range(10):
            part = body[off + i * block:off + (i + 1) * block]
            rows[i, :len(part)] = part
        for sid, row in enumerate([*rows, *reference.apply_matrix(pm, rows)]):
            files[sid] += row.tobytes()
    paths = []
    for sid, content in enumerate(files):
        p = tmp_path / f"1.ec{sid:02d}"
        p.write_bytes(bytes(content))
        paths.append(str(p))
    assert reference.differing_shard_files_many(
        [(str(dat), paths)], code, threads=2) == [[]]
    # held to RS(10,4) the same files differ in all four parities
    assert reference.differing_shard_files(
        str(dat), paths, 10, 4, 1 << 20, 4096) == [10, 11, 12, 13]
    want = reference.expected_spans(str(dat), [(4096, 512)], code)[0]
    assert want == b"".join(bytes(f[4096:4096 + 512]) for f in files)
    files[11][5000] ^= 1
    with open(paths[11], "wb") as f:
        f.write(bytes(files[11]))
    assert reference.differing_files(str(dat), paths, code) == [11]


# ---- prepare() seals under the asked code ----

def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,vif", [
    ("tiny-hdfs-rs6-3-holder-down",
     {"family": "rs", "data_shards": 6, "parity_shards": 3}),
    ("tiny-lrc10-2-2-holder-down",
     {"family": "lrc", "data_shards": 10, "local_groups": 2,
      "global_parities": 2}),
])
def test_prepare_seals_under_the_asked_code(monkeypatch, name, vif):
    """A tiny volume through the CLI servers on the CPU backend: sealed
    by ``prepare()``, its ``.vif`` carries the asked code, the shard files
    are as many as the stated code says and the reference's, and every
    object reads back with shard 3 gone."""
    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    config = _config(name)
    code = config["code"]
    total = code["data_shards"] + code["parity_shards"]
    cluster = Cluster()
    try:
        warm = cluster.start(
            {"encode": [], "apply": [], "code": loadgen.asked_code(config)},
            config["volume_size_limit_mb"], config["servers"]["max_volumes"],
            {})
        # no shape listed: nothing warmed; the report still says which
        # scheme the server would serve from a host coder
        assert warm["programs"] == []
        assert ("unwarmed" in warm) == (code["family"] == "lrc")
        corpus = loadgen.fill(cluster, {"volumes": 1, "fill": [
            {"bytes": 3000, "count": 60}, {"bytes": 70000, "count": 70}]}, 34)
        # 5 MB: blocks 0-4 of the first row, shard 3 among them.  The .dat
        # goes with the seal: a copy for the reference
        vid = corpus.vids[0]
        base = os.path.join(cluster.voldir, str(vid))
        with open(base + ".dat", "rb") as f:
            dat_bytes = f.read()
        loadgen.prepare(cluster, config, corpus)
        with open(base + ".vif") as f:
            assert json.load(f)["code"] == vif
        shards = loadgen.shard_paths(cluster, vid, total)
        assert [s for s in range(18) if os.path.exists(
            f"{base}.ec{s:02d}")] == [s for s in range(total) if s != 3]
        dat_copy = os.path.join(cluster.workdir, "copy.dat")
        with open(dat_copy, "wb") as f:
            f.write(dat_bytes)
        assert reference.differing_files(dat_copy, shards, code) == [3]
        mc = MasterClient(cluster.master, cache_ttl=0.0)
        for fid, (digest, _size) in sorted(corpus.objects.items()):
            got = operation.read_data(mc, fid)
            assert hashlib.sha256(got).hexdigest() == digest, fid
        stat = cluster.http("GET", cluster.volume
                            + f"/admin/ec/shard_stat?volumeId={vid}")
        assert sum(stat["recover_stats"].values()) >= 1
        if code["family"] == "lrc":
            assert stat["recover_stats"]["local"] >= 1
            assert stat["recover_stats"]["generic"] == 0
    except BaseException:
        cluster.print_log_tails()
        raise
    finally:
        cluster.stop()
        cluster.cleanup()


# ---- the three rehearsals, tiny ----

def drive(monkeypatch, cell, wrapper="benchmark.served_volume", trace=False):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return run.run_cell(cell, seed=3_400_000_034, seconds=1.5, trace=trace,
                        manifest_path=CELLS, volume_module=wrapper,
                        require_platform=None)


def failed(result):
    return {k for k, v in result["compared"].items() if not v["ok"]}


def test_a_nine_shard_volume_read_with_a_holder_down(monkeypatch, capfd):
    r = drive(monkeypatch, "tiny.reads.degraded.rs6-3", trace=True)
    assert r["correct"] and not failed(r), r["compared"]
    c = r["compared"]
    assert c["reads_wrong"]["value"] == 0 and c["reads_failed"]["value"] == 0
    assert c["intervals_reconstructed"]["value"] >= 1
    assert c["mesh_dispatches_in_window"]["value"] >= 1
    assert r["metrics"]["compiles_in_window.read"]["value"] == 0
    # one read in six crosses shard 3 of six; the cache serves some
    assert 8 < r["metrics"]["degraded_share.read"]["value"] <= 17
    err = capfd.readouterr().err
    # the wrapper warmed the cell's own geometry, all the listed shapes
    assert "warm-up" in err and " of rs-6-3: [['encode', 1, 1048576," in err
    assert "['apply', 4, 262144," in err and "UNWARMED" not in err
    assert "ec.encode -code 'rs-6-3'" in err


@pytest.mark.parametrize("fault", ["xor_rebuild", "altered_read"])
def test_a_broken_rebuild_of_six_is_not_correct(monkeypatch, fault):
    """``xor_rebuild`` is the control of ``tiny.reads.degraded`` (XOR of
    the survivors in place of GF(2^8) arithmetic); it fails the RS(6,3)
    read cell the same way."""
    r = drive(monkeypatch, "tiny.reads.degraded.rs6-3",
              f"benchmark.tests.faulty_volume:{fault}")
    assert not r["correct"]
    assert "reads_failed" in failed(r), r["compared"]


def test_an_lrc_seal_is_the_references_and_stays_off_the_device(
        monkeypatch, capfd):
    """Stated AND asked: LRC(10,2,2).  The reference and the program's
    host coder agree byte for byte; the program has no device coder of
    the family, so the wrapper reports ``unwarmed``, the run goes on, and
    ``correct`` is false on ``mesh_dispatches_in_window`` alone: the
    number ROADMAP M1 starts from."""
    r = drive(monkeypatch, "tiny.seal.single.lrc")
    assert failed(r) == {"mesh_dispatches_in_window"}, r["compared"]
    assert not r["correct"]
    c = r["compared"]
    assert c["shard_files_differing"]["value"] == 0
    assert c["sampled_spans_differing"]["value"] == 0
    assert c["calls_sampled"]["value"] >= 1
    assert c["mesh_dispatches_in_window"]["value"] == 0
    err = capfd.readouterr().err
    assert "of lrc-10-2-2: []" in err
    assert "UNWARMED: the server serves LRC(10,2,2) from" in err


def test_an_lrc_volume_read_with_a_holder_down(monkeypatch, capfd):
    """Sealed by ``prepare()`` under ``lrc``, shard 3 gone: every read is
    right, the lost intervals are rebuilt from the group (``local``), on
    the host on this tree (no dispatch to the mesh)."""
    r = drive(monkeypatch, "tiny.reads.degraded.lrc")
    assert failed(r) == {"mesh_dispatches_in_window"}, r["compared"]
    c = r["compared"]
    assert c["reads_wrong"]["value"] == 0 and c["reads_failed"]["value"] == 0
    assert c["intervals_reconstructed"]["value"] >= 1
    err = capfd.readouterr().err
    window = next(line for line in err.splitlines()
                  if line.startswith("[window] opened"))
    counters = json.loads(window[window.index("counters ") + 9:])
    assert counters["recover.by.local"] == counters["recover.intervals"] >= 1
    assert counters["recover.by.generic"] == 0
