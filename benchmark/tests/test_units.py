"""The yardstick's own arithmetic: the plain reference, the least-bytes
function, the end-to-end reductions, and the manifest against its files.

    python -m pytest benchmark/tests -q            (from the repo's root)
"""

import importlib
import json
import os

import numpy as np
import pytest

from benchmark import bytes_model, reference, run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
MIB = 1 << 20


# ---- reference ----

def test_field_tables():
    assert reference.gf_mul(2, 0x80) == 0x1D          # x^8 = x^4+x^3+x^2+1
    assert reference.gf_mul(0x53, reference.gf_inv(0x53)) == 1
    for a in (1, 2, 29, 255):
        assert reference.gf_mul(a, 1) == a and reference.gf_mul(a, 0) == 0


def test_generator_is_systematic_and_mds():
    gen = reference.generator_matrix(10, 4)
    assert gen[:10] == [[int(i == j) for j in range(10)] for i in range(10)]
    # any 10 rows invert: try those without each group of 4 consecutive
    for lo in range(0, 11):
        rows = [gen[i] for i in range(14) if not lo <= i < lo + 4]
        reference.mat_inv(rows)


def test_parity_matrix_is_the_programs():
    gf256 = pytest.importorskip("seaweedfs_tpu.ops.gf256")
    assert np.array_equal(np.array(reference.parity_matrix(10, 4)),
                          np.asarray(gf256.parity_matrix(10, 4)))


def test_apply_matrix_against_scalar_arithmetic():
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 256, (10, 64), dtype=np.uint8)
    pm = reference.parity_matrix(10, 4)
    got = reference.apply_matrix(pm, rows)
    for i in range(4):
        for c in range(64):
            acc = 0
            for j in range(10):
                acc ^= reference.gf_mul(pm[i][j], int(rows[j, c]))
            assert got[i, c] == acc


def test_recover_matrix_gives_the_lost_block_back():
    rng = np.random.default_rng(6)
    data = rng.integers(0, 256, (10, 32), dtype=np.uint8)
    full = np.concatenate(
        [data, reference.apply_matrix(reference.parity_matrix(10, 4), data)])
    present = [s for s in range(14) if s != 3]
    rm = reference.recover_matrix(10, 4, present, [3])
    got = reference.apply_matrix(rm, full[sorted(present)[:10]])
    assert np.array_equal(got[0], data[3])


def test_layout_rows():
    # small blocks only; the last row zero-filled
    assert reference.encode_rows(25, 10, 100, 2) == [(0, 2), (20, 2)]
    # strictly MORE than a large row left -> a large row first
    assert reference.encode_rows(1001, 10, 100, 2)[0] == (0, 100)
    assert reference.encode_rows(1000, 10, 100, 2)[0] == (0, 2)
    assert reference.shard_file_size(25, 10, 100, 2) == 4


def _write_volume(tmp_path, size, large, small, spoil=None):
    rng = np.random.default_rng(size)
    dat = tmp_path / "1.dat"
    dat.write_bytes(rng.bytes(size))
    pm = reference.parity_matrix(10, 4)
    shards = [bytearray() for _ in range(14)]
    raw = dat.read_bytes()
    for off, block in reference.encode_rows(size, 10, large, small):
        rows = np.zeros((10, block), dtype=np.uint8)
        for i in range(10):
            piece = raw[off + i * block: off + (i + 1) * block]
            rows[i, :len(piece)] = np.frombuffer(piece, dtype=np.uint8)
        parity = reference.apply_matrix(pm, rows)
        for i in range(10):
            shards[i] += rows[i].tobytes()
        for i in range(4):
            shards[10 + i] += parity[i].tobytes()
    paths = []
    for sid, body in enumerate(shards):
        if spoil and sid == spoil[0]:
            body[spoil[1]] ^= 0x40
        p = tmp_path / f"1.ec{sid:02d}"
        p.write_bytes(bytes(body))
        paths.append(str(p))
    return str(dat), paths


@pytest.mark.parametrize("spoil,want", [
    (None, []), ((12, 5), [12]), ((0, 4097), [0]), ((13, 8191), [13])])
def test_differing_shard_files(tmp_path, spoil, want):
    dat, paths = _write_volume(tmp_path, 3 * 40960 + 777, 1 << 20, 4096,
                               spoil)
    got = reference.differing_shard_files_many(
        [(dat, paths)], reference.rs_code(10, 4, 1 << 20, 4096), threads=3)
    assert got == [want]


def test_a_short_or_missing_shard_file_differs(tmp_path):
    dat, paths = _write_volume(tmp_path, 50000, 1 << 20, 4096)
    os.truncate(paths[2], 100)
    os.remove(paths[11])
    assert reference.differing_shard_files(
        dat, paths, 10, 4, 1 << 20, 4096) == [2, 11]


# ---- least bytes ----

def test_seal_min_bytes_one_full_row():
    # 10 MiB of data: 10 blocks read, 4 parity blocks written
    assert bytes_model.seal_min_bytes(10 * MIB, 10, 4, 1 << 30, MIB) \
        == 14 * MIB


def test_seal_min_bytes_tail_row():
    # a full row, then 3 MiB + 5 B: read as held; the widest block is
    # whole, so four whole parity blocks
    assert bytes_model.seal_min_bytes(13 * MIB + 5, 10, 4, 1 << 30, MIB) \
        == 14 * MIB + (3 * MIB + 5) + 4 * MIB
    # a tail of 100 B: 100 read, 4 x 100 written
    assert bytes_model.seal_min_bytes(10 * MIB + 100, 10, 4, 1 << 30, MIB) \
        == 14 * MIB + 500


# ---- end-to-end arithmetic ----

def test_percentile_is_nearest_rank_of_all_values():
    v = sorted(float(i) for i in range(1, 201))
    assert run.percentile(v, 50) == 100.0
    assert run.percentile(v, 99) == 198.0
    assert run.percentile([7.0], 99) == 7.0


def test_seal_rate_is_bytes_over_the_time_the_calls_took():
    rec = [(0.0, 2.0, 100e6, None, False, [], 0.0, 0.0),
           (2.5, 4.5, 100e6, None, False, [], 0.0, 0.3),  # after a pause
           (5.0, 6.0, 100e6, "boom", False, [], 0.0, 0.3)]  # failed: time
    assert run.seal_end_to_end(rec, 1)["seal_mbps"] \
        == pytest.approx(200.0 / 5.0)
    # two sealers side by side, back to back: the bytes over the window
    rec = [(0.0, 2.0, 100e6, None, False, [], 0.0),
           (0.0, 2.0, 100e6, None, False, [], 0.0)]
    assert run.seal_end_to_end(rec, 2)["seal_mbps"] == pytest.approx(100.0)


class _SealServer:
    """A stand-in for the cluster a sealer talks to: ``generate`` writes
    the nine shard files of a tiny RS(6,3) volume anew (``.tmp`` names,
    renamed) with ``.ecx`` and ``.vif``; ``delete_shards`` removes what
    it is told to, the index files with the last shard."""

    def __init__(self, voldir, deletes=True, writes=True):
        self.voldir, self.master, self.volume = str(voldir), "m", "v"
        self.deletes, self.writes = deletes, writes
        self.calls, self.found = [], []
        with open(os.path.join(self.voldir, "1.dat"), "wb") as f:
            f.write(bytes(6 * 4096))

    def http(self, method, url, body=None):
        op = url.rsplit("/", 1)[1]
        base = os.path.join(self.voldir, "1")
        shards = [f"{base}.ec{i:02d}" for i in range(9)]
        self.calls.append(op)
        if op == "delete_shards":
            assert body == {"volume_id": 1, "shard_ids": list(range(9))}
            if not self.deletes:
                raise RuntimeError("HTTP 500")
            for p in shards + [base + ".ecx", base + ".vif"]:
                os.remove(p)
        elif op == "generate":
            self.found.append(sum(os.path.exists(p) for p in shards))
            if self.writes or len(self.found) == 1:
                for p in shards + [base + ".ecx", base + ".vif"]:
                    with open(p + ".tmp", "wb") as f:
                        f.write(bytes(4096))
                    os.replace(p + ".tmp", p)
        return {}


def _seal_twice(server, seconds=0.25):
    from benchmark import loadgen
    corpus = loadgen.Corpus()
    corpus.vids = [1]
    config = {"code": reference.rs_code(6, 3, 1 << 20, 4096)}
    traffic = {"op": "seal", "workers": 1, "period_seconds": 0.1}
    records = []
    for stream in (3, 4):          # a warm-up window, then the window
        w = loadgen.Window(seconds)
        w.run(loadgen.seal_workers(server, config, traffic, corpus, 5,
                                   stream))
        records.append(w.all_records())
    return records


def test_every_seal_of_a_window_finds_a_volume_with_no_shards(tmp_path):
    """What the warm-up or the call before left is deleted through the
    server before each call, outside its timed span; the last call's
    files stay for the comparison."""
    server = _SealServer(tmp_path)
    warm, window = _seal_twice(server)
    assert len(warm) == 3 and len(window) == 3
    assert server.found == [0] * 6
    assert server.calls == ["generate"] + ["delete_shards", "generate"] * 5
    assert not any(r[3] or r[4] for r in warm + window)
    assert all(len(r[5]) == 16 for r in window)
    assert warm[0][7] == 0.0 and all(r[7] > 0 for r in warm[1:] + window)
    assert os.path.exists(tmp_path / "1.ec08")


def test_a_seal_over_files_that_would_not_go_is_told_by_their_inodes(tmp_path):
    """Where the delete fails the call replaces the last call's files,
    as every call did until PR 34: new inodes are a new seal's, the same
    inodes are stale; and a volume cleared and not sealed is stale."""
    server = _SealServer(tmp_path, deletes=False)
    warm, window = _seal_twice(server)
    assert server.found == [0] + [9] * 5
    assert not any(r[3] or r[4] for r in warm + window)
    # a sealer knows the inodes of its own window's calls only
    os.makedirs(tmp_path / "b")
    idle = _SealServer(tmp_path / "b", deletes=False, writes=False)
    warm, window = _seal_twice(idle)
    assert [r[4] for r in warm + window] == [False, True, True] * 2
    os.makedirs(tmp_path / "c")
    gone = _SealServer(tmp_path / "c", writes=False)
    warm, window = _seal_twice(gone)
    assert [r[4] for r in warm + window] == [False] + [True] * 5


def test_read_latency_is_over_all_reads_and_a_failed_one_is_the_worst():
    rec = [(i * 0.01, i * 0.01 + 0.004, 10, None, False) for i in range(99)]
    rec.append((0.5, 0.6, 0, "HttpError", False))
    out = run.read_end_to_end(rec, 0.0, 1.0)
    assert out["read_p50_ms"] == pytest.approx(4.0)
    assert out["read_p99_ms"] == pytest.approx(4.0)
    rec.append((0.6, 0.7, 0, "HttpError", False))
    assert run.read_end_to_end(rec, 0.0, 1.0)["read_p99_ms"] \
        == run.FAILED_READ_MS
    assert out["read_ops"] == pytest.approx(99.0)


def test_verdict():
    ok, shown = run.verdict({"a": (0, "<=", 0), "b": (3, ">=", 1)})
    assert ok and shown["a"]["ok"] and shown["b"]["limit"] == 1
    assert not run.verdict({"a": (1, "<=", 0)})[0]
    assert not run.verdict({"b": (0, ">=", 1)})[0]


# ---- the manifest against its files ----

@pytest.mark.parametrize("path", [
    os.path.join(REPO, "BENCHMARK.json"), os.path.join(HERE, "cells.json")])
def test_every_name_in_the_manifest_has_its_file(path):
    for cell in run.load_json(path)["workloads"]:
        manifest, c, config, traffic = run.load_cell(path, cell["name"])
        assert config["state"] in ("volumes", "sealed")
        assert traffic["op"] in ("seal", "read")
        assert set(traffic["warm"]) == {"encode", "apply"}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["per_layer"]:
        spec = run.metric_spec(m["name"])
        assert set(spec) == {"reader", "params"}
        importlib.import_module("benchmark.readers." + spec["reader"])
        assert m["moves"] in e2e


def test_expected_spans_are_the_shard_files_bytes(tmp_path):
    dat, paths = _write_volume(tmp_path, 3 * 40960 + 777, 1 << 20, 4096)
    spans = [(0, 4096), (4096 + 512, 1024), (3 * 4096, 4096)]
    code = reference.rs_code(10, 4, 1 << 20, 4096)
    want = reference.expected_spans(dat, spans, code)
    for (off, n), w in zip(spans, want):
        got = b"".join(open(p, "rb").read()[off:off + n] for p in paths)
        assert got == w
    with pytest.raises(ValueError):
        reference.expected_spans(dat, [(4000, 200)], code)
    with pytest.raises(ValueError):
        reference.expected_spans(dat, [(4 * 4096, 16)], code)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    from benchmark.readers import delta, ratio, roofline
    assert ratio.read({}, {"num": "a", "den": "b"}) is None
    assert ratio.read({"a": 1, "b": 0}, {"num": "a", "den": "b"}) is None
    assert ratio.read({"a": 1, "b": 4}, {"num": "a", "den": "b",
                                         "scale": 100,
                                         "complement": True}) == 75.0
    assert delta.read({"c": {"x": 0}}, {"fact": "c/x"}) == 0
    assert delta.read({}, {"fact": "c/x"}) is None
    p = {"bytes": "b", "seconds": "t/s", "peak": "p/hbm"}
    assert roofline.read({"b": 819e9, "p": {"hbm": 819e9}}, p) is None
    assert roofline.read({"b": 819e9, "t": {"s": 4.0},
                          "p": {"hbm": 819e9}}, p) == 25.0


def test_peaks_name_their_source():
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    for kind, row in peaks.items():
        assert row["hbm_bytes_per_s"] > 0 and row["source"], kind
