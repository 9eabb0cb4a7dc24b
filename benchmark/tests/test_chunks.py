"""The cell ``reads.degraded1.chunks`` (PR 31) at tiny counts and the real
1 MiB block on the CPU backend: the sound rehearsal is correct and reports
the read cells' per-layer metrics (``.read``), the control is not;
``kernel_roofline.read``'s reader; and the layout that metric's bytes rest
on, from the fill's own order.  The cell is in BENCHMARK.json under
``read_ops`` and ``setup_s`` alone (its median and tail spread too widely
over six runs to be admitted: PERF.md section 4); ``cells-chunks.json``
holds its rehearsal, and the cell itself under every read metric.

    python -m pytest benchmark/tests/test_chunks.py -q
"""

import gzip
import json
import os

import pytest

from benchmark import loadgen, run, trace_reduce
from benchmark.readers import counted_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELLS = os.path.join(HERE, "cells-chunks.json")
CELL = "reads.degraded1.chunks"
MIB = 1 << 20
# what a CPU rehearsal can read: its trace names no program and
# peaks.json has no row for the CPU, so the two metrics that select by
# the program's name stay out, as the readers' contract says
READ_LAYER_METRICS = {"degraded_share.read", "jobs_per_dispatch.read",
                      "batch_wait_ms.read", "compiles_in_window.read",
                      "device_ms_per_reconstruct.read", "device_idle.read"}


def drive(monkeypatch, wrapper, trace=False):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return run.run_cell("tiny.reads.degraded.chunks", seed=3_100_000_031,
                        seconds=2.0, trace=trace, manifest_path=CELLS,
                        volume_module=wrapper, require_platform=None)


def test_the_sound_rehearsal_is_correct(monkeypatch):
    r = drive(monkeypatch, "benchmark.served_volume", trace=True)
    assert r["correct"], r["compared"]
    c = r["compared"]
    assert c["reads_wrong"]["value"] == 0 and c["reads_failed"]["value"] == 0
    assert c["intervals_reconstructed"]["value"] >= 1
    assert c["cpu_batches"]["value"] == 0
    assert c["coder_fallbacks"]["value"] == 0
    assert c["warmup_operations_failed"]["value"] == 0
    assert c["mesh_dispatches_in_window"]["value"] >= 1
    assert READ_LAYER_METRICS <= set(r["metrics"])
    assert r["metrics"]["compiles_in_window.read"]["value"] == 0
    # 8 of the 24 chunks hold a block of shard 3; the cache serves some
    assert 5 < r["metrics"]["degraded_share.read"]["value"] <= 34
    assert 1 <= r["metrics"]["jobs_per_dispatch.read"]["value"] <= 4
    assert r["device"]["busy_s"] > 0


@pytest.mark.parametrize("fault", ["xor_rebuild", "altered_read"])
def test_the_control_and_the_fault_are_not(monkeypatch, fault):
    r = drive(monkeypatch, f"benchmark.tests.faulty_volume:{fault}")
    assert not r["correct"]
    assert not r["compared"]["reads_failed"]["ok"], r["compared"]


def test_the_cell_is_in_the_manifest_with_its_files():
    root, cell, config, traffic = run.load_cell(run.MANIFEST, CELL)
    assert cell == root["workloads"][-1]
    assert cell["chips"] == 1 and config["lost_shards"] == [3]
    assert traffic["workers"] == 20 and traffic["keys"] == "uniform"
    assert traffic["fill"] == [{"bytes": 4 * MIB, "count": 204},
                               {"bytes": 2 * MIB, "count": 102}]
    # judged on read_ops (and setup_s, which has no list): the median and
    # the tail do not hold still enough at 10 s (PERF.md section 4), and a
    # per-layer metric lists only cells that report what it moves, so the
    # seven `.read` entries, which move read_p99_ms, do not list it
    assert [m["name"] for m in root["end_to_end"]
            if run.metric_applies(m, CELL, set())] == ["read_ops", "setup_s"]
    roofline = root["per_layer"][-1]
    assert [m["name"] for m in root["per_layer"]
            if run.metric_applies(m, CELL, {"read_ops", "setup_s"})
            ] == [roofline["name"]] == ["kernel_roofline.read"]
    assert roofline["moves"] == "read_ops"
    assert roofline["workloads"] == [CELL]      # its bytes hold nowhere else
    # beside it the same cell under every read metric, for a chip run
    _m, beside, config2, traffic2 = run.load_cell(CELLS, CELL)
    assert (config2, traffic2) == (config, traffic)
    assert {k: cell[k] for k in beside} == beside
    f4 = run.load_json(os.path.join(BENCH, "configs", "f4-holder-down.json"))
    for key in ("code", "volume_size_limit_mb", "state", "lost_shards",
                "generate_body", "guarantees"):
        assert config[key] == f4[key], key
    assert config["servers"]["env"] == f4["servers"]["env"]
    entry = root["configs"][-1]
    assert entry["name"] == cell["config"]
    assert entry["reduced"] == list(config["reduced"])
    assert set(config["assumed"]) == {"one_volume", "mib",
                                      "uniform_over_chunks"}
    spec = run.metric_spec("kernel_roofline.read")
    assert spec["reader"] == "counted_roofline"
    assert run.metric_spec("kernel_roofline.seal")["reader"] == "roofline"


def test_counted_roofline_on_the_recorded_v5e_trace():
    """The recorded trace's three programs stand for the apply program's
    here (the reader selects by prefix): 100 rebuilt intervals of
    11,534,336 B at 819 GB/s over the programs' 0.0949 s."""
    with gzip.open(os.path.join(HERE, "recorded_trace_v5e.json.gz"),
                   "rt") as f:
        reduced = trace_reduce.reduce(json.load(f))
    params = dict(run.metric_spec("kernel_roofline.read")["params"])
    assert params["bytes_per_count"] == 11 * MIB
    facts = {"counters": {"recover.intervals": 100}, "trace": reduced,
             "peaks": {"hbm_bytes_per_s": 819e9}}
    assert counted_roofline.read(facts, params) is None    # no such name
    params["prefix"] = "jit_"
    want = 100.0 * (100 * 11 * MIB / 819e9) / reduced["modules_s"]
    assert counted_roofline.read(facts, params) == pytest.approx(want)
    assert 1.0 < want < 2.0


@pytest.mark.parametrize("facts", [
    {},
    {"counters": {"recover.intervals": 0},
     "trace": {"modules": {"jit_ec_apply_rs_10_4": 1.0}},
     "peaks": {"hbm_bytes_per_s": 819e9}},
    {"counters": {"recover.intervals": 5}, "trace": {"modules": {}},
     "peaks": {"hbm_bytes_per_s": 819e9}},
    {"counters": {"recover.intervals": 5},
     "trace": {"modules": {"jit_ec_encode_rs_10_4": 1.0}},
     "peaks": {"hbm_bytes_per_s": 819e9}},
    {"counters": {"recover.intervals": 5},
     "trace": {"modules": {"jit_ec_apply_rs_10_4": 1.0}}, "peaks": {}},
])
def test_counted_roofline_reads_nothing_where_a_part_is_missing(facts):
    params = run.metric_spec("kernel_roofline.read")["params"]
    assert counted_roofline.read(facts, params) is None


def test_counted_roofline_arithmetic():
    params = run.metric_spec("kernel_roofline.read")["params"]
    facts = {"counters": {"recover.intervals": 1000},
             "trace": {"modules": {"jit_ec_apply_rs_10_4": 0.75,
                                   "jit_ec_apply_rs_6_3": 0.25,
                                   "jit_ec_encode_rs_10_4": 9.0}},
             "peaks": {"hbm_bytes_per_s": 819e9}}
    assert counted_roofline.read(facts, params) == pytest.approx(
        100 * (1000 * 11534336 / 819e9) / 1.0)


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_every_lost_block_is_whole_inside_one_needle(seed):
    """From ``loadgen.fill``'s own order, in plain arithmetic: a record is
    16 B of header, 4 of data size, the data, 1 of flags, 4 of checksum
    and 8 of timestamp, padded to 8, after an 8 B superblock."""
    _m, _c, config, traffic = run.load_cell(CELLS, CELL)
    (lost,) = config["lost_shards"]
    k = config["code"]["data_shards"]
    block = config["code"]["small_block_bytes"]
    sizes = [c["bytes"] for c in traffic["fill"] for _ in range(c["count"])]
    plan = [int(s) for s in loadgen.rng_for(seed, 1, 1).permutation(sizes)]
    offset, rebuilt, touched = 8, {}, []
    for pos, size in enumerate(plan):
        length = -(-(16 + 4 + size + 1 + 4 + 8) // 8) * 8
        first, last = offset // block, (offset + length - 1) // block
        touched.append(last - first + 1)
        for b in range(first, last + 1):
            if b % k == lost:
                rebuilt.setdefault(pos, []).append(
                    min(offset + length, (b + 1) * block)
                    - max(offset, b * block))
        offset += length
    assert offset // block == 1020 and set(touched) == {3, 5}
    assert len(rebuilt) * 3 == len(plan) == 306
    assert all(v == [block] for v in rebuilt.values())
