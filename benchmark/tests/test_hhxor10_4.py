"""A piggybacked code on the device path: the tiny twin of the cell
``reads.degraded1.chunks.hhxor10-4`` (``cells-hhxor10-4.json``).  The
wrapper warms the family's programs from the chunk traffic's own warm
list (the k-wide apply of whole blocks compiles the piggyback widths on
half-blocks beside it), ``prepare()`` seals under ``hhxor-10-4``, every
lost block is rebuilt by the piggyback plan on the device, and the cell
is ``correct``; a broken repair is not.

    python -m pytest benchmark/tests/test_hhxor10_4.py -q
"""

import json
import os

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
TWIN = os.path.join(HERE, "cells-hhxor10-4.json")
ROOT = os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")
CELL = "reads.degraded1.chunks.hhxor10-4"
TINY = "tiny.reads.degraded.chunks.hhxor10-4"
NEW = ("piggyback_repair_share.read", "hhxor_program_share.read",
       "hhxor_roofline.read")


def drive(monkeypatch, wrapper="benchmark.served_volume", trace=False):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return run.run_cell(TINY, seed=3_900_000_039, seconds=1.5, trace=trace,
                        manifest_path=TWIN, volume_module=wrapper,
                        require_platform=None)


def failed(result):
    return {k for k, v in result["compared"].items() if not v["ok"]}


def window_counters(err: str) -> dict:
    line = next(ln for ln in err.splitlines()
                if ln.startswith("[window] opened"))
    return json.loads(line[line.index("counters ") + 9:])


def test_the_cell_is_in_the_root_manifest_under_its_lists():
    with open(ROOT) as f:
        root = json.load(f)
    _m, cell, config, traffic = run.load_cell(ROOT, CELL)
    _m, _c, chunks, chunk_traffic = run.load_cell(ROOT,
                                                  "reads.degraded1.chunks")
    assert (cell["traffic"], cell["chips"]) == ("degraded1.chunks", 1)
    assert traffic == chunk_traffic
    assert config["code"]["family"] == "hhxor"
    assert config["generate_body"] == {"code": "hhxor-10-4"}
    # only the code differs from the chunk configuration
    for key in ("volume_size_limit_mb", "servers", "state", "lost_shards",
                "reduced"):
        assert config[key] == chunks[key], key
    listed = {m["name"] for key in ("end_to_end", "per_layer")
              for m in root[key] if CELL in m.get("workloads", [])}
    assert listed == {"read_ops", *NEW}
    for name in NEW:
        (m,) = [m for m in root["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "read_ops"
    spec = run.metric_spec("hhxor_roofline.read")
    assert spec["params"]["bytes_per_count"] == 7_864_320
    (entry,) = [c for c in root["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == list(config["reduced"])
    assert len(entry["source"]) <= 200


def test_the_tiny_twin_is_rebuilt_by_the_piggyback_plan(monkeypatch, capfd):
    r = drive(monkeypatch, trace=True)
    assert r["correct"] and not failed(r), r["compared"]
    c = r["compared"]
    assert c["reads_wrong"]["value"] == 0 and c["reads_failed"]["value"] == 0
    assert c["intervals_reconstructed"]["value"] >= 1
    assert c["mesh_dispatches_in_window"]["value"] >= 1
    m = r["metrics"]
    assert m["piggyback_repair_share.read"]["value"] == 100
    # read from the device plane's module names, which a CPU trace has
    # not: left out here, and not an error
    assert "hhxor_program_share.read" not in m
    assert "hhxor_roofline.read" not in m
    err = capfd.readouterr().err
    counters = window_counters(err)
    assert counters["recover.by.piggyback"] == counters["recover.intervals"]
    assert counters["recover.by.substripe"] == 0
    assert counters["wrapper.compiles"] == 0
    assert " of hhxor-10-4: [['encode', 1, 1048576," in err
    assert "UNWARMED" not in err
    assert "ec.encode -code 'hhxor-10-4'" in err


@pytest.mark.parametrize("fault", ["xor_rebuild", "altered_read"])
def test_a_broken_piggyback_repair_is_not_correct(monkeypatch, fault):
    r = drive(monkeypatch, f"benchmark.tests.faulty_volume:{fault}")
    assert not r["correct"]
    assert "reads_failed" in failed(r), r["compared"]
