"""A regenerating code on the device path: the tiny twin of the cell
``reads.degraded1.chunks.clay10-4`` (``cells-clay10-4.json``).  The
wrapper warms the family's programs from the chunk traffic's own warm
list (the k-wide apply of whole blocks compiles the repair program on
256 KiB runs beside it), ``prepare()`` seals under ``clay-10-4-13``,
every lost block is rebuilt by the repair plan on the device, and the
cell is ``correct``; a broken repair is not.

    python -m pytest benchmark/tests/test_clay10_4.py -q
"""

import json
import os

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
TWIN = os.path.join(HERE, "cells-clay10-4.json")
ROOT = os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")
CELL = "reads.degraded1.chunks.clay10-4"
TINY = "tiny.reads.degraded.chunks.clay10-4"
NEW = ("clay_repair_share.read", "clay_program_share.read",
       "clay_roofline.read")


def drive(monkeypatch, wrapper="benchmark.served_volume", trace=False):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return run.run_cell(TINY, seed=4_200_000_042, seconds=1.5, trace=trace,
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
    assert config["code"]["family"] == "clay"
    assert (config["code"]["helpers"], config["code"]["substripes"]) \
        == (13, 256)
    assert config["generate_body"] == {"code": "clay-10-4-13"}
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
    from benchmark import bytes_model_clay
    spec = run.metric_spec("clay_roofline.read")
    assert spec["params"]["bytes_per_count"] == 4_456_448 \
        == bytes_model_clay.repair_bytes(config["code"], 3)
    (entry,) = [c for c in root["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == list(config["reduced"])
    assert len(entry["source"]) <= 200


def test_the_tiny_twin_is_rebuilt_by_the_repair_plan(monkeypatch, capfd):
    r = drive(monkeypatch, trace=True)
    assert r["correct"] and not failed(r), r["compared"]
    c = r["compared"]
    assert c["reads_wrong"]["value"] == 0 and c["reads_failed"]["value"] == 0
    assert c["intervals_reconstructed"]["value"] >= 1
    assert c["mesh_dispatches_in_window"]["value"] >= 1
    m = r["metrics"]
    assert m["clay_repair_share.read"]["value"] == 100
    # read from the device plane's module names, which a CPU trace has
    # not: left out here, and not an error
    assert "clay_program_share.read" not in m
    assert "clay_roofline.read" not in m
    err = capfd.readouterr().err
    counters = window_counters(err)
    assert counters["recover.by.repair_planes"] \
        == counters["recover.intervals"]
    assert counters["recover.by.planes_partial"] == 0
    assert counters["recover.by.layered"] == 0
    assert counters["wrapper.compiles"] == 0
    assert " of clay-10-4-13: [['encode', 1, 1048576," in err
    assert "UNWARMED" not in err
    assert "ec.encode -code 'clay-10-4-13'" in err


@pytest.mark.parametrize("fault", ["xor_rebuild", "altered_read"])
def test_a_broken_clay_repair_is_not_correct(monkeypatch, fault):
    r = drive(monkeypatch, f"benchmark.tests.faulty_volume:{fault}")
    assert not r["correct"]
    assert "reads_failed" in failed(r), r["compared"]
