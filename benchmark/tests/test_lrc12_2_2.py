"""A code family on the device path (PR 35): the tiny twin of the cell
``reads.degraded1.lrc12-2-2`` (``cells-lrc12-2-2.json``), and the tiny
LRC(10,2,2) cells of ``cells-families.json`` as they read now that
``BatchCoder.for_scheme`` hands a family a device coder: the wrapper
warms the family's own programs (no ``unwarmed``), a seal and a local
repair dispatch to the mesh, and the cells are ``correct``.  (The three
expectations of ``test_families.py`` that pinned the host coder,
``unwarmed`` and ``mesh_dispatches_in_window`` 0, describe PR 34's tree:
PERF.md section 7.)

    python -m pytest benchmark/tests/test_lrc12_2_2.py -q
"""

import json
import os

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
TWIN = os.path.join(HERE, "cells-lrc12-2-2.json")
FAMILIES = os.path.join(HERE, "cells-families.json")
ROOT = os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")
CELL = "reads.degraded1.lrc12-2-2"


def drive(monkeypatch, manifest, cell, wrapper="benchmark.served_volume",
          trace=False):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return run.run_cell(cell, seed=3_500_000_035, seconds=1.5, trace=trace,
                        manifest_path=manifest, volume_module=wrapper,
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
    assert (cell["traffic"], cell["chips"]) == ("degraded1", 1)
    assert config["code"]["family"] == "lrc"
    assert config["generate_body"] == {"code": "lrc-12-2-2"}
    assert traffic["workers"] == 16
    listed = {m["name"] for key in ("end_to_end", "per_layer")
              for m in root[key] if CELL in m.get("workloads", [])}
    assert listed == {
        "read_p50_ms", "read_p99_ms", "read_ops", "degraded_share.read",
        "jobs_per_dispatch.read", "batch_wait_ms.read",
        "compiles_in_window.read", "device_ms_per_reconstruct.read",
        "apply_device_ms_per_rebuild.read", "device_idle.read",
        "local_repair_share.read", "own_program_share.read"}
    for name in ("local_repair_share.read", "own_program_share.read"):
        (m,) = [m for m in root["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "read_p99_ms"
        assert run.metric_spec(name)["reader"] in ("ratio", "named_ratio")


def test_the_tiny_twin_is_repaired_from_its_group_on_the_device(
        monkeypatch, capfd):
    r = drive(monkeypatch, TWIN, "tiny.reads.degraded.lrc12-2-2", trace=True)
    assert r["correct"] and not failed(r), r["compared"]
    c = r["compared"]
    assert c["reads_wrong"]["value"] == 0 and c["reads_failed"]["value"] == 0
    assert c["intervals_reconstructed"]["value"] >= 1
    assert c["mesh_dispatches_in_window"]["value"] >= 1
    m = r["metrics"]
    assert m["compiles_in_window.read"]["value"] == 0
    assert m["local_repair_share.read"]["value"] == 100
    # read from the device plane's module names, which a CPU trace has
    # not: left out here, and not an error (on the chip: 100)
    assert "own_program_share.read" not in m
    # one read in twelve crosses shard 3 of twelve; the cache serves some
    assert 4 < m["degraded_share.read"]["value"] <= 9
    err = capfd.readouterr().err
    counters = window_counters(err)
    assert counters["recover.by.local"] == counters["recover.intervals"] >= 1
    assert counters["recover.by.global"] == counters["recover.by.generic"] == 0
    assert " of lrc-12-2-2: [['encode', 1, 1048576," in err
    assert "['apply', 4, 262144," in err and "UNWARMED" not in err
    assert "ec.encode -code 'lrc-12-2-2'" in err


@pytest.mark.parametrize("fault", ["xor_rebuild", "altered_read"])
def test_a_broken_local_repair_is_not_correct(monkeypatch, fault):
    """The local parity's coefficients are a split RS row, not ones: the
    XOR of the six survivors is not the lost block."""
    r = drive(monkeypatch, TWIN, "tiny.reads.degraded.lrc12-2-2",
              f"benchmark.tests.faulty_volume:{fault}")
    assert not r["correct"]
    assert "reads_failed" in failed(r), r["compared"]


@pytest.mark.parametrize("cell", ["tiny.seal.single.lrc",
                                  "tiny.reads.degraded.lrc"])
def test_the_lrc10_2_2_rehearsals_run_on_the_device_now(monkeypatch, capfd,
                                                        cell):
    r = drive(monkeypatch, FAMILIES, cell)
    assert r["correct"] and not failed(r), r["compared"]
    assert r["compared"]["mesh_dispatches_in_window"]["value"] >= 1
    err = capfd.readouterr().err
    assert " of lrc-10-2-2: [[" in err and "UNWARMED" not in err
