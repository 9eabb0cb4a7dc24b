"""Both readings of the check of ``seal.single.rs6-3`` (PR 27), at tiny
sizes on the CPU backend: the sound program seals RS(6,3) and is correct;
the control states RS(6,3) and asks for the server's default code, and is
not.  Their cells are in ``cells-rs6-3.json`` beside ``cells.json``.

    python -m pytest benchmark/tests/test_rs6_3.py -q
"""

import os

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells-rs6-3.json")
SEAL_LAYER_METRICS = {"jobs_per_dispatch.seal", "batch_wait_ms.seal",
                      "compiles_in_window.seal", "device_idle.seal"}


def drive(monkeypatch, cell, trace=False):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return run.run_cell(cell, seed=2_700_000_027, seconds=1.5, trace=trace,
                        manifest_path=CELLS,
                        volume_module="benchmark.served_volume",
                        require_platform=None)


def test_the_nine_shard_seal_is_correct(monkeypatch):
    r = drive(monkeypatch, "tiny.seal.single.rs6-3", trace=True)
    assert r["correct"], r["compared"]
    c = r["compared"]
    assert c["shard_files_differing"]["value"] == 0
    assert c["sampled_spans_differing"]["value"] == 0
    assert c["calls_failed"]["value"] == 0
    assert c["cpu_batches"]["value"] == 0
    assert c["mesh_dispatches_in_window"]["value"] >= 1
    assert SEAL_LAYER_METRICS <= set(r["metrics"])
    assert r["metrics"]["jobs_per_dispatch.seal"]["value"] == 1.0
    assert r["device"]["busy_s"] > 0


def test_the_control_that_seals_the_default_code_is_not(monkeypatch):
    """Stated: RS(6,3), nine files.  Sealed: ``{"code": "rs"}``, the
    server's RS(10,4)."""
    r = drive(monkeypatch, "tiny.control.seal.rs6-3-as-rs")
    assert not r["correct"]
    assert r["compared"]["shard_files_differing"]["value"] >= 1
    assert not r["compared"]["shard_files_differing"]["ok"]
