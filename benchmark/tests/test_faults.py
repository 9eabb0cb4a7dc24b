"""The rest of a run, driven without the look for a chip, with the timed
path broken underneath: ``correct`` has to come out false, once for each
fault a cell can have; and true for the sound program.  Small sizes, the
CPU backend asked for by name; a few seconds each.

    python -m pytest benchmark/tests/test_faults.py -q
"""

import os

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells.json")


def drive(monkeypatch, cell, wrapper, trace=False):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return run.run_cell(cell, seed=2_500_000_011, seconds=1.5, trace=trace,
                        manifest_path=CELLS, volume_module=wrapper,
                        require_platform=None)


def failed(result):
    return {k for k, v in result["compared"].items() if not v["ok"]}


@pytest.mark.parametrize("cell", ["tiny.seal.single", "tiny.seal.storm",
                                  "tiny.reads.degraded"])
def test_the_sound_program_is_correct(monkeypatch, cell):
    r = drive(monkeypatch, cell, "benchmark.served_volume", trace=True)
    assert r["correct"] and not failed(r), r["compared"]
    assert r["device"]["busy_s"] > 0 and r["breakdown"]["device_ops"]
    assert r["metrics"]


@pytest.mark.parametrize("cell,fault,number", [
    # a step that returns its state unchanged
    ("tiny.seal.single", "unchanged", "calls_leaving_stale_files"),
    # half of the batch left out
    ("tiny.seal.storm", "half_batch", "shard_files_differing"),
    ("tiny.seal.single", "half_batch", "shard_files_differing"),
    # ... in two calls early in the window, the last call sound
    ("tiny.seal.single", "half_batch_early", "sampled_spans_differing"),
    # an answer altered where it is produced
    ("tiny.seal.storm", "altered_seal", "shard_files_differing"),
    ("tiny.reads.degraded", "altered_read", "reads_failed"),
    # the read cell's control: XOR in place of GF(2^8) arithmetic
    ("tiny.reads.degraded", "xor_rebuild", "reads_failed"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, cell, fault,
                                            number):
    r = drive(monkeypatch, cell, f"benchmark.tests.faulty_volume:{fault}")
    assert not r["correct"]
    assert number in failed(r), r["compared"]


def test_the_seal_control_is_not_correct(monkeypatch):
    """The program's own LRC(10,2,2) family in place of RS(10,4)."""
    r = drive(monkeypatch, "tiny.control.seal.lrc", "benchmark.served_volume")
    assert not r["correct"]
    assert r["compared"]["shard_files_differing"]["value"] >= 2


def test_no_result_without_the_chip(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", "tiny.seal.single", "--seed", "1",
                   "--seconds", "1"], manifest_path=CELLS)
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
