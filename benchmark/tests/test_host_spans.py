"""The reader that picks a program's share of the device trace by its
name, and the attribution of the device's idle gaps to the host's stages:
on hand-made intervals, and on a small trace recorded on the TPU v5e
(``recorded_spans_v5e.json.gz``: the first operations of a ``seal.single``
window under ``spans_volume``, written by ``scripts/spans_on_chip.sh
smoke``, PR 25)."""

import gzip
import json
import os

import pytest

from benchmark import host_spans, run, trace_reduce
from benchmark.readers import named_ratio

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


# ---- named_ratio ----

def test_named_ratio_sums_the_names_that_match():
    facts = {"trace": {"modules": {"jit_ec_encode(123)": 0.010,
                                   "jit_ec_encode(77)": 0.030,
                                   "jit_ec_apply(5)": 0.5}},
             "counters": {"batcher.jobs_total": 80, "recover.intervals": 0}}
    spec = run.metric_spec("encode_device_ms_per_job.seal")
    assert spec["reader"] == "named_ratio"
    assert named_ratio.read(facts, spec["params"]) == pytest.approx(0.5)
    apply_spec = run.metric_spec("apply_device_ms_per_rebuild.read")
    # no rebuilds in the window: nothing to divide by
    assert named_ratio.read(facts, apply_spec["params"]) is None
    facts["counters"]["recover.intervals"] = 250
    assert named_ratio.read(facts, apply_spec["params"]) \
        == pytest.approx(2.0)
    # no trace, or no module under the name
    assert named_ratio.read({"counters": facts["counters"]},
                            spec["params"]) is None
    facts["trace"]["modules"] = {"jit_one(886)": 1.0}
    assert named_ratio.read(facts, spec["params"]) is None


def test_named_ratio_on_the_trace_recorded_before_the_names():
    """PR 24's programs were both ``jit_one``: the parent commit reports
    neither metric, and says so by leaving it out."""
    with gzip.open(os.path.join(HERE, "recorded_trace_v5e.json.gz"),
                   "rt") as f:
        reduced = trace_reduce.reduce(json.load(f))
    assert all(name.startswith("jit_one(") for name in reduced["modules"])
    facts = {"trace": reduced,
             "counters": {"batcher.jobs_total": 177,
                          "recover.intervals": 10}}
    for metric in ("encode_device_ms_per_job.seal",
                   "apply_device_ms_per_rebuild.read"):
        spec = run.metric_spec(metric)
        assert named_ratio.read(facts, spec["params"]) is None
    by_name = {m["name"]: m
               for m in run.load_json(run.MANIFEST)["per_layer"]}
    assert "seal.single" in \
        by_name["encode_device_ms_per_job.seal"]["workloads"]
    assert by_name["apply_device_ms_per_rebuild.read"]["workloads"] == [
        "reads.degraded1"]


# ---- innermost stages, gaps ----

def test_innermost_gives_each_instant_to_the_deepest_stage():
    ev = [["dispatch", 10, 80], ["stack", 12, 8], ["launch", 20, 30],
          ["fetch", 50, 30], ["idle", 100, 50], ["late", 95, 1]]
    segs = host_spans.innermost(ev)
    assert segs == [(10, 12, "dispatch"), (12, 20, "stack"),
                    (20, 50, "launch"), (50, 80, "fetch"),
                    (80, 90, "dispatch"), (95, 96, "late"),
                    (100, 150, "idle")]
    assert host_spans.self_seconds(ev) == {
        "dispatch": 12e-9, "stack": 8e-9, "launch": 30e-9, "fetch": 30e-9,
        "late": 1e-9, "idle": 50e-9}
    # a child that outlives its parent by clock jitter is cut to it
    assert host_spans.innermost([["p", 0, 10], ["c", 5, 10]]) == [
        (0, 5, "p"), (5, 10, "c")]


def test_idle_gaps_are_the_windows_complement():
    ops = [["a", 10, 5], ["b", 12, 10], ["c", 40, 5]]
    assert host_spans.idle_gaps(ops) == [(22, 40)]
    assert host_spans.idle_gaps(ops, (0, 60)) == [(0, 10), (22, 40),
                                                  (45, 60)]
    assert host_spans.idle_gaps([], (0, 60)) == [(0, 60)]
    assert host_spans.idle_gaps([]) == []


def _host(dispatcher: list, *requests: list) -> dict:
    return {"lines": [{"name": f"python3/{i}", "events": ev}
                      for i, ev in enumerate((dispatcher,) + requests)]}


def test_gaps_go_to_the_dispatchers_stage_then_to_requests_in_flight():
    # device: two programs of 1 ms, at 20 ms and at 60 ms
    ops = [["fusion", 20 * MS, MS], ["fusion", 60 * MS, MS]]
    dispatcher = [
        # (0-5: nothing recorded) idle 5-10, hold 10-15, dispatch 15-22
        ["ec.batch.idle", 5 * MS, 5 * MS],
        ["ec.batch.hold", 10 * MS, 5 * MS],
        ["ec.batch.dispatch", 15 * MS, 7 * MS],
        ["ec.batch.stack", 15 * MS, 2 * MS],
        ["ec.mesh.launch", 17 * MS, 2 * MS],
        ["ec.mesh.fetch", 19 * MS, 3 * MS],
        ["ec.batch.idle", 22 * MS, 28 * MS],
        ["ec.batch.hold", 50 * MS, 5 * MS],
        ["ec.batch.dispatch", 55 * MS, 7 * MS],
        ["ec.mesh.fetch", 58 * MS, 4 * MS]]
    reader = [["volume.read", 25 * MS, 20 * MS],
              ["store.ec.locate", 30 * MS, 10 * MS]]
    sealer = [["ec.pipeline.write", 20 * MS, 10 * MS]]
    out = host_spans.attribute_gaps(ops, _host(dispatcher, reader, sealer),
                                    window=(0, 62 * MS))
    by = out["by_dispatcher_stage"]
    assert out["gaps"] == 3
    assert out["idle_s"] == pytest.approx(0.060)
    assert by[host_spans.NO_STAGE] == pytest.approx(0.005)    # 0-5
    # 5-10 and 22-50; the program at 20-21 ran inside fetch
    assert by["ec.batch.idle"] == pytest.approx(0.033)
    assert by["ec.batch.hold"] == pytest.approx(0.010)
    assert by["ec.batch.stack"] == pytest.approx(0.002)
    assert by["ec.mesh.launch"] == pytest.approx(0.002)
    # 19-20 and 21-22, then 58-60 and 61-62
    assert by["ec.mesh.fetch"] == pytest.approx(0.005)
    assert by["ec.batch.dispatch"] == pytest.approx(0.003)    # 55-58
    assert sum(by.values()) == pytest.approx(out["idle_s"])
    assert out["under_a_stage_share"] == pytest.approx(55 / 60)
    assert out["under_idle_s"] == pytest.approx(0.033)
    req = out["under_idle_by_request_stage"]
    # innermost per thread; threads overlap, so these may exceed the idle
    assert req == {"volume.read": pytest.approx(0.010),
                   "store.ec.locate": pytest.approx(0.010),
                   "ec.pipeline.write": pytest.approx(0.008)}
    assert out["dispatcher_self_s"]["ec.batch.dispatch"] \
        == pytest.approx(0.003)
    assert out["longest_gaps"][0][0] == pytest.approx(0.039)   # 21-60
    assert out["longest_gaps"][0][1][0] == ("ec.batch.idle",
                                            pytest.approx(0.028))


def test_a_program_without_stages_is_said_to_have_none():
    ops = [["fusion", 0, MS]]
    assert host_spans.attribute_gaps(ops, {"lines": []}) is None
    assert host_spans.attribute_gaps(
        ops, _host([["ec.batch.submit", 0, 5]])) is None


def test_the_recorded_v5e_spans():
    with gzip.open(os.path.join(HERE, "recorded_spans_v5e.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    ops, host = rec["device_events"], rec["host"]
    n_events = len(ops) + sum(len(ln["events"]) for ln in host["lines"])
    assert 100 <= n_events <= 2000
    assert len(ops) == 120 and n_events == 387
    out = host_spans.attribute_gaps(ops, host)
    by = out["by_dispatcher_stage"]
    assert out["gaps"] == 99
    assert out["idle_s"] == pytest.approx(0.19939573, abs=1e-9)
    assert sum(by.values()) == pytest.approx(out["idle_s"])
    # a lone job waits out the window before every dispatch: 16 jobs
    assert by["ec.batch.hold"] == pytest.approx(0.089814209, abs=1e-9)
    assert by["ec.mesh.fetch"] == pytest.approx(0.054207865, abs=1e-9)
    assert by["ec.batch.stack"] == pytest.approx(0.024473445, abs=1e-9)
    assert by["ec.batch.idle"] == pytest.approx(0.019001248, abs=1e-9)
    # the dispatcher's first stage began before the trace did
    assert by[host_spans.NO_STAGE] == pytest.approx(0.001849027, abs=1e-9)
    assert out["under_a_stage_share"] == pytest.approx(0.99072685)
    assert out["under_idle_s"] == pytest.approx(by["ec.batch.idle"])
    assert max(out["under_idle_by_request_stage"].items(),
               key=lambda kv: kv[1]) == (
        "ec.batch.result", pytest.approx(0.015502037, abs=1e-9))
    # the same gaps with the window opened before the first recorded
    # stage: that stretch lies under no stage
    first = min(e[1] for ln in host["lines"] for e in ln["events"])
    last = max(s + d for _n, s, d in ops)
    early = host_spans.attribute_gaps(ops, host,
                                      window=(first - 50 * MS, last))
    assert early["by_dispatcher_stage"][host_spans.NO_STAGE] \
        >= 0.050 - 1e-9
