"""The host plane's stages as facts (``benchmark/stage_facts.py``, PR 37):
the three metrics of the HTTP edge on hand-made stages, what a program
without ``http.`` stages reads (nothing), the extraction of a stage's
metadata from a real ``.xplane.pb``, and that a ``host`` key beside the
device planes changes nothing the benchmark already reads."""

import gzip
import json
import os
import subprocess
import sys

import pytest

from benchmark import host_spans, run, stage_facts, trace_reduce
from benchmark.readers import named_ratio

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
MS = 1_000_000


def _host():
    """Two requests on one worker, one on another, a selector turn; the
    first of each slice waited for its worker (queue_us), the pipelined
    one did not."""
    worker_a = [
        ["http.request", 0, 10 * MS, {"queue_us": 1500}],
        ["http.head", 0, 1 * MS],
        ["volume.read", 2 * MS, 6 * MS],
        ["store.ec.read_interval", 3 * MS, 2 * MS],
        ["http.flush", 8 * MS, 1 * MS],
        ["http.request", 10 * MS, 4 * MS, {"queue_us": 0}],
        ["http.head", 10 * MS, 1 * MS],
        ["volume.read", 11 * MS, 2 * MS],
        ["http.flush", 13 * MS, 1 * MS],
    ]
    worker_b = [
        ["http.request", 20 * MS, 6 * MS, {"queue_us": 2500}],
        ["http.head", 20 * MS, 1 * MS],
        ["volume.read", 21 * MS, 4 * MS],
        ["http.flush", 25 * MS, 1 * MS],
    ]
    selector = [["http.selector", 19 * MS, 1 * MS]]
    return {"lines": [{"name": "python/3", "events": worker_a},
                      {"name": "python/4", "events": worker_b},
                      {"name": "python/5", "events": selector}]}


def test_facts_are_self_total_count_and_meta_by_stage():
    f = stage_facts.facts(_host())
    assert f["count"]["http.request"] == 3
    assert f["count"]["http.selector"] == 1
    assert f["total_s"]["http.request"] == pytest.approx(0.020)
    # a request's self time: its length less head, handler and flush
    assert f["self_s"]["http.request"] == pytest.approx(
        (10 - 1 - 6 - 1 + 4 - 1 - 2 - 1 + 6 - 1 - 4 - 1) / 1e3)
    assert f["self_s"]["volume.read"] == pytest.approx((4 + 2 + 4) / 1e3)
    assert f["meta"] == {"http.request": {"queue_us": 4000}}
    # host_spans reads three-element events: the metadata stays out
    assert all(len(ev) == 3 for ln in stage_facts.plain(_host())["lines"]
               for ev in ln["events"])


def test_the_three_edge_metrics_on_a_host_fact():
    m = stage_facts.edge_metrics(stage_facts.facts(_host()))
    assert m["edge_handoff_ms.read"] == pytest.approx(4000 / 1e3 / 3)
    assert m["request_ms.read"] == pytest.approx(20 / 3)
    # heads + flushes + the requests' own time + the selector's turn
    assert m["edge_self_ms.read"] == pytest.approx((3 + 3 + 2 + 1) / 3)
    assert m["edge_self_ms.read"] <= m["request_ms.read"]
    for name, params in stage_facts.EDGE_METRICS.items():
        assert named_ratio.read({"host": stage_facts.facts(_host())},
                                params) == m[name]


@pytest.mark.parametrize("host", [
    {"lines": []},
    {"lines": [{"name": "python/1", "events": [
        ["volume.read", 0, MS], ["store.ec.locate", 0, MS // 2]]}]},
], ids=["no stages", "stages below the edge only"])
def test_a_program_without_edge_stages_reads_none(host):
    assert stage_facts.edge_metrics(stage_facts.facts(host)) == {
        name: None for name in stage_facts.EDGE_METRICS}
    # and a run whose facts have no host key at all (the parent's)
    for params in stage_facts.EDGE_METRICS.values():
        assert named_ratio.read({"counters": {}}, params) is None


def _recorded(name: str) -> dict:
    with gzip.open(os.path.join(HERE, name), "rt") as f:
        return json.load(f)


def _facts(reduced: dict) -> dict:
    return {"op": "read", "traced_s": 10.0, "ops_ok": 1000,
            "peaks": {"hbm_bytes_per_s": 819e9}, "min_bytes": 10 ** 9,
            "counters": {"batcher.jobs_total": 177, "batcher.mesh_batches":
                         120, "batcher.wait_count": 177,
                         "batcher.wait_sum_s": 0.4, "wrapper.compiles": 0,
                         "recover.intervals": 90, "recover.by.local": 90},
            "trace": reduced}


@pytest.mark.parametrize("metric", [
    m["name"] for m in run.load_json(run.MANIFEST)["per_layer"]])
def test_a_host_key_changes_nothing_the_benchmark_reads(metric):
    """What the next ``benchmark`` PR wires (``extracted["host"]``,
    ``facts["host"]``) leaves the reduction and every accepted metric as
    they read, on the recorded v5e traces."""
    spec = run.metric_spec(metric)
    reader = __import__("benchmark.readers." + spec["reader"],
                        fromlist=["read"])
    spans = _recorded("recorded_spans_v5e.json.gz")
    for extracted in (_recorded("recorded_trace_v5e.json.gz"),
                      {"planes": [{"name": "/device:TPU:0", "device": True,
                                   "lines": [{"name": "XLA Ops", "events":
                                              spans["device_events"]}]}]}):
        before = trace_reduce.reduce(extracted)
        after = trace_reduce.reduce({**extracted, "host": spans["host"]})
        assert after == before
        facts = _facts(before)
        value = reader.read(facts, spec.get("params", {}))
        facts["host"] = stage_facts.facts(spans["host"])
        assert reader.read(facts, spec.get("params", {})) == value


def test_idle_by_host_stage_adds_the_requests_in_the_longest_gaps():
    spans = _recorded("recorded_spans_v5e.json.gz")
    got = stage_facts.idle_by_host_stage(spans["device_events"],
                                         spans["host"], top=3)
    want = host_spans.attribute_gaps(spans["device_events"], spans["host"])
    assert {k: v for k, v in got.items()
            if k != "longest_with_requests"} == want
    longest = got["longest_with_requests"]
    assert [x["s"] for x in longest] == [g[0] for g in want["longest_gaps"]
                                         ][:3]
    assert all(x["began"] >= 0 for x in longest)
    line = stage_facts.idle_line(spans["device_events"], spans["host"])
    assert line.startswith("[trace] idle by host stage: ")
    assert stage_facts.idle_by_host_stage(
        spans["device_events"], {"lines": []}) is None


def test_extract_keeps_a_stages_metadata_and_the_planes_stay_as_they_were(
        tmp_path):
    """A real ``.xplane.pb`` (CPU backend, in a child: the profiler is
    one per process): the edge's stages with ``queue_us`` through
    ``tracing.stage`` and the jax annotator; ``trace_extract`` keeps
    none of them in ``planes``."""
    code = f"""
import json, sys, threading
sys.path.insert(0, {REPO!r})
import jax
from seaweedfs_tpu.utils import tracing
from benchmark import stage_facts, trace_extract
tracing.set_annotator(jax.profiler.TraceAnnotation)
assert not tracing.recording()
jax.profiler.start_trace({str(tmp_path)!r})
assert tracing.recording()
def worker():
    for q in (1500, 0):
        with tracing.stage("http.request", {{"queue_us": q}}):
            with tracing.stage("http.head"):
                pass
            with tracing.stage("http.flush"):
                pass
t = threading.Thread(target=worker); t.start(); t.join()
jax.numpy.ones(8).sum().block_until_ready()
jax.profiler.stop_trace()
host = stage_facts.extract({str(tmp_path)!r})
planes = trace_extract.extract({str(tmp_path)!r})["planes"]
print(json.dumps({{"host": host, "planes": planes}}))
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    f = stage_facts.facts(out["host"])
    assert f["count"] == {"http.request": 2, "http.head": 2,
                          "http.flush": 2}
    assert f["meta"] == {"http.request": {"queue_us": 1500}}
    assert not any(ev[0].startswith("http.") for p in out["planes"]
                   for ln in p["lines"] for ev in ln["events"])
