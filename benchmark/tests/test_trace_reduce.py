"""The reduction from a device trace to busy time, per-operation sums and
idle gaps: on hand-made intervals, and on a small trace recorded on the
TPU v5e (``recorded_trace_v5e.json.gz``, seal.storm8, PR 24)."""

import gzip
import json
import os

import pytest

from benchmark import trace_reduce
from benchmark.trace_extract import short_name

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_of_overlapping_and_touching_intervals():
    ns = 1_000_000_000
    assert trace_reduce.union_seconds([]) == 0.0
    assert trace_reduce.union_seconds(
        [(0, ns), (ns // 2, 2 * ns), (2 * ns, 3 * ns), (5 * ns, 6 * ns),
         (5 * ns, 5 * ns + 10)]) == 4.0


def test_gaps_are_named_by_what_ends_them():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 40, 5], ["d", 1045, 1]]
    gaps = trace_reduce.longest_gaps(ev)
    assert gaps == [["before d", 1000 / 1e9], ["before c", 25 / 1e9]]


def test_two_planes_are_two_chips_and_are_averaged():
    def plane(name, scale):
        return {"name": name, "device": True, "lines": [
            {"name": "XLA Modules", "events": [["jit_f", 0, 100 * scale]]},
            {"name": "XLA Ops", "events": [["x", 0, 40 * scale],
                                           ["y", 50 * scale, 20 * scale]]},
            {"name": "Steps", "events": [["1", 0, 10 ** 9]]}]}
    r = trace_reduce.reduce({"planes": [plane("/device:TPU:0", 1),
                                        plane("/device:TPU:1", 3)]})
    assert r["busy_s"] == pytest.approx((60 + 180) / 2 / 1e9)
    assert r["ops"]["x"] == pytest.approx((40 + 120) / 2 / 1e9)
    assert r["modules_s"] == pytest.approx((100 + 300) / 2 / 1e9)
    assert r["module_calls"] == 1


def test_nothing_on_a_device_plane_reduces_to_nothing():
    host = {"name": "/host:CPU", "device": False, "lines": [
        {"name": "tf_XLAPjRtCpuClient/1", "events": [["dot", 0, 50]]}]}
    assert trace_reduce.reduce({"planes": [host]}) is None
    assert trace_reduce.reduce({"planes": []}, allow_host=True) is None
    r = trace_reduce.reduce({"planes": [host]}, allow_host=True)
    assert r["busy_s"] == pytest.approx(50 / 1e9)


def test_programs_without_an_ops_line_are_refused():
    plane = {"name": "/device:TPU:0", "device": True, "lines": [
        {"name": "XLA Modules", "events": [["jit_f", 0, 100]]}]}
    with pytest.raises(ValueError, match="XLA Ops"):
        trace_reduce.reduce({"planes": [plane]})
    idle = {"name": "/device:TPU:1", "device": True, "lines": [
        {"name": "Steps", "events": [["1", 0, 10]]}]}
    assert trace_reduce.reduce({"planes": [idle]}) is None


def test_short_names():
    assert short_name(
        "%fusion.9 = u32[1,1,262144]{2,1,0:T(1,128)S(1)} fusion(u32[1,10,"
        "262144]{2,0,1:T(1,128)} %words.1), kind=kLoop") \
        == "fusion.9 u32[1,1,262144]"
    assert short_name(
        "%copy-start = (u32[4,10,262144]{2,0,1:T(4,128)S(1)}, u32[]) "
        "copy-start(u32[4,10,262144] %w)") == "copy-start u32[4,10,262144]"
    assert short_name("jit_one(886)") == "jit_one(886)"


def test_the_recorded_v5e_trace():
    with gzip.open(os.path.join(HERE, "recorded_trace_v5e.json.gz"),
                   "rt") as f:
        recorded = json.load(f)
    r = trace_reduce.reduce(recorded)
    assert r["planes"] == ["/device:TPU:0"]
    # 1388 operations on one line, none overlapping: the union is their sum
    assert r["busy_s"] == pytest.approx(0.094917393, abs=1e-12)
    assert r["ops_s"] == pytest.approx(r["busy_s"], abs=1e-12)
    assert r["span_s"] == pytest.approx(10.314019534, abs=1e-9)
    # three programs ran: the encode at B = 1, 2 and 4
    assert r["module_calls"] == 177
    assert sorted(round(v, 9) for v in r["modules"].values()) == [
        0.014616499, 0.021324639, 0.058978979]
    assert r["modules_s"] == pytest.approx(0.094920117, abs=1e-12)
    assert r["top_ops"][0] == ["xor_xor_fusion.2 u32[4,1,262144]",
                               pytest.approx(0.015364573)]
    assert r["gaps"][0] == ["before copy-start u32[1,10,262144]",
                            pytest.approx(1.706739563)]
    assert len(r["gaps"]) == 10 and len(r["top_ops"]) == 10
