"""What one ``spans_volume`` run kept, reduced to PERF.md's tables:

    JAX_PLATFORMS=cpu python3 benchmark/tests/scripts/spans_report.py <out-dir> \\
        [--fixture <file.json.gz>] > report.json

(a) which stages are in the flight recorder and which in the ``.xplane.pb``,
    and whether every server span has ``queue_ms`` / ``cpu_ms`` / ``send_ms``;
(b) per kind of request (seal, healthy read, rebuilt read): each stage's
    self time (median and mean over the requests that have it, in ms) and
    what no stage covers: the server span's length less its own-thread
    children and ``send_ms``, as a share of its length, at the median;
(c) the dispatcher's ``loop_s`` against the wall time between the two
    ``stats()`` reads round the window, its stages against ``loop_s.dispatch``;
(d) ``host_spans.attribute_gaps`` over the device's idle time.

Human-readable lines go to stderr, one JSON object to stdout.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from benchmark import host_spans, trace_extract  # noqa: E402

# recorded by another thread than the one their parent ran in: they lie
# INSIDE a sibling (ec.batch.result, ec.pipeline's waits), so they are
# not taken out of the parent's self time
CROSS_THREAD = {"ec.batch.wait", "ec.batch.dispatch", "ec.pipeline.read",
                "ec.pipeline.write"}
SEAL_STAGES = {"submit", "wait", "stack", "pad", "launch", "fetch",
               "unpack", "demux", "result"}
READ_STAGES = {"locate", "cache", "read_interval", "recover", "survivors",
               "crc", "respond"}


def say(msg: str) -> None:
    print(msg, file=sys.stderr)


def load(path: str):
    with open(path) as f:
        return json.load(f)


def med(xs: list[float]) -> float:
    return statistics.median(xs) if xs else float("nan")


def request_kind(root: dict, names: set[str]) -> str | None:
    if root["name"].startswith("POST /admin/ec/generate"):
        return "seal"
    if "volume.read" not in names:
        return None
    return "read.rebuilt" if "store.ec.recover" in names else (
        "read.healthy" if "store.ec.read_interval" in names
        else "read.cached")


def span_tables(spans: list[dict]) -> dict:
    by_id = {s["span_id"]: s for s in spans}
    kids: dict[str, list[dict]] = {}
    for s in spans:
        kids.setdefault(s["parent_id"], []).append(s)

    def descendants(s: dict):
        for c in kids.get(s["span_id"], []):
            yield c
            yield from descendants(c)

    def self_ms(s: dict) -> float:
        own = sum(c["duration_ms"] for c in kids.get(s["span_id"], [])
                  if c["name"] not in CROSS_THREAD)
        return s["duration_ms"] - own

    out: dict = {}
    servers = [s for s in spans if s["kind"] == "server"]
    out["server_spans"] = len(servers)
    out["server_spans_with_edge_floats"] = sum(
        1 for s in servers
        if all(k in s for k in ("queue_ms", "cpu_ms", "send_ms")))
    per_kind: dict[str, dict] = {}
    for root in servers:
        if root["parent_id"] in by_id:
            continue
        desc = list(descendants(root))
        kind = request_kind(root, {d["name"] for d in desc})
        if kind is None:
            continue
        k = per_kind.setdefault(kind, {"n": 0, "duration_ms": [],
                                       "queue_ms": [], "cpu_ms": [],
                                       "send_ms": [], "uncovered": [],
                                       "stages": {}})
        k["n"] += 1
        for key in ("duration_ms", "queue_ms", "cpu_ms", "send_ms"):
            k[key].append(root[key])
        own = self_ms(root) - root["send_ms"]
        k["uncovered"].append(own / root["duration_ms"]
                              if root["duration_ms"] else 0.0)
        per_req: dict[str, float] = {}
        counts: dict[str, int] = {}
        for d in desc:
            per_req[d["name"]] = per_req.get(d["name"], 0.0) + self_ms(d)
            counts[d["name"]] = counts.get(d["name"], 0) + 1
        for name, v in per_req.items():
            st = k["stages"].setdefault(name, {"self_ms": [], "n": []})
            st["self_ms"].append(v)
            st["n"].append(counts[name])
    table = {}
    for kind, k in per_kind.items():
        table[kind] = {
            "requests": k["n"],
            **{key + "_p50": med(k[key]) for key in
               ("duration_ms", "queue_ms", "cpu_ms", "send_ms")},
            # (the per-thread CPU clock may tick in 10 ms: read the mean)
            **{key + "_mean": statistics.fmean(k[key]) for key in
               ("duration_ms", "queue_ms", "cpu_ms", "send_ms")},
            "uncovered_share_p50": med(k["uncovered"]),
            "stages": {name: {
                "requests_with_it": len(st["self_ms"]),
                "spans_a_request": statistics.fmean(st["n"]),
                "self_ms_p50": med(st["self_ms"]),
                # over ALL requests of the kind: these add up to the
                # mean duration (less the server span's own self time)
                "self_ms_mean_over_all": sum(st["self_ms"]) / k["n"]}
                for name, st in sorted(
                    k["stages"].items(),
                    key=lambda kv: -sum(kv[1]["self_ms"]))}}
    out["kinds"] = table
    out["stage_names"] = sorted({s["name"] for s in spans
                                 if s["kind"] != "server"})
    return out


def loop_check(start: dict, stop: dict) -> dict:
    a, b = start["batcher"], stop["batcher"]
    wall = stop["monotonic"] - start["monotonic"]
    loop = {k: b["loop_s"][k] - a["loop_s"][k] for k in b["loop_s"]}
    stage = {k: b["stage_s"][k] - a["stage_s"][k] for k in b["stage_s"]}
    n = {k: b["stage_n"][k] - a["stage_n"][k] for k in b["stage_n"]}
    inner = sum(stage[k] for k in ("stack", "pad", "launch", "fetch",
                                   "unpack", "demux"))
    return {"wall_s": wall, "loop_s": loop,
            "loop_over_wall": sum(loop.values()) / wall,
            "stage_s": stage, "stage_n": n,
            "stages_over_dispatch": inner / loop["dispatch"]
            if loop["dispatch"] else None,
            "by_kind": {k: {f: b["by_kind"][k][f] - a["by_kind"][k][f]
                            for f in b["by_kind"][k]}
                        for k in b["by_kind"]},
            "jobs": b["jobs_total"] - a["jobs_total"],
            "backend_compiles": [a.get("backend_compiles"),
                                 b.get("backend_compiles")]}


def top(d: dict, n: int = 5) -> list:
    total = sum(d.values()) or 1.0
    return [[k, round(v, 6), round(v / total, 4)] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def write_fixture(path: str, device_events: list, host: dict,
                  n_events: int) -> None:
    """A few hundred events from the window's start, for the tests: the
    dispatcher's first stage began before the trace did and is not in
    it, so the first gap lies under no stage."""
    events = sorted(device_events, key=lambda e: e[1])[:n_events]
    t_end = max(s + d for _n, s, d in events)
    lines = []
    for ln in host["lines"]:
        evs = [e for e in ln["events"] if e[1] + e[2] <= t_end]
        if evs:
            lines.append({"name": ln["name"], "events": evs[:4 * n_events]})
    with gzip.open(path, "wt") as f:
        json.dump({"device_events": events, "host": {"lines": lines}}, f)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("out_dir")
    ap.add_argument("--fixture")
    ap.add_argument("--fixture-events", type=int, default=120)
    args = ap.parse_args()
    d = args.out_dir
    report: dict = {}
    report["spans"] = span_tables(load(os.path.join(d, "traces.json"))
                                  ["spans"])
    report["loop"] = loop_check(load(os.path.join(d, "batcher.start.json")),
                                load(os.path.join(d, "batcher.stop.json")))
    profile = os.path.join(d, "profile")
    extracted = trace_extract.extract(profile)
    host = host_spans.extract_host(profile)
    report["xplane_bytes"] = host["xplane_bytes"]
    report["xplane_stage_events"] = sum(len(ln["events"])
                                        for ln in host["lines"])
    report["xplane_stage_names"] = sorted(
        {e[0] for ln in host["lines"] for e in ln["events"]})
    planes = [p for p in extracted["planes"] if p["device"]] \
        or extracted["planes"]
    ops = [e for p in planes[:1] for ln in p["lines"]
           if ln["name"] == "XLA Ops" or not p["device"]
           for e in ln["events"]]
    modules = sorted({e[0] for p in planes for ln in p["lines"]
                      if ln["name"] == "XLA Modules" for e in ln["events"]})
    report["modules"] = modules
    all_host = [e for ln in host["lines"] for e in ln["events"]]
    window = (min(e[1] for e in all_host + ops),
              max(e[1] + e[2] for e in all_host + ops))
    report["gaps"] = host_spans.attribute_gaps(ops, host, window)
    if args.fixture:
        write_fixture(args.fixture, ops, host, args.fixture_events)

    sp, lp, gp = report["spans"], report["loop"], report["gaps"]
    stages = {n.rsplit(".", 1)[-1] for n in sp["stage_names"]}
    xstages = {n.rsplit(".", 1)[-1] for n in report["xplane_stage_names"]}
    say(f"(a) server spans {sp['server_spans']}, with queue/cpu/send "
        f"{sp['server_spans_with_edge_floats']}; missing in /debug/traces:"
        f" seal {sorted(SEAL_STAGES - stages)} read "
        f"{sorted(READ_STAGES - stages)}; missing in the xplane: seal "
        f"{sorted(SEAL_STAGES - xstages)} read "
        f"{sorted(READ_STAGES - xstages)}; xplane {report['xplane_bytes']}"
        f" B, {report['xplane_stage_events']} stage events; modules "
        f"{modules}")
    for kind, k in sp["kinds"].items():
        say(f"(b) {kind}: {k['requests']} requests, duration p50 "
            f"{k['duration_ms_p50']:.3f} mean {k['duration_ms_mean']:.3f}"
            f" ms, queue p50 {k['queue_ms_p50']:.3f} mean "
            f"{k['queue_ms_mean']:.3f}, cpu mean {k['cpu_ms_mean']:.3f}, "
            f"send p50 {k['send_ms_p50']:.3f}, "
            f"uncovered share p50 {k['uncovered_share_p50']:.4f}")
        for name, st in k["stages"].items():
            say(f"      {name:26s} self p50 {st['self_ms_p50']:9.3f} ms  "
                f"mean over all {st['self_ms_mean_over_all']:9.3f} ms  in "
                f"{st['requests_with_it']} requests, "
                f"{st['spans_a_request']:.1f} spans each")
    say(f"(c) wall {lp['wall_s']:.3f}s loop {lp['loop_s']} = "
        f"{lp['loop_over_wall']:.4f} of wall; stages/dispatch "
        f"{lp['stages_over_dispatch']}; stage_s {lp['stage_s']} stage_n "
        f"{lp['stage_n']}; by_kind {lp['by_kind']}; compiles "
        f"{lp['backend_compiles']}")
    if gp:
        say(f"(d) device idle {gp['idle_s']:.4f}s in {gp['gaps']} gaps; "
            f"under a dispatcher stage {gp['under_a_stage_share']:.4f}; by "
            f"dispatcher stage {top(gp['by_dispatcher_stage'], 9)}; under "
            f"ec.batch.idle {gp['under_idle_s']:.4f}s, request stages in "
            f"flight then {top(gp['under_idle_by_request_stage'])}; "
            f"longest gaps {gp['longest_gaps'][:5]}")
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
