#!/usr/bin/env python3
"""The HTTP edge's stages of one traced run (PR 37; PERF.md sections 3, 5):

    python3 benchmark/tests/scripts/edge_report.py run <out-dir> \\
        --workload reads.degraded1 --seed N --seconds 20
    JAX_PLATFORMS=cpu python3 benchmark/tests/scripts/edge_report.py \\
        report <out-dir> [--fixture <file.json.gz>]

``run``: ``run.py --trace 1`` of a root cell behind
``tests/spans_volume.py``'s ``defaults=<out-dir>`` (the CLI's 1% sample;
the profile kept under ``<out-dir>/profile``), with one more log line,
``[edge] client``: how many reads the load generator completed and the
sum of their latencies over the window.  Its result line is the
benchmark's own; ``<out-dir>/run.err`` is its log.

``report`` (needs jax, touches no device): ``stage_facts`` over the kept
profile: the three metrics of ``stage_facts.EDGE_METRICS``, the server's
sum of hand-off + request time against the client's sum of read
latencies, each stage's count / total / self time, and the
``[trace] idle by host stage`` line.  One JSON object to stdout, the
lines to stderr.  ``--fixture`` writes 150 ms of the window (device
operations and host stages) for ``benchmark/tests/test_stage_facts.py``.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, REPO)

FIXTURE_NS = 150_000_000


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def do_run(out_dir: str, rest: list[str], manifest: str | None,
           platform: str) -> int:
    from benchmark import run
    real = run.read_end_to_end

    def read_end_to_end(records, t0, t_end):
        lat = [end - start for start, end, _z, err, _w in records
               if err is None]
        run.log(f"[edge] client: {len(lat)} reads, sum of latencies "
                f"{sum(lat):.6f} s over the window {t_end - t0:.3f} s")
        return real(records, t0, t_end)
    run.read_end_to_end = read_end_to_end
    os.makedirs(out_dir, exist_ok=True)
    return run.main(
        rest + ["--trace", "1"], manifest_path=manifest or run.MANIFEST,
        volume_module="benchmark.tests.spans_volume:defaults=" + out_dir,
        require_platform=None if platform == "none" else platform)


def device_ops(extracted: dict) -> list[list]:
    """The first chip's operations; none in a CPU rehearsal."""
    for plane in extracted["planes"]:
        if plane["device"]:
            return next(ln["events"] for ln in plane["lines"]
                        if ln["name"] == "XLA Ops")
    return []


def fixture(path: str, ops: list[list], host: dict) -> None:
    """The 150 ms that hold the window's longest device gap."""
    ops = sorted(ops, key=lambda e: e[1])
    longest = max(zip(ops, ops[1:]),
                  key=lambda p: p[1][1] - (p[0][1] + p[0][2]))
    a = max(ops[0][1], longest[0][1] + longest[0][2] - FIXTURE_NS // 3)
    b = a + FIXTURE_NS

    def within(ev):
        return a <= ev[1] and ev[1] + ev[2] <= b
    out = {"device_events": [e for e in ops if within(e)],
           "host": {"lines": [
               {"name": ln["name"],
                "events": [e for e in ln["events"] if within(e)]}
               for ln in host["lines"]]}}
    out["host"]["lines"] = [ln for ln in out["host"]["lines"]
                            if ln["events"]]
    with gzip.open(path, "wt") as f:
        json.dump(out, f)
    say(f"fixture: {len(out['device_events'])} operations, "
        f"{sum(len(ln['events']) for ln in out['host']['lines'])} stages "
        f"on {len(out['host']['lines'])} threads -> {path}")


def do_report(out_dir: str, fixture_path: str | None) -> int:
    from benchmark import stage_facts, trace_extract
    profile = os.path.join(out_dir, "profile")
    extracted = trace_extract.extract(profile)
    host = stage_facts.extract(profile)
    facts = stage_facts.facts(host)
    metrics = stage_facts.edge_metrics(facts)
    n = facts["count"].get(stage_facts.REQUEST, 0)
    server_s = (facts["meta"].get(stage_facts.REQUEST, {}).get(
        "queue_us", 0) / 1e6 + facts["total_s"].get(stage_facts.REQUEST, 0))
    client = None
    log = os.path.join(out_dir, "run.err")
    if os.path.exists(log):
        with open(log) as f:
            m = re.search(r"\[edge\] client: (\d+) reads, sum of latencies "
                          r"([0-9.]+) s", f.read())
        if m:
            client = {"reads": int(m.group(1)), "sum_s": float(m.group(2))}
    ops = device_ops(extracted)
    out = {"metrics": metrics, "requests": n,
           "server_handoff_plus_request_s": server_s, "client": client,
           "stages": {name: {"count": facts["count"][name],
                             "total_s": facts["total_s"][name],
                             "self_s": facts["self_s"].get(name, 0.0)}
                      for name in sorted(facts["count"])},
           "idle": stage_facts.idle_by_host_stage(ops, host)}
    say("[edge] " + ", ".join(f"{k} {v}" for k, v in metrics.items()))
    say(f"[edge] server: {n} requests, hand-off + request {server_s:.6f} s;"
        f" client: {client}")
    say("[edge] stages (count, total ms a request, self ms a request): "
        + "; ".join(f"{k} {v['count']} {v['total_s'] / max(n, 1) * 1e3:.4f}"
                    f" {v['self_s'] / max(n, 1) * 1e3:.4f}"
                    for k, v in out["stages"].items()))
    say(stage_facts.idle_line(ops, host))
    if fixture_path:
        fixture(fixture_path, ops, host)
    print(json.dumps(out))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("run", "report"))
    ap.add_argument("out_dir")
    ap.add_argument("--fixture")
    ap.add_argument("--manifest", help="run: default the root's")
    ap.add_argument("--require-platform", default="tpu",
                    help="run: 'none' for a CPU rehearsal")
    args, rest = ap.parse_known_args()
    if args.mode == "run":
        return do_run(os.path.abspath(args.out_dir), rest, args.manifest,
                      args.require_platform)
    return do_report(args.out_dir, args.fixture)


if __name__ == "__main__":
    sys.exit(main())
