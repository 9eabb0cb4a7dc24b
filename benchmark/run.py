#!/usr/bin/env python3
"""One run of one cell of the benchmark (see README.md, BENCHMARK.json).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Starts a CLI master and a CLI volume server (the latter behind
``benchmark/served_volume.py``), fills volumes from the seed, warms up,
measures for ``--seconds``, checks what the window produced against the
plain reference, stops every child, and prints one JSON object as the
last line of standard output.  Everything else goes to standard error.

This (parent) process is load generator, checker and metric arithmetic.
It NEVER imports jax: the chip belongs to the volume server.  A run whose
volume server reports another platform than ``tpu``, or fewer chips than
the cell asks for, prints no result and exits non-zero.
"""

from __future__ import annotations

import time

_T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import bytes_model, loadgen, reference  # noqa: E402
from benchmark import trace_reduce  # noqa: E402
from benchmark.harness import Cluster, log, preflight  # noqa: E402

MANIFEST = os.path.join(REPO, "BENCHMARK.json")
BATCHER_COUNTERS = ("jobs_total", "batches_total", "mesh_batches",
                    "cpu_batches", "coder_fallbacks", "programs_compiled")


FAILED_READ_MS = 30000.0


class NoAccelerator(Exception):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(manifest_path: str, name: str) -> tuple[dict, dict, dict, dict]:
    """(manifest, cell, configuration, traffic) by the names in the
    manifest.  A configuration is the file its entry names; a traffic mix
    is ``traffic/<name>.json`` beside the manifest's own files (for the
    root's BENCHMARK.json: in this directory), else in this directory."""
    manifest = load_json(manifest_path)
    if "per_layer" not in manifest:
        # the tests' cells: the root's metrics, each for whichever cell
        # reports the end-to-end metric it moves
        root = load_json(MANIFEST)
        for key in ("end_to_end", "per_layer"):
            manifest[key] = [{k: v for k, v in m.items() if k != "workloads"}
                             for m in root[key]]
    cell = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no cell {name!r} in {manifest_path}; it has "
                         f"{[w['name'] for w in manifest['workloads']]}")
    cfg = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(REPO, cfg["file"]))
    for base in (os.path.dirname(os.path.abspath(manifest_path)), HERE):
        path = os.path.join(base, "traffic", cell["traffic"] + ".json")
        if os.path.exists(path):
            return manifest, cell, config, load_json(path)
    raise SystemExit(f"no traffic file {cell['traffic']}.json")


def metric_applies(metric: dict, cell: str, reported: set[str]) -> bool:
    """A metric with a ``workloads`` list is for those cells; a per-layer
    metric without one is for every cell that reports what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def metric_spec(name: str) -> dict:
    """``metrics/<name>.json`` (``reader`` and ``params``); a metric split
    by the end-to-end metric it moves (``x.seal``, ``x.read``) reads
    ``metrics/x.json`` unless it has a file of its own."""
    for stem in (name, name.rpartition(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".json")
        if stem and os.path.exists(path):
            return load_json(path)
    raise FileNotFoundError(f"no metrics/{name}.json")


# ---- counters ----

def host_facts(cluster: Cluster) -> dict:
    """CPU ticks of the servers so far, user and system (logged beside
    the window, never a metric: a run whose calls were slow shows it in
    the volume server's system time; PERF.md section 6)."""
    out = {}
    for name, proc, _log in cluster.procs:
        with open(f"/proc/{proc.pid}/stat") as f:
            stat = f.read().rsplit(")", 1)[1].split()
        out[name] = {"utime": int(stat[11]), "stime": int(stat[12])}
    return out


def snapshot(cluster: Cluster, vids: list[int], sealed: bool) -> dict:
    """The program's counters, flat: read before and after the window."""
    b = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
    out = {f"batcher.{k}": b.get(k) or 0 for k in BATCHER_COUNTERS}
    count = total = 0.0
    for _labels, counts, s, _ex in b["wait_hist"]["series"]:
        count += sum(counts)
        total += s
    out["batcher.wait_count"] = count
    out["batcher.wait_sum_s"] = total
    stats = cluster.command("stats")
    out["wrapper.compiles"] = stats["compiles"]
    out["wrapper.compile_s"] = stats["compile_s"]
    out["device.peak_bytes"] = max(
        (d["peak_bytes_in_use"] or 0 for d in stats["devices"]), default=0)
    out["recover.intervals"] = 0
    if sealed:
        rs = cluster.http(
            "GET", cluster.volume + f"/admin/ec/shard_stat?volumeId={vids[0]}"
        )["recover_stats"]
        out["recover.intervals"] = sum(rs.values())
        # by the strategy the store took (``local``: from a lost shard's
        # own group under LRC; ``generic``: from k survivors): logged
        # beside the window, read by no metric
        out.update({f"recover.by.{k}": v for k, v in rs.items()})
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))
            and isinstance(before.get(k), (int, float))}


# ---- end-to-end arithmetic ----

def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values."""
    if not sorted_values:
        raise ValueError("no values")
    rank = max(1, -(-len(sorted_values) * q // 100))
    return sorted_values[int(rank) - 1]


def seal_end_to_end(records: list[tuple], workers: int) -> dict:
    """``.dat`` bytes sealed per second of sealing, over ALL calls started
    in the window (the one in flight at the close is waited for): the
    bytes of the calls that succeeded over the time all calls took,
    times the sealers.  With sealers that call back to back that is the
    bytes over the window; with a ``period_seconds`` the time a sealer
    waits for its next turn is the generator's and is left out, as is
    its delete of the last call's shards before each call
    (``loadgen.seal_workers``)."""
    took = sum(end - start for start, end, *_ in records)
    done = sum(size for _s, _e, size, err, *_ in records if err is None)
    return {"seal_mbps": done / 1e6 * workers / took}


def read_end_to_end(records: list[tuple], t0: float, t_end: float) -> dict:
    """Latency over ALL reads started in the window (the few in flight at
    the close are waited for and counted: they are the long ones); a
    failed read counts as the client's 30 s deadline at the least.
    Rate: reads completed inside the window over the window."""
    lat = sorted((end - start) * 1e3 if err is None
                 else max((end - start) * 1e3, FAILED_READ_MS)
                 for start, end, _s, err, _w in records)
    completed = sum(1 for _s, end, _z, err, _w in records
                    if err is None and end <= t_end)
    out = {"read_ops": completed / (t_end - t0)}
    if lat:
        out["read_p50_ms"] = percentile(lat, 50)
        out["read_p99_ms"] = percentile(lat, 99)
    return out


# ---- the check ----

def compare_seal(cluster: Cluster, config: dict, corpus, records) -> dict:
    """Every shard file left on disk by the window's last call on each
    volume, byte for byte against the plain reference's encoding of that
    volume's ``.dat`` under the configuration's STATED code (its ``code``
    block, family and all; what the seal was asked for is
    ``generate_body``'s affair); and of EVERY call of the window its look
    at its files and the spans it sampled from all of them (offsets from
    the seed), against the reference's bytes for the same spans."""
    code = config["code"]
    total = code["data_shards"] + code["parity_shards"]
    jobs = [(os.path.join(cluster.voldir, f"{vid}.dat"),
             loadgen.shard_paths(cluster, vid, total))
            for vid in corpus.vids]
    t0 = time.monotonic()
    bad = reference.differing_shard_files_many(
        jobs, code, threads=min(8, os.cpu_count() or 1))
    log(f"[check] reference encoding of {len(jobs)} volume(s) compared "
        f"with {len(jobs) * total} shard files in "
        f"{time.monotonic() - t0:.2f}s; differing: "
        f"{ {v: b for v, b in zip(corpus.vids, bad) if b} }")
    t0 = time.monotonic()
    sampled = wrong = 0
    for vid, (dat, _shards) in zip(corpus.vids, jobs):
        spans = [sp for r in records for sp in r[5] if sp[0] == vid]
        want = reference.expected_spans(
            dat, [(off, n) for _v, off, n, _d in spans], code)
        sampled += len(spans)
        wrong += sum(1 for sp, w in zip(spans, want)
                     if hashlib.sha256(w).hexdigest() != sp[3])
    log(f"[check] {sampled} spans sampled after "
        f"{sum(1 for r in records if r[5])} calls compared with the "
        f"reference in {time.monotonic() - t0:.2f}s; differing: {wrong}")
    return {
        "shard_files_differing": (sum(len(b) for b in bad), "<=", 0),
        "sampled_spans_differing": (wrong, "<=", 0),
        "calls_sampled": (sum(1 for r in records if r[5]), ">=",
                          sum(1 for r in records if r[3] is None
                              and not r[4])),
        "calls_leaving_stale_files": (
            sum(1 for r in records if r[4]), "<=", 0),
        "calls_failed": (sum(1 for r in records if r[3] is not None),
                         "<=", 0),
        "calls_completed": (sum(1 for r in records if r[3] is None),
                            ">=", len(corpus.vids)),
    }


def compare_read(records, counters: dict, config: dict) -> dict:
    out = {
        "reads_wrong": (sum(1 for r in records if r[4]), "<=", 0),
        "reads_failed": (sum(1 for r in records if r[3] is not None),
                         "<=", 0),
        "reads_completed": (sum(1 for r in records if r[3] is None),
                            ">=", 1),
    }
    if config.get("lost_shards"):
        # the cell is ABOUT reads rebuilt on the chip: a window in which
        # none was (a cache, a repair that put the shard back) is not it
        out["intervals_reconstructed"] = (
            counters["recover.intervals"], ">=", 1)
    return out


def verdict(compared: dict) -> tuple[bool, dict]:
    ok = True
    shown = {}
    for name, (value, op, limit) in compared.items():
        good = value <= limit if op == "<=" else value >= limit
        ok = ok and good
        shown[name] = {"value": value, "limit": limit, "op": op,
                       "ok": good}
    return ok, shown


# ---- the run ----

def check_device(cluster: Cluster, cell: dict, peaks: dict,
                 require_platform: str | None) -> dict:
    """The device as the volume server's own ``/status`` names it; no
    result without the platform and the chips the cell asks for."""
    status = cluster.http("GET", cluster.volume + "/status")
    batcher = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
    dev = status.get("EcDevice")
    log(f"[device] EcDevice={json.dumps(dev)} mesh_devices="
        f"{batcher.get('mesh_devices')}")
    if not dev or dev != batcher.get("device"):
        raise RuntimeError(f"status and batcher disagree about the coder's "
                           f"device: {dev} vs {batcher.get('device')}")
    if require_platform is None:
        return dev
    if dev["platform"] != require_platform:
        raise NoAccelerator(
            f"the coder's platform is {dev['platform']!r} "
            f"({dev['device_kind']}), not {require_platform!r}")
    if dev["count"] < cell["chips"]:
        raise NoAccelerator(f"the cell asks for {cell['chips']} chip(s), "
                            f"the coder dispatches to {dev['count']}")
    if dev["device_kind"] not in peaks:
        raise RuntimeError(f"device kind {dev['device_kind']!r} is not in "
                           "benchmark/peaks.json: add it with its source")
    return dev


def log_window(window, records: list[tuple], counters: dict,
               op: str) -> None:
    log(f"[window] opened at unix time "
        f"{time.time() - (time.monotonic() - window.t0):.2f}; "
        f"{len(records)} operations started in "
        f"{window.seconds:.1f}s, drained "
        f"{window.t_drained - window.t_end:.2f}s after the close; "
        f"counters {json.dumps(counters)}")
    slow = sorted(records, key=lambda r: r[0] - r[1])[:12]
    log("[window] the longest operations (start after t0, seconds): "
        + ", ".join(f"{r[0] - window.t0:.2f}+{r[1] - r[0]:.3f}"
                    for r in sorted(slow, key=lambda r: r[0])))
    lat = sorted((r[1] - r[0]) * 1e3 for r in records if r[3] is None)
    if lat:
        log("[window] operation ms: " + ", ".join(
            f"p{q}={percentile(lat, q):.2f}"
            for q in (10, 50, 75, 90, 95, 98, 99, 99.5, 99.9))
            + f", max={lat[-1]:.2f}, n={len(lat)}")
    if op == "seal":
        calls = sorted(records, key=lambda r: r[0])
        log("[window] MB/s of each call, in order: " + " ".join(
            f"{r[2] / 1e6 / (r[1] - r[0]):.0f}" for r in calls)
            + f"; the latest start {max(r[6] for r in calls) * 1e3:.1f} "
            "ms after it was due; clearing the shards before each took "
            + " ".join(f"{r[7] * 1e3:.0f}" for r in calls) + " ms")
        # the program's own account of each call (B1: no metric yet)
        log("[window] pipeline of each call, ms (wall read encode write "
            "commit; batches/overlapped): " + "; ".join(
                " ".join(f"{r[8].get(k + '_s', 0) * 1e3:.0f}" for k in
                         ("wall", "read", "encode", "write", "commit"))
                + f" {r[8].get('batches')}/{r[8].get('overlapped')}"
                for r in calls if r[8]))


def reduce_trace(cluster: Cluster, trace_dir: str, traced_s: float,
                 allow_host: bool) -> dict:
    """The profiler's files -> device events (a child that may import
    jax, after the servers have exited) -> busy time, sums, gaps."""
    extracted_path = os.path.join(cluster.workdir, "trace.json")
    subprocess.run(
        [sys.executable, "-m", "benchmark.trace_extract", trace_dir,
         extracted_path], cwd=REPO, check=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdin=subprocess.DEVNULL, stdout=sys.stderr)
    extracted = load_json(extracted_path)
    reduced = trace_reduce.reduce(extracted, allow_host=allow_host)
    if reduced is None or reduced["busy_s"] <= 0:
        raise RuntimeError("the trace holds no operation on a device "
                           "plane: the window did not drive the device")
    log(f"[trace] {extracted['xplane_bytes']} B of xplane; planes "
        f"{reduced['planes']}; busy {reduced['busy_s']:.4f}s of "
        f"{traced_s:.2f}s; {reduced['module_calls']:.0f} program runs, "
        f"{reduced['modules_s']:.4f}s: " + json.dumps(
            {n: round(t, 6) for n, t in sorted(reduced['modules'].items())}))
    return reduced


def reported_metrics(manifest: dict, cell_name: str, trace: bool,
                     e2e: dict, facts: dict) -> dict:
    """``--trace 0``: the cell's end-to-end metrics; ``--trace 1``: its
    per-layer metrics, each from its own reader (left out where the
    reader finds nothing)."""
    reported = {m["name"] for m in manifest["end_to_end"]
                if metric_applies(m, cell_name, set()) and m["name"] in e2e}
    if not trace:
        return {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in manifest["end_to_end"] if m["name"] in reported}
    metrics = {}
    for m in manifest["per_layer"]:
        if not metric_applies(m, cell_name, reported):
            continue
        spec = metric_spec(m["name"])
        reader = importlib.import_module("benchmark.readers."
                                         + spec["reader"])
        value = reader.read(facts, spec.get("params", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             manifest_path: str = MANIFEST,
             volume_module: str = "benchmark.served_volume",
             require_platform: str | None = "tpu",
             t_start: float = _T_PROCESS_START) -> dict:
    """Drive one run; returns the result object.  ``require_platform``
    None is for rehearsals and tests on the CPU backend (the device named
    in the result is still what the volume server reported)."""
    manifest, cell, config, traffic = load_cell(manifest_path, cell_name)
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    op = traffic["op"]
    sealed = config["state"] == "sealed"
    preflight()
    cluster = Cluster(volume_module)
    trace_dir = os.path.join(cluster.workdir, "trace")
    try:
        try:
            warm = cluster.start(
                {**traffic["warm"], "code": loadgen.asked_code(config)},
                config["volume_size_limit_mb"],
                config["servers"]["max_volumes"],
                config["servers"].get("env", {}))
            log(f"[servers] ready; warm-up {warm['warm_s']:.2f}s of "
                f"{warm['spec']}: {warm['programs']}; compile cache "
                f"{warm['compile_cache_dir']}"
                + (f"; UNWARMED: {warm['unwarmed']}"
                   if "unwarmed" in warm else ""))
            dev = check_device(cluster, cell, peaks, require_platform)

            corpus = loadgen.fill(cluster, traffic, seed)
            loadgen.prepare(cluster, config, corpus)
            warm_window = loadgen.Window(traffic["warmup_seconds"])
            warm_window.run(loadgen.make_workers(
                cluster, config, traffic, corpus, seed, stream=3))
            bad_warm = [r for r in warm_window.all_records()
                        if r[3] or r[4]]
            log(f"[warm-up] {len(warm_window.all_records())} operations "
                f"through the served path; failed or wrong: {bad_warm[:3]}")

            workers = loadgen.make_workers(cluster, config, traffic, corpus,
                                           seed, stream=4)
            before = snapshot(cluster, corpus.vids, sealed)
            if trace:
                r = cluster.command("trace.start", trace_dir)
                log(f"[trace] started in {r['took_s']:.2f}s")
            window = loadgen.Window(seconds)
            host_before = host_facts(cluster)
            cpu_before = time.process_time()
            t_traced0 = time.monotonic()
            setup_s = t_traced0 - t_start
            window.run(workers)
            traced_s = time.monotonic() - t_traced0
            host_after = host_facts(cluster)
            log(f"[host] load generator busy "
                f"{(time.process_time() - cpu_before) / traced_s:.2f} "
                "cores; servers' CPU ticks in the window: " + json.dumps(
                    {n: delta(host_after[n], host_before[n])
                     for n in host_after}))
            if trace:
                r = cluster.command("trace.stop")
                log(f"[trace] stopped in {r['took_s']:.2f}s")
            after = snapshot(cluster, corpus.vids, sealed)
            records = window.all_records()
            counters = delta(after, before)
            log_window(window, records, counters, op)

            if op == "seal":
                e2e = seal_end_to_end(records, traffic["workers"])
                compared = compare_seal(cluster, config, corpus, records)
            else:
                e2e = read_end_to_end(records, window.t0, window.t_end)
                compared = compare_read(records, counters, config)
            e2e["setup_s"] = setup_s
        except BaseException:
            cluster.print_log_tails()
            raise
        finally:
            cluster.stop()

        compared["warmup_operations_failed"] = (len(bad_warm), "<=", 0)
        compared["cpu_batches"] = (after["batcher.cpu_batches"], "<=", 0)
        compared["coder_fallbacks"] = (
            after["batcher.coder_fallbacks"], "<=", 0)
        compared["mesh_dispatches_in_window"] = (
            counters["batcher.mesh_batches"], ">=", 1)

        # what the per-layer readers read
        facts = {"op": op, "counters": counters, "traced_s": traced_s,
                 "peaks": peaks.get(dev["device_kind"], {}),
                 "ops_ok": sum(1 for r in records if r[3] is None)}
        if op == "seal":
            code = config["code"]
            facts["min_bytes"] = sum(
                bytes_model.seal_min_bytes(
                    r[2], code["data_shards"], code["parity_shards"],
                    code["large_block_bytes"], code["small_block_bytes"])
                for r in records if r[3] is None)
        device = {"platform": dev["platform"], "kind": dev["device_kind"],
                  "count": dev["count"],
                  "memory_peak_bytes": after["device.peak_bytes"]}
        breakdown = None
        if trace:
            reduced = reduce_trace(cluster, trace_dir, traced_s,
                                   require_platform is None)
            facts["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = traced_s
            breakdown = {"device_ops": reduced["top_ops"],
                         "idle_gaps": reduced["gaps"]}
    finally:
        cluster.stop()
        cluster.cleanup()

    correct, shown = verdict(compared)
    result = {"correct": correct, "attempted": len(records),
              "failed": sum(1 for r in records if r[3] is not None or r[4]),
              "metrics": reported_metrics(manifest, cell_name, trace, e2e,
                                          facts),
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["end_to_end_of_this_run"] = e2e
    result["compared"] = shown
    if "jax" in sys.modules:
        raise RuntimeError("the benchmark's parent process imported jax")
    for name, c in shown.items():
        log(f"[compared] {name} = {c['value']} (limit {c['op']} "
            f"{c['limit']}) {'ok' if c['ok'] else 'FAILED'}")
    return result


def main(argv=None, **overrides) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), **overrides)
    except NoAccelerator as e:
        log(f"FAILED: no accelerator for this cell: {e}")
        return 3
    except BaseException as e:  # noqa: BLE001 — reported, then non-zero
        log(f"FAILED: {type(e).__name__}: {e}")
        log(traceback.format_exc())
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
