#!/usr/bin/env python3
"""The sealed volume of a benchmark cell against the plain reference:
start the cell's servers, fill from the seed, keep a copy of the .dat,
seal it through ``loadgen.prepare()`` under the configuration's asked
code (no shard taken away), and compare every shard file with
``benchmark/reference_hitchhiker.py``'s encoding of that .dat.

    python3 tests/scripts/hhxor_volume_check.py \
        --workload reads.degraded1.chunks.hhxor10-4 --seed N

On a machine with the chip (one JAX process there: the volume server);
on the CPU backend with ``JAX_PLATFORMS=cpu`` and ``--manifest
benchmark/tests/cells-hhxor10-4.json --workload
tiny.reads.degraded.chunks.hhxor10-4``.  Prints one JSON line:
``differing`` (shard ids, [] when every file is the reference's).
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import loadgen, reference_hitchhiker, run  # noqa: E402
from benchmark.harness import Cluster, log  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--manifest", default=run.MANIFEST)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    _m, _cell, config, traffic = run.load_cell(args.manifest, args.workload)
    cluster = Cluster()
    try:
        cluster.start({**traffic["warm"], "code": loadgen.asked_code(config)},
                      config["volume_size_limit_mb"],
                      config["servers"]["max_volumes"],
                      config["servers"].get("env", {}))
        corpus = loadgen.fill(cluster, traffic, args.seed)
        (vid,) = corpus.vids
        from seaweedfs_tpu.utils.httpd import http_call
        cluster.http("POST", cluster.volume + "/admin/mark_readonly",
                     {"volume_id": vid, "read_only": True})
        status, dat, _ = http_call(
            "GET", f"http://{cluster.volume}/admin/volume_file?volumeId="
            f"{vid}&ext=.dat", timeout=300)
        if status != 200:
            raise RuntimeError(f"GET .dat: HTTP {status}")
        dat_path = os.path.join(cluster.workdir, "copy.dat")
        with open(dat_path, "wb") as f:
            f.write(dat)
        loadgen.prepare(cluster, {**config, "lost_shards": []}, corpus)
        code = config["code"]
        total = code["data_shards"] + code["parity_shards"]
        t0 = time.monotonic()
        bad = reference_hitchhiker.differing_files(
            dat_path, loadgen.shard_paths(cluster, vid, total), code)
        batcher = cluster.http("GET", cluster.volume + "/admin/ec/batcher")
        out = {"workload": args.workload, "seed": args.seed,
               "dat_bytes": len(dat), "shards": total, "differing": bad,
               "compare_s": round(time.monotonic() - t0, 2),
               "device": batcher.get("device"),
               "by_spec": batcher.get("by_spec"),
               "cpu_batches": batcher.get("cpu_batches")}
        log(f"[check] {json.dumps(out)}")
        print(json.dumps(out), flush=True)
        return 0 if not bad else 1
    except BaseException:
        cluster.print_log_tails()
        raise
    finally:
        cluster.stop()
        cluster.cleanup()


if __name__ == "__main__":
    sys.exit(main())
