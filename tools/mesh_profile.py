"""Device-scaling profile of the mesh-sharded EC coder.

Answers "does batched encode/rebuild actually scale with device count?"
for ops/rs_mesh.py: one MeshCoder per device count, same batch of
block-groups, throughput table plus the 1->2 device scaling ratio the
multichip acceptance floor watches. Mirrors tools/ec_profile.py: a
table for humans, one JSON line for scripts.

Usage:
  PYTHONPATH=. python tools/mesh_profile.py                 # 1..all devices
  PYTHONPATH=. python tools/mesh_profile.py --devices 1,2,4 # override
  PYTHONPATH=. python tools/mesh_profile.py --batch 32 --cols 262144

NOTE: on a single host CPU the virtual devices share the same cores, so
the ratio staying ~1.0 there is physics, not a bug — the floor only
binds on real multi-device hardware (see measure_scaling docstring).

measure_scaling() is the importable core: __graft_entry__'s multichip
dry run and the floor test call it so every consumer measures the same
way.  One process: the sweep runs in this interpreter, so it is the
only one that touches the chip(s).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def measure_scaling(device_counts=None, batch: int = 16,
                    n_cols: int = 64 * 1024, iters: int = 3,
                    check_identity: bool = True) -> dict:
    """Encode+rebuild throughput per device count for one shared batch
    of block-groups. Returns a dict with per-count rows, the 1->2
    scaling ratios when both counts were measured, and a CpuCoder
    bit-identity verdict. Wall-clock ratios only mean anything when the
    devices are real (distinct chips); virtual host-platform devices
    time-slice the same silicon."""
    from seaweedfs_tpu.models.coder import DEFAULT_SCHEME
    from seaweedfs_tpu.ops.rs_cpu import CpuCoder
    from seaweedfs_tpu.ops.rs_mesh import MeshCoder
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    mesh_mod.ensure_compile_cache()
    avail = mesh_mod.device_count()
    if device_counts is None:
        device_counts = [n for n in (1, 2, 4, 8, 16) if n <= avail]
    device_counts = sorted({n for n in device_counts if 1 <= n <= avail})
    scheme = DEFAULT_SCHEME
    k = scheme.data_shards
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(batch, k, n_cols), dtype=np.uint8)
    # one rebuild matrix per job, varied loss patterns
    cpu = CpuCoder(scheme)
    mats = [cpu.rebuild_matrix(
        [j for j in range(scheme.total_shards) if j != (i % k)],
        [i % k]) for i in range(batch)]
    job_bytes = batch * k * n_cols

    out: dict = {"backend": mesh_mod.default_backend(),
                 "n_devices_avail": avail, "batch": batch,
                 "cols": n_cols, "iters": iters, "rows": [],
                 "bit_identical": None,
                 "encode_scaling_1_to_2": None,
                 "rebuild_scaling_1_to_2": None}
    by_count: dict[int, dict] = {}
    for nd in device_counts:
        coder = MeshCoder(scheme, n_devices=nd)
        coder.encode_batch(data)           # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            parity = coder.encode_batch(data)
        enc_s = (time.perf_counter() - t0) / iters
        coder.rebuild_batch(data, mats)    # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            recs = coder.rebuild_batch(data, mats)
        reb_s = (time.perf_counter() - t0) / iters
        row = {"devices": nd,
               "encode_s": round(enc_s, 4),
               "encode_mbps": round(job_bytes / enc_s / 1e6, 1),
               "rebuild_s": round(reb_s, 4),
               "rebuild_mbps": round(job_bytes / reb_s / 1e6, 1)}
        out["rows"].append(row)
        by_count[nd] = row
        if check_identity and nd == device_counts[0]:
            ok = all(np.array_equal(parity[i], cpu.encode_array(data[i]))
                     for i in range(batch))
            ok = ok and all(
                np.array_equal(
                    recs[i], cpu.reconstruct_rows(data[i], mats[i]))
                for i in range(batch))
            out["bit_identical"] = bool(ok)
    if 1 in by_count and 2 in by_count:
        out["encode_scaling_1_to_2"] = round(
            by_count[2]["encode_mbps"] / by_count[1]["encode_mbps"], 2)
        out["rebuild_scaling_1_to_2"] = round(
            by_count[2]["rebuild_mbps"] / by_count[1]["rebuild_mbps"], 2)
    return out


def main(argv: list[str]) -> int:
    counts = None
    batch, cols, iters = 16, 64 * 1024, 3
    it = iter(argv)
    for a in it:
        if a == "--devices":
            counts = [int(x) for x in next(it).split(",")]
        elif a == "--batch":
            batch = int(next(it))
        elif a == "--cols":
            cols = int(next(it))
        elif a == "--iters":
            iters = int(next(it))
        else:
            print(f"unknown arg {a!r}", file=sys.stderr)
            return 2
    out = measure_scaling(counts, batch=batch, n_cols=cols, iters=iters)
    print(f"backend: {out['backend']}   devices available: "
          f"{out['n_devices_avail']}   batch: {out['batch']} x RS(10,4) "
          f"x {out['cols']} cols")
    print(f"{'devices':>8} {'encode MB/s':>12} {'rebuild MB/s':>13}")
    for r in out["rows"]:
        print(f"{r['devices']:>8} {r['encode_mbps']:>12} "
              f"{r['rebuild_mbps']:>13}")
    if out["encode_scaling_1_to_2"] is not None:
        print(f"1->2 device scaling: encode "
              f"{out['encode_scaling_1_to_2']}x, rebuild "
              f"{out['rebuild_scaling_1_to_2']}x")
    print(f"bit-identical to CpuCoder: {out['bit_identical']}")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
