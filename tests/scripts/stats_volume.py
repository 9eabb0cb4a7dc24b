"""benchmark/served_volume.py, with the batch scheduler's ``stats()``
read round every seal and left as one JSON line a call (a throwaway
wrapper, as PR 30's: PERF.md section 5's split of a seal's job comes
from it):

    python3 benchmark/tests/rehearse.py --manifest BENCHMARK.json \
        --require-platform tpu --wrapper tests.scripts.stats_volume:<file> \
        --workload seal.single --seed N --seconds 20 --trace 0

Runs on a tree without ``overlapped_dispatches`` too (the parent).
"""

from __future__ import annotations

import json
import sys
import time


def plant(out_path: str) -> None:
    from seaweedfs_tpu.storage.store import Store
    real = Store.generate_ec_shards

    def flat(st: dict) -> dict:
        out = {k: st.get(k, 0) for k in (
            "jobs_total", "batches_total", "mesh_batches",
            "lone_dispatches", "overlapped_dispatches", "cpu_batches",
            "max_coalesced")}
        for part in ("stage_s", "stage_n", "loop_s"):
            for k, v in st[part].items():
                out[f"{part}.{k}"] = v
        out["wait_n"] = sum(sum(c) for _l, c, _s, _e in
                            st["wait_hist"]["series"])
        out["wait_s"] = sum(s for _l, _c, s, _e in st["wait_hist"]["series"])
        return out

    def generate(self, vid, *a, **kw):
        sched = getattr(self.coder, "scheduler", None)
        if sched is None:
            return real(self, vid, *a, **kw)
        before, t0 = flat(sched.stats()), time.monotonic()
        try:
            return real(self, vid, *a, **kw)
        finally:
            wall = time.monotonic() - t0
            time.sleep(0.01)   # a dispatch is counted after its futures
            after = flat(sched.stats())
            line = {k: after[k] - before[k] for k in after
                    if k != "max_coalesced"}
            line["max_coalesced"] = after["max_coalesced"]
            line["generate_wall_s"] = wall
            with open(out_path, "a") as f:
                f.write(json.dumps(line) + "\n")
    Store.generate_ec_shards = generate


if __name__ == "__main__":
    from benchmark import served_volume
    out_path, control_dir = sys.argv[1], sys.argv[2]
    counted = served_volume.CompileCount()
    served_volume.warm(control_dir)
    plant(out_path)
    served_volume.serve(control_dir, sys.argv[3:], counted)
