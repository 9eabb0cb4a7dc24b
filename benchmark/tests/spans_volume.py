"""The volume server with every request sampled, for the runs that show
where the time goes (PERF.md section 5).  Same wrapper as
``benchmark/served_volume.py``; the argument is an output directory,
``defaults=<out-dir>`` or ``planes-off``:

    python3 benchmark/tests/rehearse.py --manifest BENCHMARK.json \\
        --require-platform tpu --wrapper benchmark.tests.spans_volume:<out-dir> \\
        --workload reads.degraded1 --seed 7 --seconds 10 --trace 1

- ``<out-dir>``: the server's head sample is 1.0 (every request records
  its stages in the flight recorder, whose ring is made large enough for
  a window), and round the window's device trace the wrapper keeps what
  the harness would remove with the work directory: at ``trace.start``
  ``batcher.start.json`` (``/admin/ec/batcher`` and the monotonic clock);
  at ``trace.stop`` ``batcher.stop.json``, ``traces.json``
  (``/debug/traces``), ``shard_stat.<vid>.json`` and the profile
  directory (``profile/``), all under ``<out-dir>``;
- ``defaults=<out-dir>``: the same files are kept, but the head sample
  stays at the CLI's default (1%): the counters and the device trace of
  the state the benchmark measures, without the cost of the spans;
- ``planes-off``: the planes that ride every request by default are
  switched off through the keyword arguments ``VolumeServer`` has — the
  tracer and the 19 Hz wall sampler (the hot-key sketch has no switch);
  nothing is copied.  Against the same seeds at the defaults this is what
  those planes cost a read.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

RING = 400_000           # spans: ~3,400 a second of reads at sample 1.0
MAX_VIDS = 8


def sample_everything() -> None:
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils import tracing

    real_init = VolumeServer.__init__

    def init(self, *a, **kw):
        kw["trace_sample"] = 1.0
        real_init(self, *a, **kw)
        self.tracer = tracing.Tracer(node=self.tracer.node,
                                     sample_rate=1.0, capacity=RING)
        self.http.tracer = self.tracer
    VolumeServer.__init__ = init


def keep_round_the_trace(out_dir: str, port: int) -> None:
    import jax
    from seaweedfs_tpu.utils.httpd import http_json

    def get(path: str):
        return http_json("GET", f"http://127.0.0.1:{port}{path}",
                         timeout=120)

    def keep(name: str, obj) -> None:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(obj, f)

    real_start, real_stop = jax.profiler.start_trace, jax.profiler.stop_trace
    state = {"dir": None}

    def start_trace(log_dir, *a, **kw):
        real_start(log_dir, *a, **kw)
        state["dir"] = log_dir
        keep("batcher.start.json", {"monotonic": time.monotonic(),
                                    "batcher": get("/admin/ec/batcher")})

    def stop_trace():
        keep("batcher.stop.json", {"monotonic": time.monotonic(),
                                   "batcher": get("/admin/ec/batcher")})
        real_stop()
        keep("traces.json", get(f"/debug/traces?limit={RING}"))
        for vid in range(1, MAX_VIDS + 1):
            try:
                keep(f"shard_stat.{vid}.json",
                     get(f"/admin/ec/shard_stat?volumeId={vid}"))
            except Exception:  # noqa: BLE001 — no such EC volume
                pass
        if state["dir"]:
            shutil.copytree(state["dir"], os.path.join(out_dir, "profile"),
                            dirs_exist_ok=True)

    jax.profiler.start_trace = start_trace
    jax.profiler.stop_trace = stop_trace


def planes_off() -> None:
    from seaweedfs_tpu.server.volume_server import VolumeServer
    real_init = VolumeServer.__init__

    def init(self, *a, **kw):
        kw["tracing_enabled"] = False
        kw["profile_hz"] = 0.0
        real_init(self, *a, **kw)
    VolumeServer.__init__ = init


if __name__ == "__main__":
    from benchmark import served_volume
    arg, control_dir, cli_argv = sys.argv[1], sys.argv[2], sys.argv[3:]
    counted = served_volume.CompileCount()
    served_volume.warm(control_dir)
    if arg == "planes-off":
        planes_off()
    else:
        if not arg.startswith("defaults="):
            sample_everything()
        keep_round_the_trace(
            arg.removeprefix("defaults="),
            int(cli_argv[cli_argv.index("-port") + 1]))
    served_volume.serve(control_dir, cli_argv, counted)
