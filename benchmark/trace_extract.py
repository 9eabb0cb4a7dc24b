"""Child process: the profiler's ``.xplane.pb`` -> the events of the
device planes, as JSON.

    JAX_PLATFORMS=cpu python -m benchmark.trace_extract <log-dir> <out.json>

Runs after the volume server has exited (reading a trace needs ``jax``,
which the benchmark's parent never imports, and nothing here touches a
device).  Keeps every event of every plane whose name starts with
``/device:``; of the host plane only the XLA CPU client's executor
threads, marked ``"device": false``, so that a rehearsal on the CPU
backend has something to reduce.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

HOST_XLA_LINE = "tf_XLAPjRtCpuClient"
# "%fusion.9 = u32[1,1,262144]{2,1,0:T(1,128)S(1)} fusion(u32[...": the
# trace names an operation by its whole HLO line; keep its name and the
# shape of its (first) result, which tells one program's from another's
HLO_LINE = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(name: str) -> str:
    m = HLO_LINE.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def extract(log_dir: str) -> dict:
    import jax
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = []
    for path in paths:
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            on_device = plane.name.startswith("/device:")
            lines = []
            for line in plane.lines:
                if not on_device and not line.name.startswith(HOST_XLA_LINE):
                    continue
                events = [[short_name(e.name), int(e.start_ns),
                           int(e.duration_ns)]
                          for e in line.events if e.duration_ns > 0]
                if events:
                    lines.append({"name": line.name, "events": events})
            if lines:
                planes.append({"name": plane.name, "device": on_device,
                               "lines": lines})
    return {"xplane_bytes": sum(os.path.getsize(p) for p in paths),
            "planes": planes}


if __name__ == "__main__":
    out = extract(sys.argv[1])
    with open(sys.argv[2], "w") as f:
        json.dump(out, f)
