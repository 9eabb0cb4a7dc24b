#!/usr/bin/env python3
"""What the HTTP edge's stages cost a request (PR 37; PERF.md section 6):
the worker's three stage entries as ``httpd._ConnHandler.service`` makes
them (``http.request`` with its clock read and ``queue_us`` while
recording, ``http.head`` and ``http.flush`` inside), over empty bodies,
in microseconds a request, with

- no annotator (a server without a device coder);
- ``jax.profiler.TraceAnnotation`` installed and no profile running (the
  volume server under ``-ecBatcher`` as the benchmark measures it);
- the same while a profile runs (a ``--trace 1`` run).

    python3 benchmark/tests/scripts/edge_cost.py [iterations]

Holds the chip while it runs (it imports jax): run it before the servers.
One JSON object to stdout.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from seaweedfs_tpu.utils import clockctl, tracing  # noqa: E402


def edge(n: int) -> float:
    """Seconds for ``n`` requests' stages."""
    stage, recording, monotonic = (tracing.stage, tracing.recording,
                                   clockctl.monotonic)
    t = time.perf_counter()
    for _ in range(n):
        t0 = monotonic()
        meta = {"queue_us": 0} if recording() else None
        with stage("http.request", meta, t0):
            with stage("http.head"):
                pass
            with stage("http.flush"):
                pass
    return time.perf_counter() - t


def empty(n: int) -> float:
    t = time.perf_counter()
    for _ in range(n):
        pass
    return time.perf_counter() - t


def us_a_request(n: int) -> float:
    best = min(edge(n) - empty(n) for _ in range(5))
    return best / n * 1e6


def main() -> int:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    out = {"iterations": n, "none": us_a_request(n)}
    import jax
    tracing.set_annotator(jax.profiler.TraceAnnotation)
    out["annotator_no_profile"] = us_a_request(n)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["annotator_profiling"] = us_a_request(n // 10)
        finally:
            jax.profiler.stop_trace()
    out["device"] = str(jax.devices()[0].device_kind)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
