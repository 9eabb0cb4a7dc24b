"""The volume server of a benchmark run: ``seaweedfs_tpu.cli.main``,
unchanged, in the main thread of a process that the benchmark can ask
for a device trace.

    python -m benchmark.served_volume <control-dir> volume -ecBatcher ...

Only the process that holds the chip can trace it, and that process is
the CLI volume server; nothing in the program calls ``jax.profiler``.
So this wrapper, before it hands over to the CLI,

1. compiles (or loads from the persistent cache) exactly the shapes
   ``<control-dir>/warm.json`` lists (``encode`` and ``apply``, each a
   list of ``[B, columns]``), by calling the two batch entry points of
   the mesh coder of the scheme that ``warm.json``'s ``code`` names (the
   spec string the configuration asks the program for; ``""`` or absent:
   the server's default).  Their jitted functions are cached per (scheme,
   mesh), so the server's scheduler finds them compiled.  ``warm.done``
   reports the programs, the ``spec`` they were of and, where the program
   has no device coder of that scheme, ``unwarmed`` with the reason;
2. starts a control thread that watches ``<control-dir>`` for command
   files and answers each with ``<command>.done``:
   ``trace.start`` (body: the log directory), ``trace.stop``, ``stats`` (peak bytes in use per device, and
   how many programs this process has compiled or loaded from the
   compile cache so far, counted by a ``jax.monitoring`` listener);
3. calls ``cli.main(argv)``.  The served path is the CLI's; the wrapper
   adds nothing to a request.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

POLL_S = 0.02
# device operations and XLA's own host spans; no Python frames (the
# program has no annotations of its own yet: PERF.md section 7)
HOST_TRACER_LEVEL = 1
PYTHON_TRACER_LEVEL = 0


def _write_json(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


class CompileCount:
    """Every ``backend_compile`` of this process (a compile or a load
    from the persistent cache), counted from the first."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration


def served_coder(scheme, mesh_coder):
    """The coder the server hands a volume of ``scheme`` under
    ``-ecBatcher``, by the program's own seam: the facade the server
    builds (``BatchCoder`` over an ``EcBatchScheduler``) asked
    ``for_scheme``.  The wrapper does not know the rule; it asks."""
    from seaweedfs_tpu.parallel.batcher import BatchCoder, EcBatchScheduler
    scheduler = EcBatchScheduler(mesh_coder=mesh_coder)
    try:
        return BatchCoder(scheduler).for_scheme(scheme)
    finally:
        scheduler.stop()


def warm(control_dir: str) -> None:
    """Compile the cell's own shapes and no others, under the cell's own
    code: ``warm.json``'s ``code`` is the spec string the configuration
    asks the program for (``""``: the server's default), read by the
    program's one parser, and the device coder warmed is of THAT scheme:
    its static-matrix encode program, its apply program with its own k
    and a rebuild matrix of its own, on zeros.

    Where the server would serve the scheme from a host coder
    (``served_coder`` reports no device: LRC on this tree) nothing is
    warmed and the report says so under ``unwarmed``; the run goes on,
    for the cell to fail on its counts (``mesh_dispatches_in_window``,
    ``cpu_batches``) and not here."""
    import numpy as np
    from seaweedfs_tpu.models.coder import code_spec_name, parse_code_spec
    from seaweedfs_tpu.ops.rs_mesh import MeshCoder
    from seaweedfs_tpu.parallel import mesh as mesh_mod
    with open(os.path.join(control_dir, "warm.json")) as f:
        shapes = json.load(f)
    t0 = time.monotonic()
    cache_dir = mesh_mod.ensure_compile_cache()
    scheme = parse_code_spec(shapes.get("code", ""))
    default = MeshCoder()
    coder = default if scheme == default.scheme else MeshCoder(scheme)
    k = scheme.data_shards
    took = []
    report = {"spec": code_spec_name(scheme), "compile_cache_dir": cache_dir,
              "device": coder.device_report()}
    served = served_coder(scheme, default)
    device_of = getattr(served, "device_report", None)
    if device_of is None or device_of() is None:
        report["unwarmed"] = (f"the server serves {scheme!r} from "
                              f"{type(served).__name__}, a host coder: the "
                              "program has no device coder of this scheme")
        shapes = {}
    for b, n in shapes.get("encode", []):
        t = time.monotonic()
        coder.encode_batch(np.zeros((b, k, n), dtype=np.uint8))
        took.append(["encode", b, n, round(time.monotonic() - t, 3)])
    if shapes.get("apply"):
        present = list(range(1, scheme.total_shards))
        mat = coder.rebuild_matrix(present, [0])
        for b, n in shapes["apply"]:
            t = time.monotonic()
            coder.rebuild_batch(np.zeros((b, k, n), dtype=np.uint8),
                                [mat] * b)
            took.append(["apply", b, n, round(time.monotonic() - t, 3)])
    _write_json(os.path.join(control_dir, "warm.done"),
                {"warm_s": time.monotonic() - t0, "programs": took,
                 **report})


def _stats(compiles: CompileCount) -> dict:
    from seaweedfs_tpu.parallel import mesh as mesh_mod
    out = []
    for d in mesh_mod.devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id, "peak_bytes_in_use":
                    stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit")})
    return {"devices": out, "compiles": compiles.n,
            "compile_s": compiles.seconds}


def _control_loop(control_dir: str, compiles: CompileCount) -> None:
    import jax

    def start(body: str) -> dict:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = PYTHON_TRACER_LEVEL
        opts.host_tracer_level = HOST_TRACER_LEVEL
        jax.profiler.start_trace(body, profiler_options=opts)
        return {}

    def stop(_body: str) -> dict:
        jax.profiler.stop_trace()
        return {}

    commands = {"trace.start": start, "trace.stop": stop,
                "stats": lambda _body: _stats(compiles)}
    while True:
        for name, fn in commands.items():
            path = os.path.join(control_dir, name)
            if not os.path.exists(path):
                continue
            with open(path) as f:
                body = f.read()
            os.remove(path)
            t0 = time.monotonic()
            try:
                reply = fn(body)
            except Exception as e:  # noqa: BLE001 — told to the parent
                reply = {"error": f"{type(e).__name__}: {e}"}
            reply["took_s"] = time.monotonic() - t0
            _write_json(path + ".done", reply)
        time.sleep(POLL_S)


def serve(control_dir: str, cli_argv: list[str],
          compiles: CompileCount) -> None:
    threading.Thread(target=_control_loop, args=(control_dir, compiles),
                     daemon=True, name="bench-control").start()
    from seaweedfs_tpu import cli
    cli.main(cli_argv)


if __name__ == "__main__":
    counted = CompileCount()
    warm(sys.argv[1])
    serve(sys.argv[1], sys.argv[2:], counted)
