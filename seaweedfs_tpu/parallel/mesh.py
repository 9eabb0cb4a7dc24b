"""Device discovery and mesh construction for the EC engine.

This module is the SINGLE sanctioned entry point for accelerator
discovery: every ``jax.devices()`` / ``jax.local_devices()`` call in
the tree goes through :func:`devices` (the weedlint
``raw-device-discovery`` rule enforces it).  Centralizing discovery
buys four things the scattered call sites could not:

  - one cached :func:`probe` whose outcome (and classified
    ``fallback_reason`` — device_put / timeout / probe_error) is
    shared by the multichip dry run and the batch scheduler;
  - one :func:`device_report` — the platform, device kind and count a
    coder dispatches to — so a server can say (and a checker can
    require) which hardware the EC math ran on;
  - one :func:`ensure_compile_cache` that places JAX's persistent
    compilation cache before the first ``jit`` of a process;
  - the one mesh: :func:`batch_mesh`, 1-D over the 'batch' axis — the
    cross-volume job axis the MeshCoder / batch scheduler shard over
    (one block-group of work per lane, no collectives).

It is also where the program meets ``jax.profiler`` and
``jax.monitoring``, for the same reason (utils/tracing.py and
utils/httpd.py never import jax): :func:`install_tracing` hands
``TraceAnnotation`` to ``tracing.set_annotator`` and starts the process's
one :class:`CompileWatch`; :func:`device_trace` is the one exporter of a
device trace (``POST /admin/ec/trace``).
"""

from __future__ import annotations

import contextlib
import glob
import os
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seaweedfs_tpu.utils import tracing

_probe_lock = threading.Lock()
_probe_cache: Optional[dict] = None


def devices(n: int | None = None) -> list:
    """The process's accelerator devices (first ``n`` when given).
    THE sanctioned discovery call — everything else routes here."""
    devs = jax.devices()
    return devs if n is None else devs[:n]


def device_count() -> int:
    return len(devices())


def default_backend() -> str:
    return jax.default_backend()


def device_report(devs: Optional[list] = None) -> dict:
    """``{"platform", "device_kind", "count"}`` of ``devs`` (default:
    every device of the process) as JAX reports them — what a coder's
    dispatches land on.  The batch scheduler's stats and the volume
    server's status carry it so "ran on the chip" is checkable from
    outside the one process that holds the chip."""
    if devs is None:
        devs = devices()
    d0 = devs[0]
    return {"platform": d0.platform, "device_kind": d0.device_kind,
            "count": len(devs)}


def cpu_requested() -> bool:
    """True when the CPU backend was asked for BY NAME
    (``JAX_PLATFORMS=cpu``, as the tests and the verify recipe do).
    A device coder that finds itself on the CPU without this refuses
    to start: the default platform search falling through to the CPU
    means the accelerator is missing, not that the operator chose it."""
    # jax.config.jax_platforms starts from the environment variable and
    # follows a later jax.config.update (tests/conftest.py does both)
    first = (jax.config.jax_platforms or "").split(",")[0]
    return first.strip().lower() == "cpu"


def require_accelerator(what: str) -> dict:
    """Start-up gate for anything that was asked to run EC math on the
    device (``-ecBatcher``, ``-coder jax|mesh``): returns the
    :func:`device_report` when the backend is an accelerator, or the
    CPU asked for by name; raises RuntimeError with the reason
    otherwise, so the server fails at start-up instead of serving from
    the CPU for the rest of its life."""
    p = probe()
    if not p["ok"]:
        raise RuntimeError(
            f"{what}: no usable JAX device ({p['fallback_reason']}: "
            f"{p['error']})")
    rep = device_report()
    if rep["platform"] == "cpu" and not cpu_requested():
        raise RuntimeError(
            f"{what} was asked to run on an accelerator but JAX found "
            f"only the CPU backend ({rep['count']} device(s)); set "
            "JAX_PLATFORMS=cpu to run the device path on the CPU on "
            "purpose")
    return rep


_CACHE_DIR_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_cache_lock = threading.Lock()
_cache_dir: Optional[str] = None
_cache_done = False


def ensure_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; call before the first
    ``jit`` in every process that compiles.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here; otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    must not move between runs).  A directory that cannot be created or
    written is reported and the process goes on without a cache.
    Returns the directory in use, or None."""
    global _cache_dir, _cache_done
    with _cache_lock:
        if _cache_done:
            return _cache_dir
        _cache_done = True
        env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if env:
            _cache_dir = env
            return _cache_dir
        path = _CACHE_DIR_DEFAULT
        try:
            os.makedirs(path, exist_ok=True)
            if not os.access(path, os.W_OK | os.X_OK):
                raise PermissionError(f"{path} is not writable")
        except OSError as e:
            from seaweedfs_tpu.utils import glog
            glog.warning("compile cache: cannot use %s (%s); compiling "
                         "without a persistent cache", path, e)
            return None
        jax.config.update("jax_compilation_cache_dir", path)
        _cache_dir = path
        return _cache_dir


class CompileWatch:
    """Every ``backend_compile`` of this process — a compile, or a load
    from the persistent cache — counted and timed from the moment the
    watch was started (``jax.monitoring`` has no per-listener
    unregister, so there is one per process: :func:`install_tracing`).
    A compile under an ambient request span annotates that span: a
    request that waited for the compiler says so in /debug/traces."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event != self.EVENT:
            return
        # compiles are serialized by jax's own compile lock, so this is
        # a single writer at a time
        self.n += 1
        self.seconds += duration
        tracing.annotate("compiled_in_request", round(duration, 3))


_tracing_lock = threading.Lock()
_compile_watch: Optional[CompileWatch] = None


def install_tracing() -> CompileWatch:
    """Called when a device coder is built: from then on every
    ``tracing.stage`` of the process is also a
    ``jax.profiler.TraceAnnotation`` (an atomic load while no profile
    runs; a host event on the profiler's clock, in the same
    ``.xplane.pb`` as the device's, while one does), and backend
    compiles are counted.  Idempotent; returns the process's watch."""
    global _compile_watch
    with _tracing_lock:
        if _compile_watch is None:
            tracing.set_annotator(jax.profiler.TraceAnnotation)
            _compile_watch = CompileWatch()
        return _compile_watch


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Take a device trace into ``log_dir`` for the length of the block:
    device planes plus the host's TraceMe events at level 1 (the
    program's stages, XLA's own spans), no Python frames.  Raises
    RuntimeError where a profile is already running (jax allows one per
    process).  Yields a dict that holds, after the block, the
    ``.xplane.pb`` files written and their total size."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    out: dict = {"dir": log_dir, "xplane": [], "xplane_bytes": 0}
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        out["xplane"] = sorted(glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        out["xplane_bytes"] = sum(os.path.getsize(p)
                                  for p in out["xplane"])


def classify_failure(err: Optional[str]) -> Optional[str]:
    """Map a device/probe failure string onto a stable fallback reason:
    'device_put' (the accelerator rejected the host->device transfer),
    'timeout', else 'probe_error' — the vocabulary of the batch
    scheduler's ``fallback_reason`` and its metrics label."""
    if not err:
        return None
    low = err.lower()
    if "device_put" in low:
        return "device_put"
    if "timeout" in low:
        return "timeout"
    return "probe_error"


def probe(force: bool = False) -> dict:
    """In-process device probe, cached for the life of the process
    (probing is expensive and JAX caches a failed backend init anyway,
    so asking twice cannot change the answer).  Returns::

        {"ok": bool, "backend": str|None, "n_devices": int,
         "error": str|None, "fallback_reason": None|"device_put"|
         "timeout"|"probe_error"}

    The probe enumerates devices and round-trips one tiny device_put.
    It initializes the backend, so only a process that is meant to
    hold the chip calls it (the volume server's coder, the multichip
    dry run, bench.py's device child) — never a parent that goes on to
    start such a process."""
    global _probe_cache
    with _probe_lock:
        if _probe_cache is not None and not force:
            return dict(_probe_cache)
    out: dict = {"ok": False, "backend": None, "n_devices": 0,
                 "error": None, "fallback_reason": None}
    try:
        devs = devices()
        out["backend"] = default_backend()
        out["n_devices"] = len(devs)
        x = np.arange(8, dtype=np.uint32)
        y = np.asarray(jax.device_get(jax.device_put(x, devs[0])))
        if not np.array_equal(x, y):
            raise RuntimeError("device_put round-trip mismatch")
        out["ok"] = True
    except Exception as e:  # noqa: BLE001 — classified, not swallowed
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out["fallback_reason"] = classify_failure(out["error"])
    with _probe_lock:
        _probe_cache = dict(out)
    return dict(out)


def batch_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the cross-volume 'batch' axis — the MeshCoder /
    batch-scheduler topology: independent block-groups of work, one
    slice per device, no collectives."""
    return Mesh(np.array(devices(n_devices)), ("batch",))


def batch_spec(mesh: Mesh, rank: int = 3) -> NamedSharding:
    """NamedSharding splitting the leading (batch) axis of a rank-N
    operand across a batch_mesh."""
    return NamedSharding(mesh, P("batch", *([None] * (rank - 1))))
