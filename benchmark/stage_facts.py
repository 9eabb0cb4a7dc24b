"""The program's stages in a device trace's host plane, as the facts a
per-layer reader reads (``facts["host"]``), and what the host was doing
in the device's longest idle gaps.

A stage (``seaweedfs_tpu/utils/tracing.stage``) is a ``TraceAnnotation``,
so a device trace holds it per host thread, on the device planes' clock;
its metadata (``http.request``'s ``queue_us``) rides beside it as the
event's stats.  ``extract`` takes the stages out of the ``.xplane.pb``
(``host_spans.STAGE_PREFIXES`` and the HTTP edge's ``http.``), each as
``[name, start_ns, duration_ns]`` with a fourth element, ``{key:
number}``, where it has metadata.  Nothing here enters ``planes``:
``trace_reduce.reduce(allow_host=True)`` folds every non-device plane of
``trace_extract``'s output into one ops line.

``facts`` gives, over the stages' events: ``self_s`` (stage -> self
seconds, each instant of a thread to its innermost stage), ``total_s``
(stage -> summed durations), ``count`` (stage -> events) and ``meta``
(stage -> {key: sum}).  ``EDGE_METRICS`` are the three per-layer metrics
of the volume server's HTTP edge on the ``named_ratio`` reader over
them.

NOT wired into the benchmark (PR 37 may add files to it and edit none):
the next ``benchmark`` PR adds ``out["host"] = stage_facts.extract(...)``
to ``trace_extract.extract``, ``facts["host"] = stage_facts.facts(...)``
and the ``idle_line`` log to ``run.run_cell``, and ``EDGE_METRICS`` as
``metrics/<name>.json`` with their entries in ``BENCHMARK.json``
(PERF.md section 7).  Until then ``tests/scripts/edge_report.py`` reads
them from a profile that ``tests/spans_volume.py`` kept.
"""

from __future__ import annotations

import bisect
import glob
import os

from benchmark import host_spans
from benchmark.readers import named_ratio

STAGE_PREFIXES = host_spans.STAGE_PREFIXES + ("http.",)
REQUEST = "http.request"

EDGE_METRICS = {
    # the selector saw the socket readable -> a worker began the request
    # (the pool's queue and the interpreter lock), over requests
    "edge_handoff_ms.read": {
        "num": "host/meta/" + REQUEST, "prefix": "queue_us",
        "den": "host/count/" + REQUEST, "scale": 0.001},
    # a worker began the request -> its park check: the server's time
    "request_ms.read": {
        "num": "host/total_s", "prefix": REQUEST,
        "den": "host/count/" + REQUEST, "scale": 1000},
    # of that, the edge's own work: self time of every http.* stage
    # (head, the frame and gates, flush, park check, the selector's turns)
    "edge_self_ms.read": {
        "num": "host/self_s", "prefix": "http.",
        "den": "host/count/" + REQUEST, "scale": 1000},
}


def extract(log_dir: str) -> dict:
    """``{"lines": [{"name", "events"}]}``: the stages of every host
    thread that has any.  Needs ``jax`` to read the file and touches no
    device: run it where ``trace_extract`` runs."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    lines = []
    for path in paths:
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                continue
            for i, line in enumerate(plane.lines):
                events = []
                for e in line.events:
                    if not e.name.startswith(STAGE_PREFIXES):
                        continue
                    ev = [e.name, int(e.start_ns), int(e.duration_ns)]
                    meta = {k: v for k, v in e.stats
                            if isinstance(v, (int, float))}
                    if meta:
                        ev.append(meta)
                    events.append(ev)
                if events:
                    lines.append({"name": f"{line.name}/{i}",
                                  "events": events})
    return {"lines": lines}


def plain(host: dict) -> dict:
    """The same lines with three-element events, as ``host_spans``
    takes them."""
    return {"lines": [{"name": ln["name"],
                       "events": [ev[:3] for ev in ln["events"]]}
                      for ln in host["lines"]]}


def facts(host: dict) -> dict:
    out: dict = {"self_s": {}, "total_s": {}, "count": {}, "meta": {}}
    for ln in host["lines"]:
        for name, v in host_spans.self_seconds(
                [ev[:3] for ev in ln["events"]]).items():
            out["self_s"][name] = out["self_s"].get(name, 0.0) + v
        for ev in ln["events"]:
            name = ev[0]
            total = out["total_s"]
            total[name] = total.get(name, 0.0) + ev[2] / 1e9
            out["count"][name] = out["count"].get(name, 0) + 1
            if len(ev) > 3:
                sums = out["meta"].setdefault(name, {})
                for k, v in ev[3].items():
                    sums[k] = sums.get(k, 0) + v
    return out


def edge_metrics(host_facts: dict) -> dict:
    """The three metrics as their readers would give them; None where
    the trace holds no ``http.`` stage (a program without them)."""
    f = {"host": host_facts}
    return {name: named_ratio.read(f, params)
            for name, params in EDGE_METRICS.items()}


def idle_by_host_stage(device_events: list[list], host: dict,
                       top: int = 10) -> dict | None:
    """``host_spans.attribute_gaps`` over the device's idle time, and
    for each of its ``top`` longest gaps the request threads' stages
    open inside it (seconds of the gap under each, summed over threads)
    and how many stage events of ANY thread began inside it: a long gap
    in which none began is the whole process standing still (PERF.md
    section 7, S13).  None where the host plane has no dispatcher."""
    host = plain(host)
    gaps = host_spans.attribute_gaps(device_events, host)
    if gaps is None:
        return None
    disp = next(ln for ln in host["lines"]
                if any(ev[0] == host_spans.IDLE for ev in ln["events"]))
    requests = [host_spans.innermost(ln["events"])
                for ln in host["lines"] if ln is not disp]
    starts = sorted(ev[1] for ln in host["lines"] for ev in ln["events"])
    longest = []
    for a, b in sorted(host_spans.idle_gaps(device_events),
                       key=lambda g: g[0] - g[1])[:top]:
        by: dict[str, float] = {}
        for segs in requests:
            for name, v in host_spans._overlap(segs, [(a, b)])[0].items():
                by[name] = by.get(name, 0.0) + v
        began = bisect.bisect_left(starts, b) - bisect.bisect_left(starts, a)
        longest.append({"s": (b - a) / 1e9, "began": began,
                        "request_stages_s": dict(sorted(
                            by.items(), key=lambda kv: -kv[1])[:4])})
    gaps["longest_with_requests"] = longest
    return gaps


def idle_line(device_events: list[list], host: dict) -> str:
    """The ``[trace] idle by host stage`` log line; "" without a
    dispatcher."""
    g = idle_by_host_stage(device_events, host)
    if g is None:
        return ""
    by = ", ".join(f"{k} {v:.3f}" for k, v in sorted(
        g["by_dispatcher_stage"].items(), key=lambda kv: -kv[1]))
    longest = "; ".join(
        f"{x['s'] * 1e3:.1f} ms ({x['began']} stages began: "
        + ", ".join(f"{k} {v * 1e3:.1f}"
                    for k, v in x["request_stages_s"].items()) + ")"
        for x in g["longest_with_requests"])
    return (f"[trace] idle by host stage: {g['idle_s']:.3f}s in "
            f"{g['gaps']} gaps, by the dispatcher's stage (s): {by}; "
            f"the longest, with the request stages open in them "
            f"(thread-ms): {longest}")
