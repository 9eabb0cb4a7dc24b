"""From the events of a device trace to the numbers the metrics read.

Input: what ``trace_extract.extract`` gives.  A device plane is one chip.
Its ``XLA Ops`` line holds one event per operation that ran on the chip;
``XLA Modules`` one per compiled program (a program's event spans its
operations and the gaps between them).  Nothing here knows a kernel's
name: the readers under ``metrics/`` choose by name.

- busy: the union of the intervals in which an operation ran, per plane,
  averaged over the planes (chips) that ran anything;
- ops / modules: summed durations by name, averaged over the planes;
- gaps: the longest intervals in which no operation ran, each named by
  the operation that ended it (the program has no host annotations yet,
  so what the host was doing in a gap cannot be said; PERF.md section 7).
"""

from __future__ import annotations

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union_seconds(intervals: list[tuple[int, int]]) -> float:
    """Total length of the union of [start, end) intervals, in seconds
    (inputs in nanoseconds)."""
    total = 0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def longest_gaps(events: list[list], top: int = 10) -> list[list]:
    """[[name of the operation after the gap, seconds]], longest first."""
    gaps = []
    cur_e = None
    for name, s, d in sorted(events, key=lambda ev: ev[1]):
        if cur_e is not None and s > cur_e:
            gaps.append([f"before {name}", (s - cur_e) / 1e9])
        cur_e = s + d if cur_e is None else max(cur_e, s + d)
    gaps.sort(key=lambda g: -g[1])
    return gaps[:top]


def _sum_by_name(events: list[list]) -> dict[str, float]:
    out: dict[str, float] = {}
    for name, _s, d in events:
        out[name] = out.get(name, 0.0) + d / 1e9
    return out


def _op_events(plane: dict) -> list[list]:
    """The plane's operations; none where no program ran on it.  Programs
    without an ``XLA Ops`` line are an error: a module's span holds the
    gaps between its operations, and taking it for an operation would
    count those gaps as busy."""
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    if OPS_LINE not in lines and MODULES_LINE in lines:
        raise ValueError(f"device plane {plane['name']!r} has programs but "
                         f"no {OPS_LINE!r} line; it has {sorted(lines)}")
    return lines.get(OPS_LINE, [])


def reduce(extracted: dict, allow_host: bool = False) -> dict | None:
    """None where no operation ran on a device plane (with
    ``allow_host`` a rehearsal takes the CPU client's threads as one)."""
    planes = [p for p in extracted["planes"] if p["device"]]
    if not planes and allow_host:
        host = [p for p in extracted["planes"] if not p["device"]]
        # the CPU client's executor threads together stand for one chip
        if host:
            planes = [{"name": "/host:xla-cpu", "device": False,
                       "lines": [{"name": OPS_LINE, "events": [
                           ev for p in host for ln in p["lines"]
                           for ev in ln["events"]]}]}]
    per_plane = []
    for plane in planes:
        ops = _op_events(plane)
        if not ops:
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        modules = lines.get(MODULES_LINE, ops)
        per_plane.append({
            "name": plane["name"],
            "busy_s": union_seconds([(s, s + d) for _n, s, d in ops]),
            "span_s": (max(s + d for _n, s, d in ops)
                       - min(s for _n, s, _d in ops)) / 1e9,
            "ops": _sum_by_name(ops),
            "modules": _sum_by_name(modules),
            "module_calls": len(modules),
            "gaps": longest_gaps(ops)})
    if not per_plane:
        return None
    n = len(per_plane)

    def mean_by_name(key: str) -> dict[str, float]:
        out: dict[str, float] = {}
        for p in per_plane:
            for name, v in p[key].items():
                out[name] = out.get(name, 0.0) + v / n
        return out

    ops = mean_by_name("ops")
    modules = mean_by_name("modules")
    gaps = sorted((g for p in per_plane for g in p["gaps"]),
                  key=lambda g: -g[1])[:10]
    return {
        "planes": [p["name"] for p in per_plane],
        "busy_s": sum(p["busy_s"] for p in per_plane) / n,
        "span_s": max(p["span_s"] for p in per_plane),
        "ops_s": sum(ops.values()),
        "modules_s": sum(modules.values()),
        "module_calls": sum(p["module_calls"] for p in per_plane) / n,
        "ops": ops,
        "modules": modules,
        "top_ops": [[k, v] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:10]],
        "gaps": gaps}
