"""What the host was doing while the device sat idle.

The program's stages (``seaweedfs_tpu/utils/tracing.stage``: ``ec.*``,
``store.*``, ``volume.*``) are ``TraceAnnotation``s, so a device trace
holds them in its host plane, per thread, on the clock of the device
planes.  ``extract_host`` takes them out of the ``.xplane.pb``;
``attribute_gaps`` lays them over the device's idle gaps:

- first the DISPATCHER thread's stages (the line that holds
  ``ec.batch.idle``): they partition its time, so every instant of a gap
  falls under exactly one of idle, hold, stack, pad, launch, fetch,
  unpack, demux, the rest of dispatch, or no stage at all;
- then, for the part of the gaps under ``ec.batch.idle`` (the dispatcher
  had nothing to do), the stages the REQUEST threads were in: several
  threads run at once, so these shares may overlap and add up to more
  than the time they explain.

A stage's self time is its length minus what its child stages cover:
every instant of a thread's line goes to the innermost stage open there.

Not imported by ``run.py`` (which may not change in the PR that brings
this): ``tests/scripts/spans_on_chip.sh`` uses it, and the next
``benchmark`` issue can wire it into ``breakdown.idle_gaps``.
"""

from __future__ import annotations

import glob
import os

STAGE_PREFIXES = ("ec.", "store.", "volume.")
IDLE = "ec.batch.idle"
NO_STAGE = "(no stage)"


def extract_host(log_dir: str) -> dict:
    """``{"lines": [{"name", "events": [[name, start_ns, duration_ns]]}]}``
    — the program's stages of every host thread that has any.  Needs
    ``jax`` (to read the file), touches no device: run it where
    ``trace_extract`` runs, after the servers have exited."""
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
                # "name#key=value#": a TraceMe's metadata rides its name
                events = [[e.name.split("#", 1)[0], int(e.start_ns),
                           int(e.duration_ns)] for e in line.events
                          if e.name.startswith(STAGE_PREFIXES)]
                if events:
                    lines.append({"name": f"{line.name}/{i}",
                                  "events": events})
    return {"xplane_bytes": sum(os.path.getsize(p) for p in paths),
            "lines": lines}


def innermost(events: list[list]) -> list[tuple[int, int, str]]:
    """One thread's nested stages -> disjoint ``(start, end, name)``
    segments, each instant under the innermost stage open there."""
    out: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []     # (end, name) of open stages
    cur = 0

    def close_until(t: int) -> None:
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
                cur = end

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close_until(start)
        if stack and start > cur:
            out.append((cur, start, stack[-1][1]))
        cur = max(cur, start) if stack else start
        # a child that outlives its parent (clock jitter) is cut to it
        end = min(start + dur, stack[-1][0]) if stack else start + dur
        stack.append((end, name))
    close_until(max((e for e, _n in stack), default=0))
    return out


def self_seconds(events: list[list]) -> dict[str, float]:
    out: dict[str, float] = {}
    for s, e, name in innermost(events):
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def idle_gaps(device_events: list[list],
              window: tuple[int, int] | None = None) -> list[tuple[int, int]]:
    """The intervals of ``window`` (default: first operation's start to
    the last one's end) in which no device operation ran."""
    ivs = sorted((s, s + d) for _n, s, d in device_events)
    if window is None:
        if not ivs:
            return []
        window = (ivs[0][0], max(e for _s, e in ivs))
    gaps = []
    cur = window[0]
    for s, e in ivs:
        if s > cur:
            gaps.append((cur, min(s, window[1])))
        cur = max(cur, e)
        if cur >= window[1]:
            break
    if cur < window[1]:
        gaps.append((cur, window[1]))
    return [(a, b) for a, b in gaps if b > a]


def _overlap(segments: list[tuple[int, int, str]],
             spans: list[tuple[int, int]]) -> tuple[dict[str, float], float]:
    """Seconds of ``spans`` (sorted, disjoint) under each segment's
    name, and the seconds of ``spans`` that were under any."""
    by: dict[str, float] = {}
    covered = 0
    i = 0
    for s, e, name in segments:       # sorted and disjoint by construction
        while i < len(spans) and spans[i][1] <= s:
            i += 1
        j = i
        while j < len(spans) and spans[j][0] < e:
            n = min(e, spans[j][1]) - max(s, spans[j][0])
            if n > 0:
                by[name] = by.get(name, 0.0) + n / 1e9
                covered += n
            j += 1
    return by, covered / 1e9


def _intersect(spans: list[tuple[int, int]],
               segments: list[tuple[int, int, str]],
               name: str) -> list[tuple[int, int]]:
    out = []
    for s, e, n in segments:
        if n != name:
            continue
        for a, b in spans:
            lo, hi = max(a, s), min(b, e)
            if hi > lo:
                out.append((lo, hi))
    return sorted(out)


def attribute_gaps(device_events: list[list], host: dict,
                   window: tuple[int, int] | None = None) -> dict | None:
    """See the module docstring.  ``device_events``: the ``XLA Ops`` of
    one device plane as ``trace_extract`` gives them; ``host``: what
    ``extract_host`` gives.  None where the host plane holds no
    dispatcher line (a program without the stages: the parent commit)."""
    disp = next((ln for ln in host["lines"]
                 if any(ev[0] == IDLE for ev in ln["events"])), None)
    if disp is None:
        return None
    gaps = idle_gaps(device_events, window)
    idle_s = sum(b - a for a, b in gaps) / 1e9
    segments = innermost(disp["events"])
    by_stage, covered = _overlap(segments, gaps)
    if idle_s - covered > 0:
        by_stage[NO_STAGE] = idle_s - covered
    under_idle = _intersect(gaps, segments, IDLE)
    by_request: dict[str, float] = {}
    request_self: dict[str, float] = {}
    for ln in host["lines"]:
        if ln is disp:
            continue
        segs = innermost(ln["events"])
        for name, v in _overlap(segs, under_idle)[0].items():
            by_request[name] = by_request.get(name, 0.0) + v
        for s, e, name in segs:
            request_self[name] = request_self.get(name, 0.0) + (e - s) / 1e9
    longest = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        parts, _c = _overlap(segments, [(a, b)])
        longest.append([(b - a) / 1e9, sorted(
            parts.items(), key=lambda kv: -kv[1])[:3]])
    return {
        "idle_s": idle_s,
        "gaps": len(gaps),
        "by_dispatcher_stage": by_stage,
        "under_a_stage_share": covered / idle_s if idle_s else None,
        "under_idle_s": sum(b - a for a, b in under_idle) / 1e9,
        "under_idle_by_request_stage": by_request,
        "dispatcher_self_s": self_seconds(disp["events"]),
        "request_self_s": request_self,
        "longest_gaps": longest,
    }
