"""What two_on_chip.sh's runs left under chiprun_out/two/: per cell the
end-to-end metrics of every plain run, parent beside change by seed, who
won each pair, the medians and the parent's own spread (quartile distance
over median, as the driver takes it); a seal run's job by the wall from
its logged ``pipeline`` replies.

    python3 tests/scripts/pairs_report.py chiprun_out/two
    python3 tests/scripts/pairs_report.py --stats <file.stats.jsonl>
"""

import glob
import json
import os
import re
import statistics
import sys

BETTER = {"seal_mbps": 1, "read_ops": 1, "read_p50_ms": -1,
          "read_p99_ms": -1, "setup_s": -1}


def spread(xs):
    if len(xs) < 2:
        return None
    q = statistics.quantiles(xs, n=4)
    return round((q[2] - q[0]) / statistics.median(xs), 4)


def job_ms(err_path):
    """Median over the run's window calls of (wall - commit) / batches."""
    for line in open(err_path):
        if line.startswith("[window] pipeline of each call"):
            calls = [c.split() for c in line.split("): ", 1)[1].split("; ")]
            return statistics.median(
                (float(c[0]) - float(c[4])) / int(c[5].split("/")[0])
                for c in calls)
    return None


def stats_report(path):
    calls = [json.loads(x) for x in open(path)]
    if not calls:
        print("no calls")
        return

    def med(k):
        return statistics.median(c.get(k, 0) for c in calls)
    n = med("mesh_batches") or 1
    print(f"  {len(calls)} calls; a call: mesh_batches {n:.0f} lone "
          f"{med('lone_dispatches'):.0f} overlapped "
          f"{med('overlapped_dispatches'):.0f} "
          f"({med('overlapped_dispatches') / n:.3f}) cpu "
          f"{med('cpu_batches'):.0f} max_coalesced "
          f"{max(c['max_coalesced'] for c in calls)}; generate "
          f"{med('generate_wall_s') * 1e3:.1f} ms; a job, ms: wait "
          f"{med('wait_s') / n * 1e3:.3f} " + " ".join(
              f"{k} {med('stage_s.' + k) / n * 1e3:.3f}" for k in (
                  "submit", "stack", "pad", "launch", "fetch", "unpack",
                  "demux", "result"))
          + "; loop_s a call, ms: " + " ".join(
              f"{k} {med('loop_s.' + k) * 1e3:.1f}"
              for k in ("idle", "hold", "dispatch")))
    piped = [c for c in calls if c.get("pipeline")]
    if not piped:
        return

    def pmed(k):
        return statistics.median(c["pipeline"].get(k, 0) for c in piped)
    writers = sorted({k for c in piped for k in c if k.startswith("write_rows")})
    print("  the pipeline a call, ms: " + " ".join(
        f"{k} {pmed(k + '_s') * 1e3:.1f}"
        for k in ("wall", "read", "encode", "write", "commit"))
        + " (" + " ".join(f"{k[:-2]} {statistics.median(c.get(k, 0) for c in piped) * 1e3:.1f}"
                          for k in writers) + ")"
        + f"; batches {pmed('batches'):.0f} overlapped "
        f"{pmed('overlapped'):.0f}; io_calls / batches, least and most "
        + " ".join(f"{f(c['pipeline'].get('io_calls', 0) / c['pipeline']['batches'] for c in piped):.2f}"
                   for f in (min, max)))


def main(root):
    runs = {}
    for path in sorted(glob.glob(os.path.join(root, "*.out"))):
        m = re.match(r"(.+)\.([PCV])\.(\d+)\.out$", os.path.basename(path))
        if not m:
            continue
        lines = open(path).read().strip().splitlines()
        if not lines:
            print("NO RESULT", path)
            continue
        r = json.loads(lines[-1])
        vals = {k: v["value"] for k, v in r["metrics"].items()
                if k in BETTER}
        vals["correct"] = r["correct"]
        vals["job_ms"] = job_ms(path[:-4] + ".err")
        runs.setdefault(m.group(1), {}).setdefault(
            int(m.group(3)), {})[m.group(2)] = vals
    for cell, by_seed in runs.items():
        print(f"## {cell}")
        pairs = [(s, v["P"], v["C"]) for s, v in sorted(by_seed.items())
                 if "P" in v and "C" in v]
        for s, v in sorted(by_seed.items()):
            print("  ", s, {side: {k: (round(x, 3) if isinstance(x, float)
                                         else x) for k, x in vals.items()}
                             for side, vals in sorted(v.items())})
        for metric, sign in BETTER.items():
            if not pairs or metric not in pairs[0][1]:
                continue
            p = [a[metric] for _s, a, _b in pairs]
            c = [b[metric] for _s, _a, b in pairs]
            wins = sum(1 for a, b in zip(p, c) if (b - a) * sign > 0)
            mp, mc = statistics.median(p), statistics.median(c)
            sp = spread(p)
            print(f"  {metric}: parent {mp:.3f} -> change {mc:.3f} "
                  f"({(mc / mp - 1) * 100:+.2f}%), change better in "
                  f"{wins} of {len(pairs)} pairs; parent's spread {sp} "
                  f"(= {None if sp is None else round(sp * mp, 2)}), "
                  f"change's {spread(c)}")
        jp = [a["job_ms"] for _s, a, _b in pairs if a["job_ms"]]
        jc = [b["job_ms"] for _s, _a, b in pairs if b["job_ms"]]
        if jp and jc:
            print(f"  a job by the wall, ms: parent "
                  f"{statistics.median(jp):.3f} -> change "
                  f"{statistics.median(jc):.3f}")


if __name__ == "__main__":
    if sys.argv[1] == "--stats":
        stats_report(sys.argv[2])
    else:
        main(sys.argv[1])
