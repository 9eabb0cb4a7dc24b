"""The spreads of a cell's two sets, as measure_sets.sh left them under
``chiprun_out/<cell>/``: per metric and set the median and the distance
between the quartiles as a share of it (``statistics.quantiles``, n=4),
five times the wider one, which is what the bound is set from, and
the mean of the two with each set's run farthest from its median left
out, which is what a bound may not be under twice of.  ``--all``: every
end-to-end quantity a run logged, not only the cell's metrics.

    python3 benchmark/tests/scripts/spread.py <cell>[/<subdirectory>] [--all]
"""

import glob
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
cell = sys.argv[1]
out_dir = os.path.join(HERE, "..", "..", "..", "chiprun_out", cell)
sets: dict[str, dict[str, list[float]]] = {}
for path in sorted(glob.glob(os.path.join(out_dir, "[AB].*.out"))):
    lines = open(path).read().strip().splitlines()
    if not lines:
        print("no result", path)
        continue
    result = json.loads(lines[-1])
    if not result["correct"]:
        print("INCORRECT", path)
    values = {n: m["value"] for n, m in result["metrics"].items()}
    if "--all" in sys.argv:
        values = result["end_to_end_of_this_run"]
    for name, v in values.items():
        sets.setdefault(name, {}).setdefault(
            os.path.basename(path)[0], []).append(v)


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


for name, by_set in sets.items():
    rows, trimmed = [], []
    for s in sorted(by_set):
        values = by_set[s]
        med = statistics.median(values)
        rows.append((s, round(med, 4), round(spread(values), 4),
                     [round(v, 3) for v in values]))
        if len(values) > 4:
            kept = list(values)
            kept.remove(max(values, key=lambda v: abs(v - med)))
            trimmed.append(spread(kept))
    widest = max(r[2] for r in rows)
    print(name, "widest spread", widest, "-> bound ~", round(5 * widest, 3),
          "; without each set's farthest run",
          [round(t, 4) for t in trimmed], "mean",
          round(statistics.mean(trimmed), 4) if trimmed else None)
    for r in rows:
        print("   ", r)
    if len(rows) == 2:
        print("    second/first median", round(rows[1][1] / rows[0][1], 4))
