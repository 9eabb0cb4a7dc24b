"""The spreads of a cell's two sets, as measure_sets.sh left them under
``chiprun_out/<cell>/``: per metric and set the median and the distance
between the quartiles as a share of it (``statistics.quantiles``, n=4),
and five times the wider one, which is what the bound is set from.

    python3 benchmark/tests/scripts/spread.py <cell>
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
    for name, m in result["metrics"].items():
        sets.setdefault(name, {}).setdefault(
            os.path.basename(path)[0], []).append(m["value"])
for name, by_set in sets.items():
    rows = []
    for s in sorted(by_set):
        values = by_set[s]
        q = statistics.quantiles(values, n=4)
        med = statistics.median(values)
        rows.append((s, round(med, 4), round((q[2] - q[0]) / med, 4),
                     [round(v, 3) for v in values]))
    widest = max(r[2] for r in rows)
    print(name, "widest spread", widest, "-> bound ~", round(5 * widest, 3))
    for r in rows:
        print("   ", r)
    if len(rows) == 2:
        print("    second/first median", round(rows[1][1] / rows[0][1], 4))
