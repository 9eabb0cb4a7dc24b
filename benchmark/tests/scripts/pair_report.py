"""A cell on the parent and on the change, as families_on_chip.sh's
``pairs`` left them (<prefix>.<side>.<plain|traced>.out / .err): what
has to be the SAME on both sides (the names and limits compared, the
shapes the wrapper warmed, which metrics a run reports), and the
end-to-end metrics side by side.

    python3 benchmark/tests/scripts/pair_report.py chiprun_out/families/<cell>
"""

import ast
import json
import re
import sys

prefix = sys.argv[1]


def result(side: str, kind: str):
    lines = open(f"{prefix}.{side}.{kind}.out").read().strip().splitlines()
    return json.loads(lines[-1]) if lines else None


def warmed(side: str, kind: str):
    """[kind, B, columns] of every program the wrapper warmed (the
    seconds each took left out)."""
    for line in open(f"{prefix}.{side}.{kind}.err"):
        m = re.match(r"\[servers\] ready; warm-up [^:]*: (\[.*?\]); compile",
                     line)
        if m:
            return [p[:3] for p in ast.literal_eval(m.group(1))]
    return None


for kind in ("plain", "traced"):
    p, c = result("parent", kind), result("change", kind)
    if p is None or c is None:
        print(kind, "NO RESULT on", "parent" if p is None else "change")
        continue
    limits = [{k: (v["op"], v["limit"]) for k, v in r["compared"].items()}
              for r in (p, c)]
    shapes = [warmed(side, kind) for side in ("parent", "change")]
    print(f"-- {kind}: correct {p['correct']} / {c['correct']}; compared "
          f"names and limits the same: {limits[0] == limits[1]}; shapes "
          f"warmed the same: {shapes[0] == shapes[1]} {shapes[1]}; "
          f"metrics reported the same: "
          f"{sorted(p['metrics']) == sorted(c['metrics'])} "
          f"({len(c['metrics'])}); memory_peak_bytes "
          f"{p['device']['memory_peak_bytes']} / "
          f"{c['device']['memory_peak_bytes']}")
    for name in c["metrics"]:
        a = p["metrics"].get(name, {}).get("value")
        b = c["metrics"][name]["value"]
        print(f"   {name}: {a} -> {b}")
    if kind == "traced":
        print("   of this run:", p["end_to_end_of_this_run"], "->",
              c["end_to_end_of_this_run"])
