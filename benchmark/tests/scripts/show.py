"""One line for a run's result file: python3 show.py <file.out>"""
import json
import sys

lines = open(sys.argv[1]).read().strip().splitlines()
if not lines:
    print("NO RESULT")
    sys.exit()
r = json.loads(lines[-1])
print(r["correct"], r["attempted"], r["failed"],
      {k: round(v["value"], 4) for k, v in r["metrics"].items()},
      {k: v["value"] for k, v in r["compared"].items() if not v["ok"]},
      r["device"].get("memory_peak_bytes"), r["device"].get("busy_s"),
      r["device"].get("window_s"))
