"""Spreads of result lines: python3 benchmark/tools/spread.py FILE.jsonl...

For each file (one set of runs) and each metric: median, the quartile
distance by statistics.quantiles(n=4) as a share of the median."""
import json
import statistics
import sys

for path in sys.argv[1:]:
    rows = [json.loads(x) for x in open(path) if x.strip().startswith("{")]
    names = sorted({k for r in rows for k in r["metrics"]})
    print(f"{path}: {len(rows)} runs, correct={[r['correct'] for r in rows]}")
    for n in names:
        v = [r["metrics"][n]["value"] for r in rows if n in r["metrics"]]
        if len(v) < 2:
            continue
        q = statistics.quantiles(v, n=4)
        med = statistics.median(v)
        print(f"  {n}: median={med:.6g} iqr/median={(q[2] - q[0]) / med:.4%} "
              f"min={min(v):.6g} max={max(v):.6g} first={v[0]:.6g}")
