"""One line from a result line on stdin: correct, metrics, compared numbers,
and (control runs) the program's and the planted fault's readings."""
import json
import sys


def nums(d):
    return " ".join(f"{k}={v:.4g}" if v is not None else f"{k}=None"
                    for k, v in d.items())


try:
    d = json.loads(sys.stdin.read().strip().splitlines()[-1])
except (ValueError, IndexError):
    print("no result line")
    sys.exit(0)
m = " ".join(f"{k}={v['value']:.6g}" for k, v in d["metrics"].items())
c = nums({k: v["value"] for k, v in d["check"].items()})
dev, info = d["device"], d.get("info") or {}
extra = ""
if "busy_s" in dev:
    extra = f" busy={dev['busy_s']:.3f}/{dev['window_s']:.3f}"
groups = {"program": info.get("program"), "half_batch": info.get("half_batch"),
          "not_compared": info.get("not_compared")}
groups.update({f"control {q}": v
               for q, v in (info.get("controls") or {}).items()})
groups["marks_s"] = info.get("marks_s")
for key, numbers in groups.items():
    if numbers:
        extra += f" | {key}: {nums(numbers)}"
if "engine" in info:
    extra += f" | engine: {nums(info['engine'])}"
if "reference_s" in info:
    extra += f" | reference_s={info['reference_s']:.1f}"
print(f"correct={d['correct']} n={d['attempted']} fail={d['failed']} {m} | {c} "
      f"| peak={dev['memory_peak_bytes'] / 1e9:.2f}GB{extra}")
