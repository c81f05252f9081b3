"""Are the serving engine's stalls the interpreter's garbage collections?

Runs one cell once, as ``benchmark/run.py`` does, with ``gc.callbacks``
timing every collection from outside the harness, and prints the pauses of
10 ms and more (seconds into the window, generation, ms) beside the run's
``info.slow`` (the requests 3 % and more over the median latency).

    python3 benchmark/tools/gc_probe.py --workload <name> --seed <n> --seconds <s>
"""
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402


def main():
    pauses, began = [], {}

    def on_gc(phase, info):
        now = time.perf_counter()
        if phase == "start":
            began[info["generation"]] = now
        else:
            t0 = began.pop(info["generation"], now)
            if now - t0 >= 0.010:
                pauses.append((t0 - run.T_START, info["generation"],
                               round((now - t0) * 1e3, 1)))

    gc.callbacks.append(on_gc)
    line, _, _ = run.run_cell(run.parse_args(sys.argv[1:] + ["--trace", "0"]))
    gc.callbacks.remove(on_gc)
    opens = line["info"]["marks_s"]["window_opens"]
    closed = line["info"]["marks_s"]["window_closed"]
    print(json.dumps({
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "correct": line["correct"], "slow": line["info"].get("slow"),
        "gc_pauses_in_window": [(round(t - opens, 2), gen, ms)
                                for t, gen, ms in pauses
                                if opens <= t <= closed],
        "gc_pauses_outside": len([p for p in pauses
                                  if not opens <= p[0] <= closed])}))


if __name__ == "__main__":
    main()
