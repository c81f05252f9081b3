#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names the cell's
configuration (``benchmark/configs/<config>.json``) and traffic
(``benchmark/traffic/<traffic>.json``, whose ``kind`` picks the driver
``benchmark/kinds/<kind>.py``); each per-layer metric is
``benchmark/metrics/<name>.json`` naming a reader
``benchmark/readers/<reader>.py``; the limits of the comparison that decides
``correct`` are ``benchmark/limits/<workload>.json``.  Adding a cell, a
configuration or a metric over an existing reader adds files and entries and
edits none.

The measured path needs the accelerator: without a TPU, or with fewer chips
than the cell asks for, this exits non-zero and prints no result.
``--rehearse-tiny`` walks the same control flow at the traffic file's
``tiny`` sizes on whatever backend there is, and its result line names the
platform it ran on: a rehearsal, never a measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, default=0, choices=[0, 1])
    p.add_argument("--rehearse-tiny", action="store_true",
                   help="tiny sizes on any backend; not a measurement")
    # for the control and the planted faults (tests, benchmark/tools)
    p.add_argument("--fault", default=None)
    p.add_argument("--reference-quant", default=None)
    return p.parse_args(argv)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def peak_row(kind):
    table = load_json(HERE, "peaks.json")
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in benchmark/peaks.json:"
                         " an unknown device is an error, not a default")
    return table[kind]


class MemoryWatch:
    """The fullest chip's peak, from ``memory_stats()``.

    ``peak_bytes_in_use`` is the allocator's live buffers (arrays and loaded
    code) and does not cover a program's temporaries; those are reserved
    apart (``bytes_reserved``: 6.3 GB for the batch-16 train step against
    1.5-1.9 GB in use; PERF.md section 6).  The two peaks need not fall
    together (their sum passed the chip's 16.9 GB in the serve cells), so a
    thread samples ``bytes_in_use + bytes_reserved`` ten times a second
    from set-up to the close of the window, and the peak reported is the
    largest of that and of the two peaks the allocator keeps itself."""

    def __init__(self, devices, period=0.1):
        self.devices, self.period = devices, period
        self.sampled = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _read(self):
        both = own = 0
        for d in self.devices:
            st = d.memory_stats() or {}
            both = max(both, int(st.get("bytes_in_use", 0))
                       + int(st.get("bytes_reserved", 0)))
            own = max(own, int(st.get("peak_bytes_in_use", 0)),
                      int(st.get("peak_bytes_reserved", 0)))
        self.sampled = max(self.sampled, both)
        return own

    def _run(self):
        while not self._stop.wait(self.period):
            self._read()

    def start(self):
        self._thread.start()

    def stop(self):
        """Ends the sampling; -> the peak in bytes."""
        self._stop.set()
        self._thread.join()
        return max(self._read(), self.sampled)


def run_cell(args, on_trace=None):
    """One run of one cell -> (result line, check table, correct).
    ``on_trace(trace_dir, facts)`` is for ``benchmark/tools/trace_dump.py``:
    called on a ``--trace 1`` run before the trace is deleted."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, conf["file"])
    tr = load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = load_json(HERE, "limits", cell["name"] + ".json")["limits"]
    tiny = bool(args.rehearse_tiny)
    if tiny:
        tr = {**tr, **tr.get("tiny", {})}
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])

    import jax

    # A cell's programs (the step, the reference's) pass 192 MiB together,
    # and a smaller cap evicts one while the other is written: every run
    # would then compile.  Where the cache lives stays the machine's choice.
    jax.config.update("jax_compilation_cache_max_size", 4 * 2 ** 30)
    devices = jax.devices()
    platform = devices[0].platform
    if not tiny and (platform != "tpu" or len(devices) < cell["chips"]):
        print(f"benchmark: needs {cell['chips']} TPU chip(s), found "
              f"{len(devices)} {platform} device(s); no result",
              file=sys.stderr)
        raise SystemExit(3)
    chips = cell["chips"] if not tiny else min(cell["chips"], len(devices))
    peaks = peak_row(devices[0].device_kind) if platform == "tpu" else None

    watch = MemoryWatch(devices[:chips])
    watch.start()

    work = os.path.join(ROOT, ".bench_work", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    marks = {}

    def mark(name):
        marks[name] = round(time.perf_counter() - T_START, 3)

    ctx = {"mark": mark, "config": config, "traffic": tr, "seed": args.seed,
           "seconds": seconds, "trace": bool(args.trace), "chips": chips,
           "workdir": work, "tiny": tiny, "t_start": T_START,
           "memory_peak": watch.stop, "fault": args.fault,
           "reference_quant": args.reference_quant}
    kind = importlib.import_module(f"benchmark.kinds.{tr['kind']}")
    try:
        res = kind.run(ctx)
        mark("compared")
        summary = None
        if args.trace:
            from benchmark import trace as trace_mod

            summary = trace_mod.reduce_trace(res["facts"]["trace_dir"],
                                             chips)
            if on_trace is not None:
                on_trace(res["facts"]["trace_dir"], res["facts"])
    finally:
        shutil.rmtree(work, ignore_errors=True)

    from benchmark import check

    metrics = {}
    if not args.trace:
        for m in bench["end_to_end"]:
            if not applies(m, cell["name"]):
                continue
            v = (res["setup_s"] if m["name"] == "setup_s"
                 else res["e2e"].get(m["name"]))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        rctx = {"facts": res["facts"], "trace": summary, "peaks": peaks,
                "config": config, "traffic": tr}
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}")
            v = reader.read(rctx, **spec.get("args", {}))
            if v is not None:          # nothing to read: left out, never 0
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    numbers = {k: v for k, v in res["numbers"].items()
               if not k.startswith("_")}
    correct, table = check.judge(numbers, limits)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": bool(correct), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if tiny:
        line["rehearsal"] = f"tiny sizes on {platform}: not a measurement"
    if summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    line["info"] = dict(res["numbers"].get("_info") or {},
                        not_compared={k: v for k, v in numbers.items()
                                      if k not in table})
    mark("line")
    line["info"]["marks_s"] = marks
    line["check"] = table
    return line, table, correct


def main(argv=None):
    args = parse_args(argv)
    line, table, correct = run_cell(args)
    from benchmark import check

    sys.stdout.flush()
    check.print_table(table, correct)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
