"""Reconstruct distributed trace trees from telemetry logs.

``raft_tpu/obs/trace.py`` emits one ``trace_span`` JSONL record per
span (trace_id / span_id / parent_id + monotonic timing); this script
turns a telemetry directory full of them back into trees and answers
the question flat logs cannot: *where did THIS request's milliseconds
go*::

    python scripts/trace_report.py runs/telemetry --slowest 3
    python scripts/trace_report.py runs/telemetry --trace 7f3a9c2d1b4e8f60
    python scripts/trace_report.py runs/telemetry --perfetto out.json
    python scripts/trace_report.py runs/telemetry --json

Per trace it prints a waterfall (children indented under parents,
offsets relative to the root start) and a **critical-path
attribution**: walking back from each span's end to the child whose
end reaches latest into it, the chain of spans that actually bounded
the end-to-end latency — a hedged request whose losing attempt was
slow but whose winner was fast correctly attributes to the winner.

``--perfetto`` exports Chrome/Perfetto ``trace_event`` JSON (load in
https://ui.perfetto.dev or chrome://tracing).  ``--json`` prints one
check_regression-format line whose config block carries ``critical_path_ms``
(per span name, p95 self-time on the critical path) and
``serve_span_names`` — the inputs for ``scripts/check_regression.py
--max-critical-path-ms`` and its span-coverage check.  ``--tiny``
round-trips a synthetic hedged trace through the real tracer + sink
and reports on it (the CI selftest).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Two spans' monotonic clocks agree only within the same process;
#: cross-process skew plus float rounding means a child may end a hair
#: "after" its parent.  Ends within EPS still count as covered.
EPS_S = 1e-4


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="trace_span JSONL -> trace trees, critical paths, "
                    "Perfetto export")
    p.add_argument("path", nargs="?", default=None,
                   help="telemetry-*.jsonl file or a directory of them")
    p.add_argument("--trace", default=None, metavar="ID",
                   help="report exactly this trace_id")
    p.add_argument("--slowest", type=int, default=5, metavar="N",
                   help="report the N slowest traces by root duration "
                        "(default 5)")
    p.add_argument("--perfetto", default=None, metavar="OUT.json",
                   help="export all loaded traces as Chrome/Perfetto "
                        "trace_event JSON")
    p.add_argument("--json", action="store_true",
                   help="print one check_regression-format JSON line "
                        "(critical_path_ms + serve_span_names in the "
                        "config block) instead of waterfalls")
    p.add_argument("--roofline", action="store_true",
                   help="per-span-name roofline table over the spans "
                        "that carry cost attrs (flops/bytes/mfu, "
                        "attached by the serve engine from "
                        "obs/cost.py), plus the cost-weighted "
                        "critical path")
    p.add_argument("--device-kind", default=None, metavar="KIND",
                   help="classify --roofline against this device's "
                        "peak specs (e.g. 'v5e') instead of the "
                        "current backend's — for reading a "
                        "TPU-captured trace on a laptop")
    p.add_argument("--tiny", action="store_true",
                   help="selftest: synthesize a hedged trace through "
                        "the real tracer, then report on it")
    return p.parse_args(argv)


# ---------------------------------------------------------------------------
# loading + tree building
# ---------------------------------------------------------------------------


def load_spans(path):
    """Every ``trace_span`` record under ``path`` (file or directory)."""
    files = ([path] if os.path.isfile(path)
             else sorted(glob.glob(os.path.join(path, "*.jsonl"))))
    if not files:
        raise SystemExit(f"no .jsonl telemetry under {path!r}")
    spans = []
    for fname in files:
        with open(fname) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn final line of a killed run
                if rec.get("event") == "trace_span":
                    spans.append(rec)
    return spans


def _end(rec):
    return float(rec.get("t_start_mono", 0.0)) + float(
        rec.get("dur_s", 0.0))


def build_traces(spans):
    """``{trace_id: {"spans": {id: rec}, "children": {id: [rec...]},
    "roots": [rec...]}}``.

    A span is an *effective root* when its parent_id is None OR names a
    span absent from the log — the serve handler's root continues a
    client-side span that never reaches this sink (wire propagation),
    and it must still anchor a tree."""
    traces = {}
    for rec in spans:
        t = traces.setdefault(rec["trace_id"],
                              {"spans": {}, "children": {}, "roots": []})
        t["spans"][rec["span_id"]] = rec
    for t in traces.values():
        for rec in t["spans"].values():
            pid = rec.get("parent_id")
            if pid is None or pid not in t["spans"]:
                t["roots"].append(rec)
            else:
                t["children"].setdefault(pid, []).append(rec)
        for kids in t["children"].values():
            kids.sort(key=lambda r: r.get("t_start_mono", 0.0))
        t["roots"].sort(key=lambda r: r.get("t_start_mono", 0.0))
    return traces


def root_of(trace):
    """The trace's primary root: the effective root with the longest
    duration (ties to the earliest start)."""
    if not trace["roots"]:
        return None
    return max(trace["roots"],
               key=lambda r: (float(r.get("dur_s", 0.0)),
                              -float(r.get("t_start_mono", 0.0))))


# ---------------------------------------------------------------------------
# critical path
# ---------------------------------------------------------------------------


def critical_path(trace, root=None):
    """``[(span, self_ms), ...]`` root-first: the chain of spans that
    bounded the root's latency.

    Walk: at each node pick the child whose END reaches latest without
    (meaningfully) exceeding the node's own end — the operation the
    node was still waiting on when it finished.  A hedge's losing
    attempt ends after the root settled, so it is (correctly) skipped.
    Each node's self-time is its duration minus its on-path child's —
    the milliseconds attributable to that node alone."""
    if root is None:
        root = root_of(trace)
    if root is None:
        return []
    path, node = [root], root
    while True:
        kids = trace["children"].get(node["span_id"], [])
        covered = [k for k in kids if _end(k) <= _end(node) + EPS_S]
        if not covered:
            break
        node = max(covered, key=_end)
        path.append(node)
    out = []
    for i, n in enumerate(path):
        child_s = (float(path[i + 1].get("dur_s", 0.0))
                   if i + 1 < len(path) else 0.0)
        self_s = max(float(n.get("dur_s", 0.0)) - child_s, 0.0)
        out.append((n, round(self_s * 1e3, 3)))
    return out


# ---------------------------------------------------------------------------
# human output
# ---------------------------------------------------------------------------

_SKIP_KEYS = {"event", "trace_id", "span_id", "parent_id", "name",
              "t_start", "t_start_mono", "dur_s", "status", "t_wall",
              "t_mono", "process", "step"}


def _attr_str(rec):
    attrs = [f"{k}={v}" for k, v in sorted(rec.items())
             if k not in _SKIP_KEYS]
    return (" [" + " ".join(attrs) + "]") if attrs else ""


def print_waterfall(trace, out=sys.stdout):
    """One tree, children indented, offsets in ms from the root start."""
    root = root_of(trace)
    if root is None:
        return
    t0 = float(root.get("t_start_mono", 0.0))
    on_path = {id(n) for n, _ in critical_path(trace, root)}

    def _one(rec, depth):
        off = (float(rec.get("t_start_mono", 0.0)) - t0) * 1e3
        dur = float(rec.get("dur_s", 0.0)) * 1e3
        status = rec.get("status", "ok")
        mark = "*" if id(rec) in on_path else " "
        flag = "" if status == "ok" else f"  !{status}"
        width = max(24 - 2 * depth, 1)
        print(f"{mark} {'  ' * depth}{rec['name']:<{width}}"
              f" {off:9.2f}ms +{dur:9.2f}ms{flag}{_attr_str(rec)}",
              file=out)
        for kid in trace["children"].get(rec["span_id"], []):
            _one(kid, depth + 1)

    print(f"trace {root['trace_id']}  "
          f"({len(trace['spans'])} spans; * = critical path)", file=out)
    for r in trace["roots"]:
        _one(r, 0)
    print("  critical path: "
          + " > ".join(f"{n['name']}:{ms:g}ms"
                       for n, ms in critical_path(trace, root)),
          file=out)


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------


def perfetto_events(traces):
    """Chrome/Perfetto ``trace_event`` complete events (``ph: "X"``,
    microsecond timestamps).  One "process" per trace so concurrent
    requests don't interleave on a shared track; nesting depth maps to
    the thread id, which renders parents above their children."""
    events = []
    for i, (tid, t) in enumerate(sorted(traces.items())):
        root = root_of(t)
        if root is None:
            continue
        events.append({"ph": "M", "pid": i, "name": "process_name",
                       "args": {"name": f"trace {tid} "
                                        f"({root['name']})"}})

        def _walk(rec, depth, pid=i):
            events.append({
                "ph": "X", "pid": pid, "tid": depth,
                "name": rec["name"],
                "ts": round(float(rec.get("t_start", 0.0)) * 1e6, 1),
                "dur": round(float(rec.get("dur_s", 0.0)) * 1e6, 1),
                "args": {k: v for k, v in rec.items()
                         if k not in ("event",)},
            })
            for kid in t["children"].get(rec["span_id"], []):
                _walk(kid, depth + 1, pid)

        for r in t["roots"]:
            _walk(r, 0)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# bench-format summary (check_regression input)
# ---------------------------------------------------------------------------

#: Root names originated by the serve path — their trees must carry
#: the engine's queue/pad/device spans or serve instrumentation broke
#: (scripts/check_regression.py span-coverage check).
SERVE_ROOTS = ("serve_http", "route")


def _p95(vals):
    vals = sorted(vals)
    return vals[min(int(len(vals) * 0.95), len(vals) - 1)]


def bench_record(traces):
    """One check_regression-format record: ``critical_path_ms`` maps span name
    -> p95 self-time ms over every trace's critical path (what
    ``check_regression --max-critical-path-ms NAME:MS`` gates);
    ``serve_span_names`` lists every span name observed inside
    serve-rooted traces (the coverage check's input)."""
    self_ms, serve_names, errors = {}, set(), 0
    roots = 0
    for t in traces.values():
        root = root_of(t)
        if root is None:
            continue
        roots += 1
        if root.get("status") == "error":
            errors += 1
        for n, ms in critical_path(t, root):
            self_ms.setdefault(n["name"], []).append(ms)
        if root.get("name") in SERVE_ROOTS:
            serve_names.update(r["name"] for r in t["spans"].values())
    return {
        "metric": "trace_report",
        "value": roots,
        "unit": "traces",
        "vs_baseline": 0.0,
        "config": {
            "source": "trace_report",
            "traces_total": roots,
            "traced_error_rate": round(errors / roots, 4) if roots
            else 0.0,
            "critical_path_ms": {name: round(_p95(v), 3)
                                 for name, v in sorted(self_ms.items())},
            "serve_span_names": sorted(serve_names),
        },
    }


# ---------------------------------------------------------------------------
# roofline + cost-weighted critical path (--roofline)
# ---------------------------------------------------------------------------


def _resolve_spec(device_kind=None):
    """Peak specs for the roofline verdicts.  An explicit
    ``--device-kind`` wins (classify a TPU trace offline); otherwise
    the current backend's — degrading to unknown peaks (not an error)
    when no backend is importable, since this is a log-reading tool."""
    from raft_tpu.obs import cost as cost_mod

    try:
        return cost_mod.peak_spec(device_kind)
    except Exception:
        return cost_mod.PeakSpec(str(device_kind or "unknown"),
                                 None, None)


def _span_flops(rec):
    v = rec.get("flops")
    return float(v) if isinstance(v, (int, float)) and v > 0 else None


def roofline_report(traces, spec, out=sys.stdout):
    """Two tables off the cost attrs the engine attaches to its spans
    (``flops``/``bytes`` from the compile-time ledger, ``mfu`` from the
    observed call time — obs/cost.py):

    - per span name: total work, arithmetic intensity and the
      compute/memory verdict against ``spec``'s ridge point, plus the
      observed MFU spread (``-`` throughout on unknown peaks / CPU);
    - the **cost-weighted critical path**: of the FLOPs executed on
      the latency-bounding chains, which span name runs them, at what
      p95 self-time — a span owning most on-path FLOPs at low MFU is
      the optimization target; one owning milliseconds but no FLOPs
      is queueing/host overhead no kernel work will fix."""
    per = {}
    for t in traces.values():
        for rec in t["spans"].values():
            fl = _span_flops(rec)
            if fl is None:
                continue
            row = per.setdefault(rec["name"],
                                 {"n": 0, "flops": 0.0, "bytes": 0.0,
                                  "mfu": []})
            row["n"] += 1
            row["flops"] += fl
            row["bytes"] += float(rec.get("bytes", 0.0) or 0.0)
            if isinstance(rec.get("mfu"), (int, float)):
                row["mfu"].append(float(rec["mfu"]))
    ridge = spec.ridge
    print(f"roofline vs {spec.kind}: "
          f"peak {spec.tflops if spec.tflops else '-'} bf16 TFLOP/s, "
          f"{spec.hbm_gbps if spec.hbm_gbps else '-'} GB/s, "
          f"ridge {f'{ridge:.1f}' if ridge else '-'} flop/byte",
          file=out)
    if not per:
        print("  (no spans carry cost attrs — trace predates the cost "
              "model, or the engine ran with RAFT_TELEMETRY_COST=0)",
              file=out)
        return
    hdr = (f"  {'span':<16} {'n':>4} {'GFLOPs':>10} {'MB':>10} "
           f"{'flop/byte':>10} {'bound_by':>9} {'mfu_p50':>8} "
           f"{'mfu_max':>8}")
    print(hdr, file=out)
    print("  " + "-" * (len(hdr) - 2), file=out)
    for name, row in sorted(per.items(), key=lambda kv: -kv[1]["flops"]):
        ai = row["flops"] / row["bytes"] if row["bytes"] > 0 else None
        bound = ("unknown" if ai is None or ridge is None
                 else "compute" if ai >= ridge else "memory")
        mfu = sorted(row["mfu"])
        p50 = f"{mfu[len(mfu) // 2]:.4f}" if mfu else "-"
        mx = f"{mfu[-1]:.4f}" if mfu else "-"
        print(f"  {name:<16} {row['n']:>4} "
              f"{row['flops'] / 1e9:>10.3f} {row['bytes'] / 1e6:>10.3f} "
              f"{f'{ai:.3f}' if ai is not None else '-':>10} "
              f"{bound:>9} {p50:>8} {mx:>8}", file=out)

    on_path = {}
    total_flops = 0.0
    for t in traces.values():
        for n, ms in critical_path(t):
            row = on_path.setdefault(n["name"],
                                     {"flops": 0.0, "ms": []})
            row["ms"].append(ms)
            fl = _span_flops(n)
            if fl is not None:
                row["flops"] += fl
                total_flops += fl
    print("  cost-weighted critical path "
          "(share of on-path FLOPs, p95 self-time):", file=out)
    for name, row in sorted(on_path.items(),
                            key=lambda kv: (-kv[1]["flops"],
                                            -max(kv[1]["ms"]))):
        share = (f"{row['flops'] / total_flops * 100.0:5.1f}%"
                 if total_flops > 0 and row["flops"] > 0 else "    -")
        print(f"    {name:<16} {share}  {_p95(row['ms']):9.3f}ms "
              f"x{len(row['ms'])}", file=out)


# ---------------------------------------------------------------------------
# --tiny selftest
# ---------------------------------------------------------------------------


def _synthesize(directory):
    """Round-trip a hedged serve trace and a train-step trace through
    the REAL tracer + sink — the selftest exercises the same emit path
    production uses, not a hand-written log."""
    import time

    from raft_tpu.obs.events import EventSink
    from raft_tpu.obs.trace import Tracer, record_span

    sink = EventSink(directory)
    tracer = Tracer(sink=sink, sample_rate=1.0, seed=0)

    # Hedged request: attempt a is slow, the hedge (attempt b) wins.
    # Real sleeps (~0.1 s total), not synthetic stamps: span end order
    # must agree with the live clocks the Span objects read.
    root = tracer.start_trace("route", bucket="40x56")
    t0 = time.perf_counter()
    a = root.child("attempt", replica="r0", hedge=False)
    record_span(a, "queue", t0, t0 + 0.020)
    record_span(a, "device", t0 + 0.020, t0 + 0.100, retries=0,
                flops=2.0e9, bytes=1.0e9, mfu=0.18)
    time.sleep(0.040)
    b = root.child("attempt", replica="r1", hedge=True)
    record_span(b, "queue", t0 + 0.040, t0 + 0.042)
    record_span(b, "pad", t0 + 0.042, t0 + 0.043, real=1, ballast=1)
    dev = record_span(b, "device", t0 + 0.043, t0 + 0.055, retries=0,
                      flops=2.0e9, bytes=1.0e9, mfu=0.31)
    # the engine's device span owns its stages (obs/stages.py)
    record_span(dev, "h2d", t0 + 0.043, t0 + 0.045)
    record_span(dev, "launch", t0 + 0.045, t0 + 0.048)
    record_span(dev, "drain", t0 + 0.048, t0 + 0.055)
    time.sleep(0.020)               # past b's device end
    b.end(status="ok", won=True)
    root.mark_keep()                # the hedge fired: tail-keep
    root.end(status="ok", hedged=True)
    time.sleep(0.045)               # past a's device end
    a.end(status="ok", won=False)   # loser lands late, after the flush

    st = tracer.start_trace("train_step", step=7)
    t1 = time.perf_counter() - 0.110
    record_span(st, "queue_wait", t1, t1 + 0.004)
    record_span(st, "h2d", t1 + 0.001, t1 + 0.003)
    record_span(st, "step_dispatch", t1 + 0.004, t1 + 0.104)
    st.end()
    sink.close()
    return root.trace_id


def _selftest():
    import tempfile

    with tempfile.TemporaryDirectory(prefix="raft-trace-tiny-") as tdir:
        hedged_id = _synthesize(tdir)
        traces = build_traces(load_spans(tdir))
        assert hedged_id in traces, "hedged trace did not round-trip"
        t = traces[hedged_id]
        root = root_of(t)
        assert root["name"] == "route" and len(t["roots"]) == 1, \
            "hedged request must reconstruct as ONE tree"
        attempts = t["children"].get(root["span_id"], [])
        assert len(attempts) == 2, \
            f"expected both attempts under the root, got {len(attempts)}"
        assert {a.get("hedge") for a in attempts} == {True, False}
        cp_names = [n["name"] for n, _ in critical_path(t, root)]
        assert "device" in cp_names, \
            f"critical path must bottom out in a device span: {cp_names}"
        # The winner (hedge=True) bounds latency, not the slow loser.
        assert any(n.get("hedge") is True for n, _ in
                   critical_path(t, root) if n["name"] == "attempt")
        for trace in traces.values():
            print_waterfall(trace)
        pf = perfetto_events(traces)
        json.loads(json.dumps(pf))  # exports as valid JSON
        assert any(e.get("ph") == "X" for e in pf["traceEvents"])
        # Roofline over the cost attrs the device spans carried, under
        # a KNOWN peak (v5e) so bound-by classifies and MFU folds.
        import io

        from raft_tpu.obs import cost as cost_mod

        buf = io.StringIO()
        roofline_report(traces, cost_mod.peak_spec("v5e"), out=buf)
        txt = buf.getvalue()
        print(txt, end="")
        assert "device" in txt and "memory" in txt, \
            f"2 flop/byte vs the v5e ridge must read memory-bound:\n{txt}"
        assert "0.31" in txt, f"max observed mfu must surface:\n{txt}"
        assert "cost-weighted critical path" in txt
        # The bench record stays the LAST stdout line (tests and the
        # backlog scripts tail it into check_regression).
        rec = bench_record(traces)
        assert rec["config"]["traces_total"] == 2
        assert {"queue", "pad", "device"} <= set(
            rec["config"]["serve_span_names"])
        print(json.dumps(rec))
    return 0


# ---------------------------------------------------------------------------


def main(argv=None):
    args = parse_args(argv)
    if args.tiny:
        return _selftest()
    if not args.path:
        raise SystemExit("pass a telemetry path (or --tiny)")
    traces = build_traces(load_spans(args.path))
    if not traces:
        raise SystemExit(f"no trace_span events under {args.path!r} "
                         "(run with tracing on: --trace-sample-rate / "
                         "$RAFT_TRACE_SAMPLE_RATE)")
    if args.perfetto:
        with open(args.perfetto, "w") as f:
            json.dump(perfetto_events(traces), f)
        print(f"perfetto export: {args.perfetto} "
              f"({len(traces)} traces) — load in https://ui.perfetto.dev",
              file=sys.stderr)
        if not (args.trace or args.json):
            return 0
    if args.json:
        print(json.dumps(bench_record(traces)))
        return 0
    if args.roofline:
        roofline_report(traces, _resolve_spec(args.device_kind))
        return 0
    if args.trace:
        matches = [t for tid, t in traces.items()
                   if tid.startswith(args.trace)]
        if not matches:
            raise SystemExit(f"trace {args.trace!r} not in log "
                             f"({len(traces)} traces present)")
        for t in matches:
            print_waterfall(t)
        return 0
    ranked = sorted(
        traces.values(),
        key=lambda t: float((root_of(t) or {}).get("dur_s", 0.0)),
        reverse=True)
    for t in ranked[:max(args.slowest, 1)]:
        print_waterfall(t)
        print()
    print(f"{len(traces)} traces total; showing the "
          f"{min(len(ranked), max(args.slowest, 1))} slowest "
          "(--trace <id> for one, --json for the gate record)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
