"""Fold a JSONL telemetry log into one JSON line.

Any instrumented run (``python -m raft_tpu train --telemetry_dir ...``
or ``RAFT_TELEMETRY_DIR=...``) leaves ``telemetry-p*.jsonl`` files; this
script turns the per-step ``train_step`` stream of one run into ONE
JSON line of the ``metric``/``value``/``unit``/``vs_baseline``/
``config`` schema every script here prints and
``scripts/check_regression.py`` reads; the per-stage metric names are
defined below (``scripts/bench_input.py`` imports the stage table)::

    python scripts/telemetry_summary.py runs/telemetry/
    python scripts/telemetry_summary.py runs/telemetry/telemetry-p0.jsonl

The last ``run_config`` record in the log (and its following
``train_step`` records) is summarized by default; ``--skip`` drops the
leading steps, whose wall time is trace+compile, from the steady-state
figure (the ``compile`` event is in the log if you want that number).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# BASELINE.json north_star (v5e, chairs crop).
BASELINE_PAIRS_PER_SEC_PER_CHIP = 30.0

# Training-stage names for the reference curriculum's crop shapes
# (train_standard.sh): one mapping for every script's metric series.
_STAGE_NAMES = {(368, 496): "flyingchairs", (400, 720): "flyingthings",
                (368, 768): "sintelstage", (288, 960): "kittistage"}


def _stage_name(h: int, w: int) -> str:
    return _STAGE_NAMES.get((h, w), "custom")


def _train_metric_name(h: int, w: int) -> str:
    return f"train_throughput_{_stage_name(h, w)}_{h}x{w}_bf16_iters12"


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="telemetry JSONL -> one JSON line")
    p.add_argument("path", help="telemetry-*.jsonl file, or a directory "
                                "of them (a multi-host run's per-process "
                                "files are merged by step)")
    p.add_argument("--skip", type=int, default=2,
                   help="leading steps to drop (compile + pipeline "
                        "fill); all steps are kept when fewer exist")
    return p.parse_args(argv)


def iter_records(path):
    files = ([path] if os.path.isfile(path)
             else sorted(glob.glob(os.path.join(path, "*.jsonl"))))
    if not files:
        raise SystemExit(f"no .jsonl telemetry under {path!r}")
    for fname in files:
        with open(fname) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    yield json.loads(line)
                except ValueError:
                    pass  # torn final line of a killed run


def last_run(records):
    """``(run_config, [train_step...], [train_health...], faults,
    [trace_span...], [cost_report...])`` of the LAST run in the log
    (files append across runs; run_config marks each start).  Logs from
    builds without training-health, tracing, or cost-model telemetry
    simply yield empty lists.

    ``faults`` counts the fault-tolerance events (docs/ROBUSTNESS.md)
    over the WHOLE log, not just the last run: resume fallback fires
    BEFORE the resumed run's run_config is written, and a quarantined
    sample is data rot regardless of which restart hit it — the
    check_regression gate wants the conservative total.

    ``quality`` collects the flow-quality stream
    (``quality_score``/``quality_drift`` events,
    ``raft_tpu/obs/quality.py``) over the whole log like ``faults`` —
    drift that fired before the last restart is still drift.

    ``retires`` collects ``serve_retire`` iteration counts over the
    whole log, split by the event's ``warm`` tag (streaming warm-start
    frames vs cold admissions, docs/SERVING.md "Streaming sessions") —
    the split is what makes the warm saving visible in a summary.

    ``incidents`` collects the incident-engine stream
    (``incident_open``/``incident_close``/``slo_burn`` events plus the
    final SLO gauge values, docs/OBSERVABILITY.md "Incidents & SLOs")
    over the whole log like ``faults`` — an incident that opened before
    the last restart still happened.

    ``fabric`` collects the multi-host fabric stream (``fleet_scale``
    autoscaler moves, ``net_retry`` wire failures,
    ``fleet_remote_rejoin`` partition heals; docs/SERVING.md
    "Multi-host fabric") over the whole log — the
    ``check_regression.py --max-scale-flaps / --max-net-retry-rate``
    gates read the totals."""
    run_cfg, steps, health, spans, costs = None, [], [], [], []
    faults = {"sample_quarantine": 0, "ckpt_fallback": 0,
              "serve_retry": 0, "chaos_inject": 0}
    quality = {"scores": [], "drifts": []}
    retires = {"warm": [], "cold": []}
    incidents = {"opened": [], "closed": 0, "burns": [],
                 "burn_gauge": {}, "budget_gauge": {}}
    fabric = {"scales": [], "net_retries": 0, "rejoins": 0}
    for rec in records:
        ev = rec.get("event")
        if ev == "run_config":
            run_cfg, steps, health, spans, costs = rec, [], [], [], []
        elif ev == "train_step":
            steps.append(rec)
        elif ev == "train_health":
            health.append(rec)
        elif ev == "trace_span":
            spans.append(rec)
        elif ev == "cost_report":
            costs.append(rec)
        elif ev == "quality_score":
            quality["scores"].append(rec)
        elif ev == "quality_drift":
            quality["drifts"].append(rec)
        elif ev == "serve_retire":
            it = rec.get("iters")
            if isinstance(it, (int, float)):
                retires["warm" if rec.get("warm")
                        else "cold"].append(int(it))
        elif ev == "incident_open":
            incidents["opened"].append(rec)
        elif ev == "incident_close":
            incidents["closed"] += 1
        elif ev == "slo_burn":
            incidents["burns"].append(rec)
        elif ev == "fleet_scale":
            fabric["scales"].append(rec)
        elif ev == "net_retry":
            fabric["net_retries"] += 1
        elif ev == "fleet_remote_rejoin":
            fabric["rejoins"] += 1
        elif ev == "metrics_summary":
            # The run's final raft_cost_mfu gauge values ride along as
            # a synthetic record so summarize() folds them next to the
            # compile-time cost_report stream.
            vals = rec.get("metrics", {}).get("raft_cost_mfu",
                                              {}).get("values")
            if vals:
                costs.append({"_mfu_gauge": vals})
            # Final SLO gauge values (same pattern): a healthy tracked
            # run summarizes with explicit 0.0 burn rates, so the
            # check_regression --max-slo-burn gate has a record to read
            # even when no slo_burn event ever fired.
            for gauge, key in (("raft_slo_burn_rate", "burn_gauge"),
                               ("raft_slo_budget_remaining",
                                "budget_gauge")):
                vals = rec.get("metrics", {}).get(gauge, {}).get(
                    "values")
                if vals:
                    incidents[key] = vals
        elif ev in faults:
            faults[ev] += 1
    return (run_cfg, steps, health, faults, spans, costs, quality,
            retires, incidents, fabric)


def _wait_s(rec):
    """Consumer-side input wait of one train_step record.

    PR 3 split ``data_wait_s`` into consumer-side ``queue_wait_s`` plus
    the producer-side ``h2d_s``/``prep_s`` spans; older logs carry only
    ``data_wait_s`` (which measured the same consumer-side block)."""
    return rec.get("queue_wait_s", rec.get("data_wait_s", 0.0))


def trace_summary(spans):
    """Fold ``trace_span`` records (raft_tpu/obs/trace.py) into
    per-name duration percentiles plus trace-level counts.  Returns
    ``{}`` for logs without tracing — old logs summarize unchanged."""
    if not spans:
        return {}
    by_name = {}
    roots = {}
    for s in spans:
        by_name.setdefault(s.get("name", "?"), []).append(
            float(s.get("dur_s", 0.0)))
        if s.get("parent_id") is None:
            tid = s.get("trace_id")
            roots[tid] = (roots.get(tid, False)
                          or s.get("status") == "error")
    span_ms = {}
    for name, durs in sorted(by_name.items()):
        durs.sort()
        span_ms[name] = {
            "p50_ms": round(durs[len(durs) // 2] * 1e3, 3),
            "p95_ms": round(durs[min(int(len(durs) * 0.95),
                                     len(durs) - 1)] * 1e3, 3),
            "n": len(durs),
        }
    out = {"span_ms": span_ms}
    if roots:
        out["traces_total"] = len(roots)
        out["traced_error_rate"] = round(
            sum(1 for err in roots.values() if err) / len(roots), 4)
    return out


def cost_summary(costs, value):
    """Fold the run's ``cost_report`` events (obs/cost.py, one per
    captured program) + final ``raft_cost_mfu`` gauge values into
    config-block fields.  ``value`` is the measured pairs/sec/chip —
    multiplying it back through the compiled step's ``flops_per_pair``
    is what turns the throughput into ``achieved_tflops``/``mfu``
    (None when the device peak is unknown, e.g. CPU).  ``{}`` for logs
    without cost telemetry — old logs summarize unchanged."""
    if not costs:
        return {}
    out = {}
    by_prog = {}
    for c in costs:
        if "_mfu_gauge" in c:
            out["mfu_gauge"] = c["_mfu_gauge"]
        elif c.get("program"):
            by_prog[c["program"]] = c  # last capture wins
    if by_prog:
        out["cost"] = {
            prog: {k: c.get(k) for k in
                   ("flops", "bytes", "flops_per_pair",
                    "arithmetic_intensity", "bound_by", "source")}
            for prog, c in sorted(by_prog.items())}
    tc = by_prog.get("train_step")
    if tc and tc.get("flops_per_pair"):
        fpp = tc["flops_per_pair"]
        achieved = value * fpp / 1e12
        peak = tc.get("peak_tflops")
        out["flops_per_pair"] = fpp
        out["achieved_tflops"] = round(achieved, 4)
        out["mfu"] = round(achieved / peak, 4) if peak else None
        out["bound_by"] = tc.get("bound_by")
    return out


def _pctl(vals, q):
    vals = sorted(vals)
    return vals[min(int(len(vals) * q), len(vals) - 1)]


def quality_summary(quality):
    """Fold the flow-quality stream (``quality_score`` /
    ``quality_drift`` events, raft_tpu/obs/quality.py) into
    config-block fields: per-proxy p50/p95 over the sampled scores,
    the drift-event count, and ``quality_drift_score`` — the PEAK PSI
    score any drift event reported, which is what
    ``scripts/check_regression.py --max-quality-drift`` gates on.
    Returns ``{}`` for logs without quality events — old logs
    summarize unchanged."""
    if not quality or not (quality.get("scores")
                           or quality.get("drifts")):
        return {}
    per_proxy = {}
    for rec in quality.get("scores", []):
        for key in ("photometric", "residual", "cycle"):
            v = rec.get(key)
            if isinstance(v, (int, float)) and v >= 0:
                per_proxy.setdefault(key, []).append(float(v))
    out = {"quality": {
        "scored_total": len(quality.get("scores", [])),
        **{k: {"p50": round(_pctl(v, 0.50), 6),
               "p95": round(_pctl(v, 0.95), 6), "n": len(v)}
           for k, v in sorted(per_proxy.items())},
    }}
    drifts = quality.get("drifts", [])
    if drifts:
        scores = [d.get("score") for d in drifts
                  if isinstance(d.get("score"), (int, float))]
        out["quality_drift_events"] = len(drifts)
        if scores:
            out["quality_drift_score"] = round(max(scores), 6)
    return out


def retire_summary(retires):
    """Fold ``serve_retire`` iteration counts, split by the ``warm``
    tag, into config-block fields — p50/p95/n per class plus
    ``warm_iters_saved_frac`` (1 - warm p50 / cold p50, the same figure
    ``scripts/bench_stream.py`` records and
    ``check_regression.py --min-warm-iters-saved-frac`` gates on).
    Returns ``{}`` for logs without retirements (training logs, old
    serve logs) — they summarize unchanged."""
    if not retires or not (retires.get("warm") or retires.get("cold")):
        return {}
    out = {"serve_iters_used": {
        k: {"p50": _pctl(v, 0.50), "p95": _pctl(v, 0.95), "n": len(v)}
        for k, v in sorted(retires.items()) if v}}
    warm, cold = retires.get("warm"), retires.get("cold")
    if warm and cold:
        w50, c50 = _pctl(warm, 0.50), _pctl(cold, 0.50)
        if c50 > 0:
            out["warm_iters_saved_frac"] = round(1.0 - w50 / c50, 4)
    return out


def _slo_label(label):
    """``"slo=avail"`` (registry snapshot label string) -> ``"avail"``."""
    for part in str(label).split(","):
        if part.startswith("slo="):
            return part[len("slo="):]
    return str(label)


def incident_summary(incidents):
    """Fold the incident-engine stream (``incident_open`` /
    ``incident_close`` / ``slo_burn`` events + final SLO gauge values,
    raft_tpu/obs/incident.py + obs/slo.py) into config-block fields:
    incident counts by peak severity, how many never closed, and the
    worst per-SLO burn rate / budget remaining — merged from burn
    events AND the final gauges, so a healthy tracked run reports an
    explicit 0.0 instead of omitting the field
    (``check_regression.py --max-incidents / --max-slo-burn`` gate on
    these).  Returns ``{}`` for logs without incident telemetry — old
    logs summarize unchanged."""
    if not incidents or not (incidents.get("opened")
                             or incidents.get("burns")
                             or incidents.get("burn_gauge")):
        return {}
    out = {}
    opened = incidents.get("opened", [])
    if opened:
        by_sev = {}
        for rec in opened:
            sev = rec.get("severity", "warning")
            by_sev[sev] = by_sev.get(sev, 0) + 1
        out["incidents"] = dict(sorted(by_sev.items()))
        out["incidents_total"] = len(opened)
        out["incidents_open"] = max(
            len(opened) - incidents.get("closed", 0), 0)
    rates = {_slo_label(k): float(v)
             for k, v in incidents.get("burn_gauge", {}).items()}
    budgets = {_slo_label(k): float(v)
               for k, v in incidents.get("budget_gauge", {}).items()}
    for rec in incidents.get("burns", []):
        name = rec.get("slo", "?")
        rate = rec.get("burn_rate")
        if isinstance(rate, (int, float)):
            rates[name] = max(rates.get(name, 0.0), float(rate))
        rem = rec.get("budget_remaining")
        if isinstance(rem, (int, float)):
            budgets[name] = min(budgets.get(name, 1.0), float(rem))
    if rates:
        out["slo_burn_rates"] = {k: round(v, 4)
                                 for k, v in sorted(rates.items())}
    if budgets:
        out["slo_budget_remaining"] = {
            k: round(v, 4) for k, v in sorted(budgets.items())}
    return out


def fabric_summary(fabric):
    """Fold the multi-host fabric stream (``fleet_scale`` /
    ``net_retry`` / ``fleet_remote_rejoin`` events, serve/remote.py +
    the fleet autoscaler) into config-block fields: autoscaler move
    counts by direction, the flap count (direction reversals —
    ``check_regression.py --max-scale-flaps`` gates it), request-path
    wire-failure totals, and partition rejoins.  Returns ``{}`` for
    logs without fabric events — old logs summarize unchanged."""
    if not fabric or not (fabric.get("scales")
                          or fabric.get("net_retries")
                          or fabric.get("rejoins")):
        return {}
    out = {}
    scales = fabric.get("scales", [])
    if scales:
        ups = sum(1 for s in scales if s.get("direction") == "up")
        flaps = 0
        last = None
        for s in scales:
            d = s.get("direction")
            if last is not None and d != last:
                flaps += 1
            last = d
        out["fleet_scale"] = {"ups": ups, "downs": len(scales) - ups,
                              "flaps": flaps}
        out["scale_flaps"] = flaps
    if fabric.get("net_retries"):
        out["net_retry_total"] = fabric["net_retries"]
    if fabric.get("rejoins"):
        out["remote_rejoins_total"] = fabric["rejoins"]
    return out


def summarize(run_cfg, steps, health=None, faults=None, spans=None,
              costs=None, quality=None, retires=None, incidents=None,
              fabric=None, skip=2):
    if run_cfg is None:
        raise SystemExit("no run_config event in log (telemetry written "
                         "by an older build?) — cannot recover batch "
                         "size / device count")
    if not steps:
        raise SystemExit("no train_step events in log")
    steps = sorted(steps, key=lambda r: r.get("step", 0))
    kept = steps[skip:] if len(steps) > skip else steps
    batch = run_cfg["batch_size"]
    n_dev = max(run_cfg.get("num_devices", 1), 1)
    h, w = run_cfg["image_size"]
    wall = sum(r["step_time_s"] for r in kept)
    wait = sum(_wait_s(r) for r in kept)
    h2d = sum(r.get("h2d_s", 0.0) for r in kept)
    value = len(kept) * batch / wall / n_dev if wall > 0 else 0.0
    vs = (value / BASELINE_PAIRS_PER_SEC_PER_CHIP
          if _stage_name(h, w) == "flyingchairs" else 0.0)
    times = sorted(r["step_time_s"] for r in kept)
    # Training-health fields from the run's last train_health record
    # (docs/OBSERVABILITY.md): non-finite step count gates
    # scripts/check_regression.py; the final update-ratio and per-
    # iteration EPE curve summarize where the run's numerics ended up.
    # Old logs without the event just omit the fields.
    # Fault-tolerance whole-log totals (docs/ROBUSTNESS.md): quarantined
    # samples and checkpoint-fallback steps are silent data/state rot a
    # bench number would otherwise hide; check_regression gates on them
    # (--max-quarantined / --max-ckpt-fallback).  chaos_injected
    # distinguishes a chaos drill from organic rot.
    health_cfg = {}
    if faults is not None:
        health_cfg["quarantined_total"] = faults.get(
            "sample_quarantine", 0)
        health_cfg["ckpt_fallback_total"] = faults.get("ckpt_fallback", 0)
        if faults.get("chaos_inject"):
            health_cfg["chaos_injected_total"] = faults["chaos_inject"]
    # Distributed-tracing fold (docs/OBSERVABILITY.md "Distributed
    # tracing"): per-span-name duration percentiles + how many traces
    # completed and what fraction erred.  Absent without trace events.
    health_cfg.update(trace_summary(spans))
    # Cost-model fold (docs/OBSERVABILITY.md "Cost model & roofline").
    health_cfg.update(cost_summary(costs, value))
    # Flow-quality fold (docs/OBSERVABILITY.md "Flow quality").
    health_cfg.update(quality_summary(quality))
    # Streaming warm/cold retirement fold (docs/SERVING.md).
    health_cfg.update(retire_summary(retires))
    # Incident + SLO-burn fold (docs/OBSERVABILITY.md "Incidents &
    # SLOs").
    health_cfg.update(incident_summary(incidents))
    # Multi-host fabric fold (docs/SERVING.md "Multi-host fabric").
    health_cfg.update(fabric_summary(fabric))
    last_health = (health or [None])[-1]
    if last_health is not None:
        health_cfg["nonfinite_steps_total"] = last_health.get(
            "nonfinite_steps_total", 0)
        if "update_ratio" in last_health:
            health_cfg["final_update_ratio"] = last_health["update_ratio"]
        if "epe_iter" in last_health:
            health_cfg["final_epe_iter"] = last_health["epe_iter"]
    return {
        "metric": _train_metric_name(h, w),
        "value": round(value, 3),
        "unit": "image-pairs/sec/chip",
        "vs_baseline": round(vs, 3),
        "config": {
            "source": "telemetry",
            "batch_size": batch,
            "num_devices": n_dev,
            "image_size": [h, w],
            "steps_measured": len(kept),
            "steps_skipped": len(steps) - len(kept),
            # queue_wait_frac near 1 -> input-bound (consumer starving);
            # h2d_frac is producer-side and OVERLAPPED when device
            # prefetch is on — big h2d_frac + small queue_wait_frac
            # means the overlap is hiding the transfer, not a problem.
            "queue_wait_frac": round(wait / wall, 4) if wall > 0 else 0.0,
            "h2d_frac": round(h2d / wall, 4) if wall > 0 else 0.0,
            "step_time_p50_s": round(times[len(times) // 2], 6),
            **health_cfg,
        },
    }


def main(argv=None):
    args = parse_args(argv)
    (run_cfg, steps, health, faults, spans, costs, quality,
     retires, incidents, fabric) = last_run(iter_records(args.path))
    print(json.dumps(summarize(run_cfg, steps, health, faults, spans,
                               costs, skip=args.skip, quality=quality,
                               retires=retires, incidents=incidents,
                               fabric=fabric)))


if __name__ == "__main__":
    main()
