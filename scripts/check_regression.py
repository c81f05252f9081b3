"""Regression gate: fold the newest bench/telemetry JSON against the
prior series and fail loudly.

Inputs are the one-line JSON records the repo's measurement tools
already produce — ``scripts/bench_input.py`` /
``scripts/bench_serve.py`` output, driver ``BENCH_*.json`` wrappers
(the record under their ``parsed`` key), and
``scripts/telemetry_summary.py`` output (whose ``config`` block now
carries ``nonfinite_steps_total``).  Records are grouped by ``metric``
in the order given (the default glob sorts ``BENCH_r01..rNN``), and the
NEWEST record of each series is gated:

- **throughput regression**: newest ``value`` more than
  ``--max-drop-pct`` below the median of the last ``--window`` prior
  non-null values of the same metric -> exit 1;
- **numerics**: any ``config.nonfinite_steps_total > 0`` in a newest
  record -> exit 1 (a run that needed the non-finite guard is not a
  clean number);
- optional ``--min-vs-baseline``: newest ``vs_baseline`` below the
  floor -> exit 1 (BASELINE.json's 30 pairs/sec/chip north star is the
  1.0 point of that field);
- optional ``--max-early-exit-epe-delta``: adaptive early exit
  (``ServeConfig.early_exit_threshold``) trades refinement iterations
  for latency — this bounds what it may cost in accuracy.  The newest
  records must carry ``config.early_exit_epe_delta`` (max |EPE delta|
  vs the full-iteration baseline, from ``evaluate.py
  --early_exit_threshold`` / ``bench_serve.py``) or the raw
  ``config.early_exit_delta_vs_full`` arm dict; exceeding the budget
  -> exit 1, and NO record carrying the figure also -> exit 1 (an
  accuracy gate must not pass because the sweep silently didn't run);
- optional ``--max-quality-drift`` / ``--max-canary-proxy-delta``:
  flow-quality gates over the unsupervised proxies
  (``raft_tpu/obs/quality.py``) — the peak PSI drift score of the
  production quality distribution (``config.quality_drift_score``)
  and the golden-batch proxy regression of the last weight-update
  canary (``config.canary_proxy_delta_pct``).  Both fail vacuously
  when no record carries the figure, like ``--min-mfu``.

Records with ``value: null`` (backend unavailable — the CPU container
writing TPU series) are reported but never gate, so the check is safe
in CI without hardware.

::

    python scripts/check_regression.py                  # BENCH_*.json
    python scripts/check_regression.py runs/summary.json BENCH_r*.json
    python scripts/check_regression.py --tiny           # CPU self-test

``--tiny`` builds a synthetic series in a temp dir and asserts the gate
passes a flat series, catches an injected 30% drop, and catches an
injected non-finite count — the gate gating itself (wired into tier-1).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="bench/telemetry JSON regression gate")
    p.add_argument("paths", nargs="*",
                   help="JSON records, oldest first (default: "
                        "BENCH_*.json in the repo root, name-sorted)")
    p.add_argument("--max-drop-pct", type=float, default=10.0,
                   help="fail when the newest value drops more than "
                        "this %% below the prior-series median")
    p.add_argument("--window", type=int, default=3,
                   help="prior records per metric forming the "
                        "reference median")
    p.add_argument("--min-vs-baseline", type=float, default=None,
                   help="fail when the newest vs_baseline is below "
                        "this floor (unset = no check)")
    p.add_argument("--max-quarantined", type=int, default=0,
                   help="fail when a newest record's "
                        "config.quarantined_total exceeds this (silent "
                        "data rot gate; docs/ROBUSTNESS.md)")
    p.add_argument("--max-ckpt-fallback", type=int, default=0,
                   help="fail when a newest record's "
                        "config.ckpt_fallback_total exceeds this "
                        "(torn-checkpoint gate)")
    p.add_argument("--max-serve-error-rate", type=float, default=0.0,
                   help="fail when a newest serve record's error_rate "
                        "(failed + timed-out requests over submitted; "
                        "429 sheds excluded) exceeds this fraction — "
                        "a fleet drill that dropped requests must not "
                        "pass on throughput alone")
    p.add_argument("--max-early-exit-epe-delta", type=float,
                   default=None, metavar="EPE",
                   help="fail when a newest record's early-exit EPE "
                        "delta vs the full-iteration baseline "
                        "(config.early_exit_epe_delta, or max |delta| "
                        "over config.early_exit_delta_vs_full) exceeds "
                        "this; also fails when NO record carries the "
                        "figure (unset = no check)")
    p.add_argument("--max-quality-drift", type=float, default=None,
                   metavar="SCORE",
                   help="fail when a newest record's "
                        "config.quality_drift_score (peak PSI of the "
                        "flow-quality drift detector, from "
                        "scripts/telemetry_summary.py / "
                        "scripts/quality_smoke.py; "
                        "docs/OBSERVABILITY.md) exceeds this; also "
                        "fails when NO record carries the figure — a "
                        "drift gate must not pass because quality "
                        "scoring silently turned off (unset = no "
                        "check)")
    p.add_argument("--min-warm-iters-saved-frac", type=float,
                   default=None, metavar="FRAC",
                   help="fail when a newest record's "
                        "config.warm_iters_saved_frac (1 - warm-frame "
                        "iters_used p50 / cold p50, from "
                        "scripts/bench_stream.py; docs/SERVING.md "
                        "'Streaming sessions') is below this floor — "
                        "the warm start stopped saving refinement "
                        "work; also fails when NO record carries the "
                        "figure (unset = no check)")
    p.add_argument("--max-stream-epe-delta", type=float, default=None,
                   metavar="EPE",
                   help="fail when a newest record's "
                        "config.stream_epe_delta (streamed-arm EPE "
                        "minus independent-pair EPE on identical "
                        "frames, from scripts/bench_stream.py) exceeds "
                        "this; also fails when NO record carries the "
                        "figure (unset = no check)")
    p.add_argument("--max-canary-proxy-delta", type=float, default=None,
                   metavar="PCT",
                   help="fail when a newest record's "
                        "config.canary_proxy_delta_pct (relative "
                        "golden-batch proxy regression %% of the last "
                        "weight-update canary, from "
                        "scripts/quality_smoke.py) exceeds this; also "
                        "fails when NO record carries the figure "
                        "(unset = no check)")
    p.add_argument("--max-critical-path-ms", action="append",
                   default=[], metavar="NAME:MS",
                   help="fail when a newest record's "
                        "config.critical_path_ms[NAME] (p95 self-time "
                        "of span NAME on the trace critical path, from "
                        "scripts/trace_report.py --json) exceeds MS; "
                        "repeatable.  Also fails when NO record carries "
                        "the figure — a latency gate must not pass "
                        "because tracing silently turned off")
    p.add_argument("--min-mfu", action="append", default=[],
                   metavar="NAME:PCT",
                   help="fail when the newest record of a metric series "
                        "containing NAME posts MFU below PCT%% "
                        "(config.mfu or top-level mfu, from the "
                        "obs/cost.py accounting in "
                        "bench_serve.py / telemetry_summary.py); "
                        "repeatable.  Interpret-mode records and "
                        "records with no MFU (unknown device peak, "
                        "e.g. CPU) never qualify — and a named gate "
                        "with NO qualifying record fails (a "
                        "hardware-utilization gate must not pass "
                        "because the bench ran on the wrong backend)")
    p.add_argument("--max-flops-per-pair-growth", type=float,
                   default=None, metavar="PCT",
                   help="fail when a newest record's flops_per_pair "
                        "grew more than PCT%% over the prior-series "
                        "median (work creep: a 'faster' number that "
                        "quietly shrank shapes passes the throughput "
                        "gate; one that grew work per pair should not "
                        "slip through either).  Also fails when NO "
                        "record carries flops_per_pair (unset = no "
                        "check)")
    p.add_argument("--max-incidents", action="append", default=[],
                   metavar="SEV:N",
                   help="fail when a newest record's "
                        "config.incidents[SEV] (correlated incidents "
                        "opened at peak severity SEV — info/warning/"
                        "critical — from scripts/telemetry_summary.py "
                        "or scripts/incident_smoke.py; "
                        "docs/OBSERVABILITY.md 'Incidents & SLOs') "
                        "exceeds N; repeatable.  Also fails when NO "
                        "record carries config.incidents — the "
                        "incident engine silently off must not look "
                        "like zero incidents")
    p.add_argument("--max-slo-burn", action="append", default=[],
                   metavar="NAME:RATE",
                   help="fail when a newest record's "
                        "config.slo_burn_rates[NAME] (worst error-"
                        "budget burn rate of SLO NAME over the run, "
                        "1.0 = spending the budget exactly; from "
                        "scripts/telemetry_summary.py / "
                        "scripts/incident_smoke.py) exceeds RATE; "
                        "repeatable.  Also fails when NO record "
                        "carries the named rate — SLO tracking "
                        "silently off must not look like a healthy "
                        "burn rate")
    p.add_argument("--max-scale-flaps", type=int, default=None,
                   metavar="N",
                   help="fail when a newest record's "
                        "config.scale_flaps (autoscaler direction "
                        "reversals over the run, from "
                        "scripts/telemetry_summary.py / "
                        "scripts/fabric_smoke.py; docs/SERVING.md "
                        "'Multi-host fabric') exceeds N; also fails "
                        "when NO record carries the figure — the "
                        "autoscaler silently off must not look like a "
                        "flap-free run (unset = no check)")
    p.add_argument("--max-net-retry-rate", type=float, default=None,
                   metavar="PCT",
                   help="fail when a newest record's "
                        "config.net_retry_rate (request-path wire "
                        "failures as %% of routed requests, from "
                        "scripts/fabric_smoke.py) exceeds PCT; also "
                        "fails when NO record carries the figure "
                        "(unset = no check)")
    p.add_argument("--lint-report", default=None, metavar="PATH",
                   help="fail when the raftlint JSON report at PATH "
                        "(scripts/lint_repo.py --json, or `python -m "
                        "raft_tpu lint --json`) carries non-baselined "
                        "findings; ALSO fails when PATH is missing or "
                        "not a raftlint report — lint silently not "
                        "running must not look like lint passing "
                        "(docs/ANALYSIS.md)")
    p.add_argument("--tiny", action="store_true",
                   help="self-test on synthetic series (CPU smoke; "
                        "exercises the pass, drop and nonfinite paths)")
    return p.parse_args(argv)


def load_record(path):
    """One bench-format record from ``path`` (unwraps the driver's
    ``parsed`` envelope); None when the file holds neither."""
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, ValueError):
        return None
    if isinstance(d, dict) and isinstance(d.get("parsed"), dict):
        d = d["parsed"]
    if isinstance(d, dict) and "metric" in d:
        return d
    return None


def build_series(paths):
    """metric -> [records oldest..newest] (input order preserved)."""
    series = {}
    for path in paths:
        rec = load_record(path)
        if rec is not None:
            series.setdefault(rec["metric"], []).append(
                dict(rec, _path=path))
    return series


#: Span names every serve-rooted trace must contain (the engine's
#: per-request instrumentation, raft_tpu/serve/engine.py) — a trace
#: tree without them means the instrumentation silently broke.
SERVE_REQUIRED_SPANS = ("queue", "pad", "device")


def parse_named_gates(items, flag, example):
    """``["device:50", ...] -> {"device": 50.0}``."""
    gates = {}
    for item in items or []:
        name, sep, val = str(item).rpartition(":")
        try:
            if not sep or not name:
                raise ValueError
            gates[name] = float(val)
        except ValueError:
            raise SystemExit(f"{flag} expects NAME:{example[0]} "
                             f"(e.g. {example[1]}), got {item!r}")
    return gates


def parse_cp_gates(items):
    return parse_named_gates(items, "--max-critical-path-ms",
                             ("MS", "device:50"))


def _rec_flops_per_pair(rec):
    """A record's flops_per_pair (telemetry_summary.py puts it in
    ``config``, bench_serve.py at the top level); None when absent."""
    cfg = rec.get("config") or {}
    v = cfg.get("flops_per_pair", rec.get("flops_per_pair"))
    return v if isinstance(v, (int, float)) and v > 0 else None


def check(series, max_drop_pct=10.0, window=3, min_vs_baseline=None,
          max_quarantined=0, max_ckpt_fallback=0,
          max_serve_error_rate=0.0, max_critical_path_ms=None,
          max_early_exit_epe_delta=None, min_mfu=None,
          max_flops_per_pair_growth=None,
          max_quality_drift=None, max_canary_proxy_delta=None,
          min_warm_iters_saved_frac=None, max_stream_epe_delta=None,
          max_incidents=None, max_slo_burn=None, max_scale_flaps=None,
          max_net_retry_rate=None):
    """``(failures, report)`` over the newest record of each metric."""
    failures, report = [], []
    cp_gates = dict(max_critical_path_ms or {})
    cp_seen = set()
    inc_gates = dict(max_incidents or {})
    inc_seen = set()
    slo_gates = dict(max_slo_burn or {})
    slo_seen = set()
    mfu_gates = dict(min_mfu or {})
    mfu_seen = set()
    ee_seen = False
    fpp_seen = False
    qd_seen = False
    cpx_seen = False
    wis_seen = False
    sed_seen = False
    sf_seen = False
    nrr_seen = False
    for metric, recs in sorted(series.items()):
        newest = recs[-1]
        value = newest.get("value")
        cfg = newest.get("config") or {}
        entry = {"metric": metric, "value": value,
                 "path": newest.get("_path"), "n_records": len(recs)}
        nf = cfg.get("nonfinite_steps_total")
        if isinstance(nf, (int, float)) and nf > 0:
            failures.append(
                f"{metric}: nonfinite_steps_total={int(nf)} — the run "
                "hit the non-finite guard; its numbers are not clean")
        # Fault-tolerance gates (docs/ROBUSTNESS.md): a run that had to
        # quarantine samples or walk past torn checkpoints produced a
        # number, but the number hides rot — fail unless the budget
        # says otherwise (chaos drills pass explicit budgets).
        q = cfg.get("quarantined_total")
        if isinstance(q, (int, float)) and q > max_quarantined:
            failures.append(
                f"{metric}: quarantined_total={int(q)} > "
                f"{max_quarantined} — samples were silently skipped "
                "(data rot or a chaos drill without a budget)")
        fb = cfg.get("ckpt_fallback_total")
        if isinstance(fb, (int, float)) and fb > max_ckpt_fallback:
            failures.append(
                f"{metric}: ckpt_fallback_total={int(fb)} > "
                f"{max_ckpt_fallback} — resume skipped torn "
                "checkpoint step(s)")
        # Serve-path gate: bench_serve records carry error_rate (failed
        # + timed-out requests over submitted; 429 sheds excluded).  A
        # fleet whose failover quietly loses requests still posts good
        # throughput — this is the gate that notices.
        er = newest.get("error_rate")
        if isinstance(er, (int, float)) and er > max_serve_error_rate:
            failures.append(
                f"{metric}: error_rate={er:g} > {max_serve_error_rate:g}"
                f" ({newest.get('errors', '?')} errors, "
                f"{newest.get('timeouts', '?')} timeouts)")
        # Trace-derived SLO gates (scripts/trace_report.py --json):
        # per-span critical-path budgets, plus a coverage check — a
        # serve trace tree missing the engine's queue/pad/device spans
        # means the instrumentation regressed, and a latency gate over
        # absent data would pass vacuously.
        cp = cfg.get("critical_path_ms")
        if isinstance(cp, dict):
            for name, budget in cp_gates.items():
                v = cp.get(name)
                if isinstance(v, (int, float)):
                    cp_seen.add(name)
                    if v > budget:
                        failures.append(
                            f"{metric}: critical-path {name} p95 "
                            f"{v:g}ms > budget {budget:g}ms")
        # Hardware-utilization floor (obs/cost.py MFU): interpret-mode
        # records and unknown-peak records (CPU — mfu is null there by
        # design, never a fabricated ratio) are EXCLUDED from
        # qualifying, so a TPU gate cannot be satisfied by a CPU smoke.
        mfu = cfg.get("mfu", newest.get("mfu"))
        if not cfg.get("interpret") and isinstance(mfu, (int, float)):
            for name, floor in mfu_gates.items():
                if name not in metric:
                    continue
                mfu_seen.add(name)
                if mfu * 100.0 < floor:
                    failures.append(
                        f"{metric}: mfu {mfu * 100.0:.2f}% < floor "
                        f"{floor:g}% — the chip is underutilized vs "
                        "the gated baseline (scheduling/fusion "
                        "regression, or the wrong device peak)")
        # Work-creep gate: flops_per_pair is hardware- and mesh-
        # invariant, so growth over the series means the program
        # genuinely does more work per pair — a throughput 'win' from
        # shape shrink shows up here as the mirror failure.
        if max_flops_per_pair_growth is not None:
            fpp = _rec_flops_per_pair(newest)
            if fpp is not None:
                fpp_seen = True
                prior_fpp = [v for v in map(_rec_flops_per_pair,
                                            recs[:-1]) if v is not None]
                if prior_fpp:
                    ref = statistics.median(
                        prior_fpp[-max(window, 1):])
                    growth = (fpp - ref) / ref * 100.0
                    if growth > max_flops_per_pair_growth:
                        failures.append(
                            f"{metric}: flops_per_pair {fpp:g} grew "
                            f"{growth:.1f}% over the prior-series "
                            f"median {ref:g} (budget "
                            f"{max_flops_per_pair_growth:g}%) — work "
                            "per pair crept up")
        # Early-exit accuracy gate: iterations saved by the convergence
        # cut (docs/SERVING.md) must stay within the EPE budget the
        # sweep measured (evaluate.py --early_exit_threshold).
        if max_early_exit_epe_delta is not None:
            ee = cfg.get("early_exit_epe_delta")
            dv = cfg.get("early_exit_delta_vs_full")
            if ee is None and isinstance(dv, dict):
                arms = [abs(v) for v in dv.values()
                        if isinstance(v, (int, float))]
                ee = max(arms) if arms else None
            if isinstance(ee, (int, float)):
                ee_seen = True
                if abs(ee) > max_early_exit_epe_delta:
                    failures.append(
                        f"{metric}: early-exit EPE delta {ee:g} exceeds "
                        f"budget {max_early_exit_epe_delta:g} — the "
                        "convergence threshold is trading too much "
                        "accuracy for latency")
        # Flow-quality gates (docs/OBSERVABILITY.md): the PSI drift
        # score is the unsupervised production-quality signal
        # (raft_tpu/obs/quality.py), the canary proxy delta is what the
        # last weight-update canary measured on the golden batch.  Like
        # --min-mfu, a gate with no qualifying record FAILS — quality
        # scoring silently off must not look like quality stable.
        if max_quality_drift is not None:
            qd = cfg.get("quality_drift_score")
            if isinstance(qd, (int, float)):
                qd_seen = True
                if qd > max_quality_drift:
                    failures.append(
                        f"{metric}: quality_drift_score {qd:g} > budget "
                        f"{max_quality_drift:g} — the flow-quality "
                        "proxy distribution shifted vs its reference "
                        "(input drift or a bad weight rollout)")
        if max_canary_proxy_delta is not None:
            cpx = cfg.get("canary_proxy_delta_pct")
            if isinstance(cpx, (int, float)):
                cpx_seen = True
                if cpx > max_canary_proxy_delta:
                    failures.append(
                        f"{metric}: canary_proxy_delta_pct {cpx:g}% > "
                        f"budget {max_canary_proxy_delta:g}% — the "
                        "weight-update canary scored worse on the "
                        "golden batch than the live fleet")
        # Streaming warm-start gates (scripts/bench_stream.py,
        # docs/SERVING.md "Streaming sessions"): warm-started frames
        # must keep converging in fewer iterations than cold ones, and
        # the accuracy cost of carrying state across frames stays
        # inside its budget.
        if min_warm_iters_saved_frac is not None:
            wis = cfg.get("warm_iters_saved_frac")
            if isinstance(wis, (int, float)):
                wis_seen = True
                if wis < min_warm_iters_saved_frac:
                    failures.append(
                        f"{metric}: warm_iters_saved_frac {wis:g} < "
                        f"floor {min_warm_iters_saved_frac:g} — "
                        "warm-started frames no longer converge "
                        "meaningfully faster than cold ones (broken "
                        "carry-over or a mis-set warm budget)")
        if max_stream_epe_delta is not None:
            sed = cfg.get("stream_epe_delta")
            if isinstance(sed, (int, float)):
                sed_seen = True
                if sed > max_stream_epe_delta:
                    failures.append(
                        f"{metric}: stream_epe_delta {sed:g} > budget "
                        f"{max_stream_epe_delta:g} — streaming warm "
                        "start costs more accuracy vs independent "
                        "pairs than the budget allows")
        # Incident-engine gates (docs/OBSERVABILITY.md "Incidents &
        # SLOs"): a run that paged its way through a cascade still
        # posts a throughput number — these are the gates that notice.
        # A record qualifies by carrying config.incidents at all (an
        # EMPTY dict is a healthy incident-enabled run; the key's
        # absence means the engine never ran).
        inc = cfg.get("incidents")
        if isinstance(inc, dict):
            for sev, budget in inc_gates.items():
                inc_seen.add(sev)
                n = inc.get(sev, 0)
                if isinstance(n, (int, float)) and n > budget:
                    failures.append(
                        f"{metric}: incidents[{sev!r}]={int(n)} > "
                        f"{budget:g} — the run opened more {sev} "
                        "incidents than the budget allows "
                        "(python -m raft_tpu incidents list)")
        sbr = cfg.get("slo_burn_rates")
        if isinstance(sbr, dict):
            for name, budget in slo_gates.items():
                v = sbr.get(name)
                if isinstance(v, (int, float)):
                    slo_seen.add(name)
                    if v > budget:
                        failures.append(
                            f"{metric}: slo_burn_rates[{name!r}]="
                            f"{v:g} > {budget:g} — the {name} SLO "
                            "burned its error budget faster than the "
                            "gate allows")
        # Multi-host fabric gates (docs/SERVING.md "Multi-host
        # fabric"): an autoscaler that reverses direction within one
        # run is flapping (its hysteresis/cooldown knobs regressed),
        # and a fabric drill whose wire-failure rate blows past the
        # budget is retrying its way through a problem the failover
        # machinery should have absorbed.
        if max_scale_flaps is not None:
            sf = cfg.get("scale_flaps")
            if isinstance(sf, (int, float)):
                sf_seen = True
                if sf > max_scale_flaps:
                    failures.append(
                        f"{metric}: scale_flaps={int(sf)} > "
                        f"{max_scale_flaps} — the autoscaler reversed "
                        "direction more than the budget allows "
                        "(hysteresis/cooldown too tight for the load)")
        if max_net_retry_rate is not None:
            nrr = cfg.get("net_retry_rate")
            if isinstance(nrr, (int, float)):
                nrr_seen = True
                if nrr > max_net_retry_rate:
                    failures.append(
                        f"{metric}: net_retry_rate={nrr:g}% > "
                        f"{max_net_retry_rate:g}% — the fabric burned "
                        "more wire retries per routed request than the "
                        "budget allows (partition outlasting the "
                        "breaker, or a flaky link)")
        sn = cfg.get("serve_span_names")
        if isinstance(sn, list) and sn:
            missing = sorted(set(SERVE_REQUIRED_SPANS) - set(sn))
            if missing:
                failures.append(
                    f"{metric}: serve traces are missing the "
                    f"{missing} span(s) — the request-path "
                    "instrumentation is incomplete (engine spans "
                    "lost?); refusing to gate on partial traces")
        if value is None:
            entry["skipped"] = "value null (backend unavailable)"
            report.append(entry)
            continue
        prior = [r.get("value") for r in recs[:-1]
                 if isinstance(r.get("value"), (int, float))]
        if prior:
            ref = statistics.median(prior[-max(window, 1):])
            entry["reference"] = ref
            if ref > 0:
                drop = (ref - value) / ref * 100.0
                entry["drop_pct"] = round(drop, 2)
                if drop > max_drop_pct:
                    failures.append(
                        f"{metric}: {value} is {drop:.1f}% below the "
                        f"prior-series median {ref} "
                        f"(threshold {max_drop_pct}%)")
        vs = newest.get("vs_baseline")
        if (min_vs_baseline is not None
                and isinstance(vs, (int, float)) and vs < min_vs_baseline):
            failures.append(f"{metric}: vs_baseline {vs} < floor "
                            f"{min_vs_baseline}")
        report.append(entry)
    for name in sorted(set(cp_gates) - cp_seen):
        failures.append(
            f"critical-path gate {name!r}: no record carries "
            f"config.critical_path_ms[{name!r}] — tracing is off or "
            "the span never appeared; the gate cannot pass vacuously")
    for name in sorted(set(mfu_gates) - mfu_seen):
        failures.append(
            f"mfu gate {name!r}: no qualifying record carries an MFU "
            "figure (interpret-mode and unknown-peak/CPU records are "
            "excluded) — the cost-instrumented bench did not run on "
            "known hardware; the gate cannot pass vacuously")
    if max_flops_per_pair_growth is not None and not fpp_seen:
        failures.append(
            "flops-per-pair gate: no record carries flops_per_pair "
            "(config or top-level) — the cost accounting did not run; "
            "the gate cannot pass vacuously")
    if max_early_exit_epe_delta is not None and not ee_seen:
        failures.append(
            "early-exit gate: no record carries "
            "config.early_exit_epe_delta (or early_exit_delta_vs_full) "
            "— the accuracy sweep did not run; the gate cannot pass "
            "vacuously")
    if max_quality_drift is not None and not qd_seen:
        failures.append(
            "quality-drift gate: no record carries "
            "config.quality_drift_score — quality scoring did not run "
            "(ServeConfig.quality_sample_rate 0, or the summary "
            "predates the quality proxies); the gate cannot pass "
            "vacuously")
    if min_warm_iters_saved_frac is not None and not wis_seen:
        failures.append(
            "warm-iters gate: no record carries "
            "config.warm_iters_saved_frac — the streaming bench "
            "(scripts/bench_stream.py) did not run, or its warm/cold "
            "histograms were empty; the gate cannot pass vacuously")
    if max_stream_epe_delta is not None and not sed_seen:
        failures.append(
            "stream-epe gate: no record carries "
            "config.stream_epe_delta — the streaming bench "
            "(scripts/bench_stream.py) did not run both arms; the "
            "gate cannot pass vacuously")
    for sev in sorted(set(inc_gates) - inc_seen):
        failures.append(
            f"incident gate {sev!r}: no record carries "
            "config.incidents — the incident engine "
            "(ServeConfig.incidents / RAFT_INCIDENTS=1) did not run; "
            "the gate cannot pass vacuously")
    for name in sorted(set(slo_gates) - slo_seen):
        failures.append(
            f"slo-burn gate {name!r}: no record carries "
            f"config.slo_burn_rates[{name!r}] — SLO tracking for that "
            "objective did not run (slo_* targets unset?); the gate "
            "cannot pass vacuously")
    if max_scale_flaps is not None and not sf_seen:
        failures.append(
            "scale-flap gate: no record carries config.scale_flaps — "
            "the autoscaler did not run (autoscale_max 0, or the "
            "summary predates the fabric fold); the gate cannot pass "
            "vacuously")
    if max_net_retry_rate is not None and not nrr_seen:
        failures.append(
            "net-retry gate: no record carries config.net_retry_rate "
            "— no fabric drill ran (scripts/fabric_smoke.py); the "
            "gate cannot pass vacuously")
    if max_canary_proxy_delta is not None and not cpx_seen:
        failures.append(
            "canary-proxy gate: no record carries "
            "config.canary_proxy_delta_pct — no proxy-gated weight "
            "update ran (canary_proxy_budget unset, or no update "
            "happened); the gate cannot pass vacuously")
    return failures, report


def lint_gate(path):
    """Failure list from a raftlint JSON report.  Three ways to fail:
    the report is missing/unreadable, it is not a raftlint report, or
    it carries non-baselined findings.  A clean report (``total: 0``)
    passes; an ABSENT report does not — the gate must distinguish
    "raftlint ran and found nothing" from "raftlint never ran"."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from raft_tpu.analysis.core import load_report

    report, err = load_report(path)
    if report is None:
        return [f"lint gate: {err} — refusing to pass without a "
                "raftlint run (python -m raft_tpu lint --json PATH)"]
    findings = report.get("findings")
    total = report.get("total")
    n = total if isinstance(total, int) else len(findings)
    if n <= 0:
        return []
    by_rule = report.get("counts_by_rule") or {}
    head = "; ".join(
        "{}:{}:{} {}".format(f.get("rule"), f.get("path"),
                             f.get("line"), f.get("message", ""))[:160]
        for f in findings[:3] if isinstance(f, dict))
    return [f"lint gate: {n} non-baselined raftlint finding(s) "
            f"({json.dumps(by_rule)}) — fix them or baseline with a "
            f"justification (docs/ANALYSIS.md). First: {head}"]


def _selftest() -> int:
    """The gate gating itself: synthetic series through the real
    file-loading path."""

    def run(values, nonfinite_last=0, drop_pct=10.0, last_cfg=None,
            last_top=None, cfgs=None, **gate_kw):
        with tempfile.TemporaryDirectory() as td:
            paths = []
            for i, v in enumerate(values):
                rec = {"metric": "train_throughput_tiny", "value": v,
                       "unit": "image-pairs/sec/chip", "vs_baseline": 0.0,
                       "config": {}}
                if cfgs is not None:
                    rec["config"].update(cfgs[i])
                if i == len(values) - 1:
                    if nonfinite_last:
                        rec["config"]["nonfinite_steps_total"] = \
                            nonfinite_last
                    rec["config"].update(last_cfg or {})
                    rec.update(last_top or {})
                if i % 2:  # alternate raw and driver-wrapped envelopes
                    rec = {"n": i, "rc": 0, "parsed": rec}
                p = os.path.join(td, f"BENCH_r{i:02d}.json")
                with open(p, "w") as f:
                    json.dump(rec, f)
                paths.append(p)
            return check(build_series(paths), max_drop_pct=drop_pct,
                         **gate_kw)

    cases = [
        ("flat series passes", run([30.0, 31.0, 30.5]), False),
        ("30% drop fails", run([30.0, 31.0, 21.0]), True),
        ("nonfinite fails", run([30.0, 31.0, 30.5], nonfinite_last=2),
         True),
        ("null value never gates", run([30.0, 31.0, None]), False),
        ("single record passes", run([30.0]), False),
        ("quarantine fails", run([30.0, 31.0, 30.5],
                                 last_cfg={"quarantined_total": 3}),
         True),
        ("quarantine within budget passes",
         run([30.0, 31.0, 30.5], last_cfg={"quarantined_total": 3},
             max_quarantined=3), False),
        ("ckpt fallback fails", run([30.0, 31.0, 30.5],
                                    last_cfg={"ckpt_fallback_total": 1}),
         True),
        ("zero fault totals pass",
         run([30.0, 31.0, 30.5], last_cfg={"quarantined_total": 0,
                                           "ckpt_fallback_total": 0}),
         False),
        ("serve error_rate fails",
         run([30.0, 31.0, 30.5],
             last_top={"error_rate": 0.125, "errors": 2, "timeouts": 1}),
         True),
        ("serve error_rate within budget passes",
         run([30.0, 31.0, 30.5], last_top={"error_rate": 0.125},
             max_serve_error_rate=0.2), False),
        ("zero error_rate passes",
         run([30.0, 31.0, 30.5],
             last_top={"error_rate": 0.0, "errors": 0, "timeouts": 0}),
         False),
        ("rejected-only record passes",
         run([30.0, 31.0, 30.5],
             last_top={"error_rate": 0.0, "rejected": 5}), False),
        ("critical path within budget passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"critical_path_ms": {"device": 12.0}},
             max_critical_path_ms={"device": 50.0}), False),
        ("critical path over budget fails",
         run([30.0, 31.0, 30.5],
             last_cfg={"critical_path_ms": {"device": 80.0}},
             max_critical_path_ms={"device": 50.0}), True),
        ("critical-path gate without data fails",
         run([30.0, 31.0, 30.5],
             max_critical_path_ms={"device": 50.0}), True),
        ("serve span coverage complete passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"serve_span_names": ["attempt", "device", "pad",
                                            "queue", "route"]}), False),
        ("serve span coverage missing fails",
         run([30.0, 31.0, 30.5],
             last_cfg={"serve_span_names": ["route", "attempt"]}), True),
        ("no serve traces skips coverage",
         run([30.0, 31.0, 30.5], last_cfg={"serve_span_names": []}),
         False),
        ("early-exit delta within budget passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"early_exit_epe_delta": 0.03},
             max_early_exit_epe_delta=0.05), False),
        ("early-exit delta over budget fails",
         run([30.0, 31.0, 30.5],
             last_cfg={"early_exit_epe_delta": 0.09},
             max_early_exit_epe_delta=0.05), True),
        ("early-exit arm dict over budget fails",
         run([30.0, 31.0, 30.5],
             last_cfg={"early_exit_delta_vs_full": {"0.05": 0.01,
                                                    "0.2": -0.3}},
             max_early_exit_epe_delta=0.05), True),
        ("early-exit gate without data fails",
         run([30.0, 31.0, 30.5], max_early_exit_epe_delta=0.05), True),
        ("early-exit delta without the gate passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"early_exit_epe_delta": 9.0}), False),
        ("mfu above floor passes",
         run([30.0, 31.0, 30.5], last_cfg={"mfu": 0.45},
             min_mfu={"train_throughput": 40.0}), False),
        ("mfu below floor fails",
         run([30.0, 31.0, 30.5], last_cfg={"mfu": 0.25},
             min_mfu={"train_throughput": 40.0}), True),
        ("mfu gate without record fails",
         run([30.0, 31.0, 30.5],
             min_mfu={"train_throughput": 40.0}), True),
        ("interpret record never satisfies the mfu gate",
         run([30.0, 31.0, 30.5],
             last_cfg={"interpret": True, "mfu": 0.45},
             min_mfu={"train_throughput": 40.0}), True),
        ("null mfu (CPU peak) never satisfies the mfu gate",
         run([30.0, 31.0, 30.5], last_cfg={"mfu": None},
             min_mfu={"train_throughput": 40.0}), True),
        ("low mfu without the gate passes",
         run([30.0, 31.0, 30.5], last_cfg={"mfu": 0.02}), False),
        ("flat flops_per_pair passes",
         run([30.0, 31.0, 30.5],
             cfgs=[{"flops_per_pair": 1e9}] * 3,
             max_flops_per_pair_growth=5.0), False),
        ("flops_per_pair growth over budget fails",
         run([30.0, 31.0, 30.5],
             cfgs=[{"flops_per_pair": 1e9}, {"flops_per_pair": 1e9},
                   {"flops_per_pair": 1.2e9}],
             max_flops_per_pair_growth=5.0), True),
        ("first costed record passes the growth gate",
         run([30.0, 31.0, 30.5],
             last_cfg={"flops_per_pair": 1e9},
             max_flops_per_pair_growth=5.0), False),
        ("flops_per_pair gate without data fails",
         run([30.0, 31.0, 30.5], max_flops_per_pair_growth=5.0), True),
        ("flops_per_pair growth without the gate passes",
         run([30.0, 31.0, 30.5],
             cfgs=[{"flops_per_pair": 1e9}, {"flops_per_pair": 1e9},
                   {"flops_per_pair": 9e9}]), False),
        ("quality drift within budget passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"quality_drift_score": 0.2},
             max_quality_drift=0.5), False),
        ("quality drift over budget fails",
         run([30.0, 31.0, 30.5],
             last_cfg={"quality_drift_score": 1.7},
             max_quality_drift=0.5), True),
        ("quality-drift gate without data fails",
         run([30.0, 31.0, 30.5], max_quality_drift=0.5), True),
        ("high drift score without the gate passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"quality_drift_score": 9.0}), False),
        ("canary proxy delta within budget passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"canary_proxy_delta_pct": 12.0},
             max_canary_proxy_delta=50.0), False),
        ("canary proxy delta over budget fails",
         run([30.0, 31.0, 30.5],
             last_cfg={"canary_proxy_delta_pct": 180.0},
             max_canary_proxy_delta=50.0), True),
        ("canary-proxy gate without data fails",
         run([30.0, 31.0, 30.5], max_canary_proxy_delta=50.0), True),
        ("canary proxy delta without the gate passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"canary_proxy_delta_pct": 999.0}), False),
        ("warm iters saving above floor passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"warm_iters_saved_frac": 0.4},
             min_warm_iters_saved_frac=0.1), False),
        ("warm iters saving below floor fails",
         run([30.0, 31.0, 30.5],
             last_cfg={"warm_iters_saved_frac": 0.02},
             min_warm_iters_saved_frac=0.1), True),
        ("warm-iters gate without data fails",
         run([30.0, 31.0, 30.5], min_warm_iters_saved_frac=0.1), True),
        ("zero warm saving without the gate passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"warm_iters_saved_frac": 0.0}), False),
        ("stream EPE delta within budget passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"stream_epe_delta": 0.02},
             max_stream_epe_delta=0.1), False),
        ("stream EPE delta over budget fails",
         run([30.0, 31.0, 30.5],
             last_cfg={"stream_epe_delta": 0.7},
             max_stream_epe_delta=0.1), True),
        ("stream-epe gate without data fails",
         run([30.0, 31.0, 30.5], max_stream_epe_delta=0.1), True),
        ("high stream EPE delta without the gate passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"stream_epe_delta": 9.0}), False),
        ("incidents within budget pass",
         run([30.0, 31.0, 30.5],
             last_cfg={"incidents": {"critical": 1}},
             max_incidents={"critical": 1}), False),
        ("incidents over budget fail",
         run([30.0, 31.0, 30.5],
             last_cfg={"incidents": {"critical": 2, "warning": 1}},
             max_incidents={"critical": 1}), True),
        ("empty incidents dict satisfies a zero budget",
         run([30.0, 31.0, 30.5], last_cfg={"incidents": {}},
             max_incidents={"critical": 0}), False),
        ("incident gate without data fails",
         run([30.0, 31.0, 30.5], max_incidents={"critical": 0}), True),
        ("incidents without the gate pass",
         run([30.0, 31.0, 30.5],
             last_cfg={"incidents": {"critical": 9}}), False),
        ("slo burn within budget passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"slo_burn_rates": {"availability": 0.4}},
             max_slo_burn={"availability": 1.0}), False),
        ("slo burn over budget fails",
         run([30.0, 31.0, 30.5],
             last_cfg={"slo_burn_rates": {"availability": 14.4}},
             max_slo_burn={"availability": 1.0}), True),
        ("slo-burn gate without the named series fails",
         run([30.0, 31.0, 30.5],
             last_cfg={"slo_burn_rates": {"latency": 0.0}},
             max_slo_burn={"availability": 1.0}), True),
        ("slo-burn gate without data fails",
         run([30.0, 31.0, 30.5], max_slo_burn={"availability": 1.0}),
         True),
        ("hot slo burn without the gate passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"slo_burn_rates": {"availability": 99.0}}),
         False),
        ("scale flaps within budget pass",
         run([30.0, 31.0, 30.5], last_cfg={"scale_flaps": 1},
             max_scale_flaps=1), False),
        ("scale flaps over budget fail",
         run([30.0, 31.0, 30.5], last_cfg={"scale_flaps": 3},
             max_scale_flaps=1), True),
        ("zero scale flaps satisfy a zero budget",
         run([30.0, 31.0, 30.5], last_cfg={"scale_flaps": 0},
             max_scale_flaps=0), False),
        ("scale-flap gate without data fails",
         run([30.0, 31.0, 30.5], max_scale_flaps=1), True),
        ("scale flaps without the gate pass",
         run([30.0, 31.0, 30.5], last_cfg={"scale_flaps": 9}), False),
        ("net retry rate within budget passes",
         run([30.0, 31.0, 30.5], last_cfg={"net_retry_rate": 4.0},
             max_net_retry_rate=25.0), False),
        ("net retry rate over budget fails",
         run([30.0, 31.0, 30.5], last_cfg={"net_retry_rate": 60.0},
             max_net_retry_rate=25.0), True),
        ("net-retry gate without data fails",
         run([30.0, 31.0, 30.5], max_net_retry_rate=25.0), True),
        ("hot net retry rate without the gate passes",
         run([30.0, 31.0, 30.5],
             last_cfg={"net_retry_rate": 99.0}), False),
    ]

    def run_lint(payload):
        """Lint-gate case through the real file path.  ``payload``:
        None = no file on disk; str = raw file contents; dict = a
        report skeleton (tool/findings/total filled in by the caller)."""
        with tempfile.TemporaryDirectory() as td:
            p = os.path.join(td, "lint.json")
            if payload is not None:
                with open(p, "w") as f:
                    f.write(payload if isinstance(payload, str)
                            else json.dumps(payload))
            return lint_gate(p), []

    finding = {"rule": "JIT101", "path": "raft_tpu/models/raft.py",
               "line": 7, "detail": "time.time",
               "message": "host call inside a jit-traced function"}
    cases += [
        ("lint clean report passes",
         run_lint({"tool": "raftlint", "findings": [], "total": 0}),
         False),
        ("lint new finding fails",
         run_lint({"tool": "raftlint", "findings": [finding],
                   "counts_by_rule": {"JIT101": 1}, "total": 1}), True),
        ("lint missing report fails", run_lint(None), True),
        ("lint garbage report fails", run_lint("not json {"), True),
        ("lint wrong-tool report fails",
         run_lint({"tool": "flake8", "findings": []}), True),
    ]
    bad = [name for name, (failures, _), want_fail in cases
           if bool(failures) != want_fail]
    print(json.dumps({
        "metric": "check_regression_selftest",
        "value": 0.0 if bad else 1.0,
        "unit": "pass",
        "vs_baseline": 0.0,
        "config": {"cases": len(cases), "failed": bad},
    }))
    return 1 if bad else 0


def main(argv=None):
    args = parse_args(argv)
    if args.tiny:
        return _selftest()
    paths = args.paths or sorted(
        glob.glob(os.path.join(REPO, "BENCH_*.json")))
    if not paths and not args.lint_report:
        raise SystemExit("no input records (no BENCH_*.json found and "
                         "no paths given)")
    failures, report = check(build_series(paths),
                             max_drop_pct=args.max_drop_pct,
                             window=args.window,
                             min_vs_baseline=args.min_vs_baseline,
                             max_quarantined=args.max_quarantined,
                             max_ckpt_fallback=args.max_ckpt_fallback,
                             max_serve_error_rate=args.max_serve_error_rate,
                             max_critical_path_ms=parse_cp_gates(
                                 args.max_critical_path_ms),
                             max_early_exit_epe_delta=(
                                 args.max_early_exit_epe_delta),
                             min_mfu=parse_named_gates(
                                 args.min_mfu, "--min-mfu",
                                 ("PCT", "train_throughput:40")),
                             max_flops_per_pair_growth=(
                                 args.max_flops_per_pair_growth),
                             max_quality_drift=args.max_quality_drift,
                             max_canary_proxy_delta=(
                                 args.max_canary_proxy_delta),
                             min_warm_iters_saved_frac=(
                                 args.min_warm_iters_saved_frac),
                             max_stream_epe_delta=(
                                 args.max_stream_epe_delta),
                             max_incidents=parse_named_gates(
                                 args.max_incidents, "--max-incidents",
                                 ("N", "critical:0")),
                             max_slo_burn=parse_named_gates(
                                 args.max_slo_burn, "--max-slo-burn",
                                 ("RATE", "availability:1")),
                             max_scale_flaps=args.max_scale_flaps,
                             max_net_retry_rate=args.max_net_retry_rate)
    if args.lint_report:
        failures.extend(lint_gate(args.lint_report))
    print(json.dumps({"ok": not failures, "failures": failures,
                      "checked": report}))
    if failures:
        for f in failures:
            print(f"REGRESSION: {f}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
