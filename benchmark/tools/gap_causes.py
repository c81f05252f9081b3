"""Which stage of the program's own loops held the chip idle: runs one cell
once with ``--trace 1`` and puts EVERY idle interval of the first device
plane (all of them, not the ten longest) down to the innermost
``raft/<loop>/<stage>`` annotation of the stage clock
(``raft_tpu/obs/stages.py``) that covers its middle, followed by the
shortest informative event among XLA's own host events there, as
``benchmark.trace.host_doing`` picks it, and whether that event ran on the
stage's own thread (``raft/serve/launch > Transpose (another thread)``).
An interval no stage covers gets XLA's name alone; those under
``MIN_NAMED_S`` are summed in one row (naming each costs a scan of the
host plane).  Prints idle seconds by cause, where the gaps of a
millisecond and more lie in their stage, and the ``raft/`` annotations the
host plane holds; FILE also gets each loop's slowest unit cycles beside
its median unit (which stage held a stall), every unit over 20 ms slower
than the median with its distance from the newest one (the capture
begins ``trace_seconds`` before the window closes and stalls the loops
itself), and the programs XLA traced, lowered, built or loaded so far
with their seconds.  Like ``tools/trace_dump.py`` it sees the trace before
the harness deletes it.

    python3 benchmark/tools/gap_causes.py FILE --workload <name> --seed <n> --seconds <s>

FILE gets the table as JSON; the run's result line is the last line
printed.
"""
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run, trace  # noqa: E402
from benchmark.readers import stage_clock  # noqa: E402

MIN_NAMED_S = 20e-6
UNNAMED = "intervals under 20 us outside every stage"


def read_planes(path):
    """-> (device events of the first TPU plane as ``benchmark.trace``
    labels them, host events as (name, start_ns, duration_ns, thread))."""
    from jax.profiler import ProfileData

    device, _ = trace.read_xplane(path)
    threads = [(e.name, int(e.start_ns), int(e.duration_ns), line.name)
               for plane in ProfileData.from_file(path).planes
               if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events]
    return (device[sorted(device)[0]] if device else []), threads


def doing(host_events, t_ns):
    """``benchmark.trace.host_doing`` that also says on which thread:
    (name, thread) of the shortest informative event covering ``t_ns``."""
    best = None
    for name, s, d, thread in host_events:
        if s <= t_ns < s + d and not trace._DULL.match(name):
            if best is None or d < best[0]:
                best = (d, name, thread)
    return best[1:] if best else ("host idle or untraced", None)


def idle_by_cause(device_events, host_events):
    """-> {idle_by_cause_s, gaps_in_stages, annotations, busy_s, window_s,
    idle_s} from ``(name, start_ns, duration_ns)`` device events and
    ``(name, start_ns, duration_ns, thread)`` host events.
    ``gaps_in_stages``: for each cause, of its gaps of a millisecond and
    more inside a stage, [how many, median ms, median ms from the stage's
    start to the gap's, median ms from the stage's end to the gap's]."""
    ev = [(s, d) for _, s, d in device_events if d > 0]
    window = (min(s for s, _ in ev), max(s + d for s, d in ev))
    staged = sorted((s, s + d, n, t) for n, s, d, t in host_events
                    if n.startswith("raft/"))
    seen = {}
    for s, e, n, _ in staged:
        row = seen.setdefault(n, [0, 0.0])
        row[0] += 1
        row[1] += (e - s) / 1e9
    others = [e for e in host_events if not e[0].startswith("raft/")]
    causes, placed = {}, {}
    active, nxt = [], 0             # the stages open at the gap's middle
    for start, dur in trace.gaps(ev, window):     # in order of time
        mid = start + dur // 2
        while nxt < len(staged) and staged[nxt][0] <= mid:
            active.append(staged[nxt])
            nxt += 1
        active = [a for a in active if a[1] > mid]
        stage = min(active, key=lambda a: a[1] - a[0], default=None)
        if dur / 1e9 < MIN_NAMED_S:
            name = UNNAMED if stage is None else f"{stage[2]} > short"
        else:
            name, thread = doing(others, mid)
            if stage is not None:
                where = ("the stage's thread" if thread == stage[3]
                         else "another thread")
                name = f"{stage[2]} > {name}" + (
                    f" ({where})" if thread else "")
                if dur >= 1e6:
                    placed.setdefault(name, []).append(
                        (dur / 1e6, (start - stage[0]) / 1e6,
                         (start + dur - stage[1]) / 1e6))
        causes[name] = causes.get(name, 0.0) + dur / 1e9
    busy = trace.union_ns(ev) / 1e9
    return {
        "busy_s": busy, "window_s": (window[1] - window[0]) / 1e9,
        "idle_s": (window[1] - window[0]) / 1e9 - busy,
        "idle_by_cause_s": dict(sorted(causes.items(),
                                       key=lambda kv: -kv[1])),
        "gaps_in_stages": {
            name: [len(rows)] + [round(statistics.median(col), 3)
                                 for col in zip(*rows)]
            for name, rows in placed.items()},
        "annotations": seen}


def slow_cycles(loop, window_s, top=3):
    """The loop's ``top`` longest unit cycles of the window beside its
    median unit, and every unit over 20 ms slower than the median as
    [seconds before the newest unit ended, ms over the median]; {} where
    the program has no stage clock."""
    recs = stage_clock.ring(loop, window_s)
    if not recs:
        return {}
    names = sorted({s for r in recs for s in r["stages"]})
    med = {s: statistics.median(r["stages"].get(s, 0.0) for r in recs)
           for s in names}
    med_cycle = statistics.median(stage_clock.cycle(r) for r in recs)
    newest = recs[-1]["t_end"]

    def over(r):
        return 1e3 * (stage_clock.cycle(r) - med_cycle)

    return {
        "units": len(recs), "median_cycle_ms": 1e3 * med_cycle,
        "median_stages_ms": {s: round(1e3 * v, 3) for s, v in med.items()},
        "slowest": [{
            "n": r["n"], "before_newest_s": round(newest - r["t_end"], 3),
            "over_median_ms": round(over(r), 3),
            "stages_over_median_ms": {
                s: round(1e3 * (r["stages"].get(s, 0.0) - med[s]), 3)
                for s in names}}
            for r in sorted(recs, key=stage_clock.cycle)[:-top - 1:-1]],
        "over_20_ms": [[round(newest - r["t_end"], 3), round(over(r), 1)]
                       for r in recs if over(r) > 20.0]}


def programs_built(least_s=0.5):
    """The ``compile`` ring so far, in order: every trace, lowering,
    compile and load from the persistent cache of ``least_s`` seconds or
    more as [name, kind, seconds], the shorter ones summed by kind (traces
    and lowerings of nested functions overlap their callers')."""
    recs = stage_clock.ring("compile")
    rows = [[r.get("name"), r["kind"], round(r["seconds"], 3)]
            for r in recs if r["seconds"] >= least_s]
    for kind in sorted({r["kind"] for r in recs}):
        short = [r["seconds"] for r in recs
                 if r["kind"] == kind and r["seconds"] < least_s]
        rows.append([f"{len(short)} shorter", kind, round(sum(short), 3)])
    return rows


def main():
    out, argv = sys.argv[1], sys.argv[2:]
    table = {}

    def on_trace(trace_dir, facts):
        device, host = read_planes(trace.find_xplane(trace_dir))
        table.update(slow_cycles={loop: slow_cycles(loop, facts["window_s"])
                                  for loop in ("serve", "train", "input")},
                     programs_built=programs_built())
        # a CPU rehearsal has stages and no device plane
        table.update(idle_by_cause(device or [("no device plane", 0, 1)],
                                   host))

    line, _, _ = run.run_cell(run.parse_args(argv + ["--trace", "1"]),
                              on_trace=on_trace)
    with open(out, "w") as f:
        json.dump(table, f, indent=1)
    idle = table.get("idle_s") or 0.0
    print(f"idle {idle:.4f} s of {table.get('window_s', 0.0):.4f} s traced")
    for name, s in table.get("idle_by_cause_s", {}).items():
        print(f"  {s:9.4f} s  {100 * s / idle if idle else 0:5.1f} %  {name}")
    for name, (n, ms, from_start, past_end) in table.get(
            "gaps_in_stages", {}).items():
        print(f"  {n} gaps of 1 ms and more in {name}: median {ms} ms, "
              f"beginning {from_start} ms after the stage began, ending "
              f"{past_end} ms after it ended")
    for name, (n, s) in sorted(table.get("annotations", {}).items()):
        print(f"  annotation {name}: {n} events, {s:.4f} s")
    print(json.dumps(line))


if __name__ == "__main__":
    main()
