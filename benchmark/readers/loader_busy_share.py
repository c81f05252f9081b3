"""How busy the loader's worker threads were: the seconds they spent
loading samples (decode + augment; every ``input`` record quotes the
cumulative ``sample_seconds_total`` as it stood when the record closed)
over the workers' wall time, in per cent, summed over the intervals
between the window's consecutive producer records.  An interval over three
times the median one is left out, seconds and wall both: the consumer
stood still in it (a traced run starts and stops the profiler inside a
step), so the feed was parked, not slack.  Near 100: the feed is the
bound.  Well under: the loop parks on the queue because it runs ahead."""

import statistics

from benchmark.readers import stage_clock


def read(ctx):
    recs = [r for r in stage_clock.window(ctx, "input")
            if "sample_seconds_total" in r]
    workers = int(ctx["traffic"].get("num_workers") or 0)
    if len(recs) < 2 or not workers:
        return None
    steps = [(b["t_end"] - a["t_end"],
              b["sample_seconds_total"] - a["sample_seconds_total"])
             for a, b in zip(recs, recs[1:])]
    longest = 3.0 * statistics.median(wall for wall, _ in steps)
    steps = [(wall, busy) for wall, busy in steps if 0 < wall <= longest]
    if not steps:
        return None
    return 100.0 * sum(busy for _, busy in steps) / (
        workers * sum(wall for wall, _ in steps))
