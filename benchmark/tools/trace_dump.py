"""What a traced run's trace holds, to be read by hand (which name does the
lookup kernel print?): runs one cell once with ``--trace 1`` and writes
``benchmark.trace.describe`` of its trace, the device's memory statistics
and the run's facts to FILE.

    python3 benchmark/tools/trace_dump.py FILE --workload <name> --seed <n> --seconds <s>
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run, trace  # noqa: E402


def main():
    out, argv = sys.argv[1], sys.argv[2:]

    def dump(trace_dir, facts):
        import jax

        d = trace.describe(trace_dir)
        d["memory_stats"] = jax.devices()[0].memory_stats()
        d["facts"] = facts
        with open(out, "w") as f:
            json.dump(d, f, indent=1, default=str)

    line, _, _ = run.run_cell(run.parse_args(argv + ["--trace", "1"]),
                              on_trace=dump)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
