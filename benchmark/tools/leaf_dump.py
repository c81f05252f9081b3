"""The leaf-by-leaf table behind a training run's worst-leaf numbers, to
find why a seed reads high: runs one cell once as ``run.py`` does and writes
to FILE, for every comparison the run makes (the program, then with
``--reference-quant`` the planted half batch and each control) and for the
unit's run (the reference on bfloat16-rounded weights), each leaf's norm on
both sides and the norm of the difference, of the first gradient and of the
parameters' change.  Prints the run's result line.

    python3 benchmark/tools/leaf_dump.py FILE --workload <name> --seed <n> --seconds <s>
"""
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check, run  # noqa: E402
from benchmark.kinds import train  # noqa: E402


def leaf_table(side, ref):
    """{leaf: [norm of side, norm of ref, norm of side - ref]}"""
    a, b = dict(_flat(side)), dict(_flat(ref))
    return {k: [_norm(a[k]), _norm(b[k]), _norm(a[k] - b[k])] for k in b}


def _flat(tree, prefix=()):
    if isinstance(tree, dict) or hasattr(tree, "items"):
        for k, v in tree.items():
            yield from _flat(v, prefix + (str(k),))
    else:
        yield "/".join(prefix), np.asarray(tree, np.float64)


def _norm(a):
    return float(np.sqrt(np.sum(a * a)))


def main():
    out, argv = sys.argv[1], sys.argv[2:]
    tables = []
    compare, unit_of = train.compare, train.gradient_unit

    def spy_compare(variables, ref, prog, info, *rest):
        p0 = dict(_flat(variables["params"]))
        delta = [{k: v - p0[k] for k, v in _flat(t)}
                 for t in (prog[2], ref[2])]
        tables.append({"losses": list(prog[0]), "ref_losses": list(ref[0]),
                       "grad": leaf_table(prog[1], ref[1]),
                       "change": leaf_table(*delta)})
        return compare(variables, ref, prog, info, *rest)

    def spy_unit(variables, ref, follow, batches):
        def spy_follow(*a, **kw):
            got = follow(*a, **kw)
            tables.append({"unit_grad": leaf_table(got[1], ref[1])})
            return got

        return unit_of(variables, ref, spy_follow, batches)

    train.compare, train.gradient_unit = spy_compare, spy_unit
    args = run.parse_args(argv)
    line, table, correct = run.run_cell(args)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"seed": args.seed, "tables": tables, "line": line}, f)
    check.print_table(table, correct)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
