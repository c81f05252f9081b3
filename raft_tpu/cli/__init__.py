"""Command-line entry points (reference L6: train.py / evaluate.py /
demo.py / a_lk_vs_raft.py argparse scripts, SURVEY.md §1).

Run as modules::

    python -m raft_tpu.cli.train --name raft-chairs --stage chairs ...
    python -m raft_tpu.cli.evaluate --model checkpoints/raft-things ...
    python -m raft_tpu.cli.demo --model checkpoints/raft-things --path frames/
    python -m raft_tpu.cli.serve --model checkpoints/raft-things --port 8080
    python -m raft_tpu.cli.lk_compare --model checkpoints/raft-things ...

(or via the ``python -m raft_tpu <subcommand>`` multi-tool,
``raft_tpu/__main__.py``)
"""

from raft_tpu.config import ARCHS, RAFTConfig


def add_arch_argument(parser) -> None:
    """``--arch {full,small,gma,searaft,gmflow,unimatch}`` with ``--small``
    kept as an alias of ``--arch small``; read the result with
    :func:`arch_from_args`."""
    parser.add_argument("--arch", choices=ARCHS, default=None,
                        help="model architecture (default: full)")
    parser.add_argument("--small", action="store_true",
                        help="alias of --arch small")


def arch_from_args(args) -> str:
    if args.small and args.arch not in (None, "small"):
        raise SystemExit(f"--small and --arch {args.arch} disagree; "
                         "give one of them")
    return "small" if args.small else (args.arch or "full")


def parse_with_arch(parser, argv):
    """``parser.parse_args(argv)`` for a CLI that has ``--arch`` and
    ``--iters``: ``--iters`` is the length of a refinement loop, so for an
    architecture without one (``RAFTConfig.refines`` false) a value other
    than the flag's default is refused by name instead of being ignored,
    and for one published with its own count
    (``RAFTConfig.published_iters``) that count is the default."""
    args = parser.parse_args(argv)
    arch = arch_from_args(args)
    iters = RAFTConfig.preset(arch).published_iters
    if iters is not None:
        parser.set_defaults(iters=iters)
        args = parser.parse_args(argv)
    if (not RAFTConfig.preset(arch).refines
            and args.iters != parser.get_default("iters")):
        raise SystemExit(
            f"--arch {arch} has no refinement loop, so --iters "
            f"{args.iters} does not apply: leave --iters out")
    return args
