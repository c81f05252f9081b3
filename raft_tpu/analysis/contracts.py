"""CLI drift checker (rules CFG401, CFG402).

The argparse flags in ``cli/*.py`` and ``scripts/*.py`` and the docs
that name them are kept aligned by nothing but convention.  Drift here
is user-facing: a flag that parses but is never read silently ignores
the user's intent; a doc that names a flag the CLI dropped sends them
to ``error: unrecognized arguments``.

Rules:

- ``CFG401`` dead flag: an ``add_argument`` whose dest is never
  consumed in its own module — not accessed as an attribute
  (``args.<dest>``), not named in a string literal (``getattr`` /
  dict-key forwarding), and the module doesn't bulk-forward via
  ``vars(args)``.  The match is deliberately lenient; what it still
  catches is the flag nothing reads at all.
- ``CFG402`` phantom doc flag: ``--flag`` named inside a backtick
  span in ``README.md`` / ``docs/*.md`` that no argparse declaration
  anywhere in the repo provides.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from raft_tpu.analysis.core import Finding, Workspace

CLI_SCOPE = ("raft_tpu/cli", "scripts", "raft_tpu/convert.py",
             "chip_smoke.py")
DOC_SCOPE = ("README.md", "docs")

#: ``--flag`` / ``--flag_name`` inside a backtick span.
_BACKTICK_RE = re.compile(r"`([^`]+)`")
_FLAG_RE = re.compile(r"--[A-Za-z0-9][-A-Za-z0-9_]*")


def _str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class _Flag:
    __slots__ = ("dest", "options", "path", "line")

    def __init__(self, dest, options, path, line):
        self.dest = dest
        self.options = options
        self.path = path
        self.line = line


def collect_flags(ws: Workspace,
                  scope: Sequence[str] = CLI_SCOPE) -> List[_Flag]:
    flags: List[_Flag] = []
    for sf in ws.glob_py(*scope, exclude=("tests/",)):
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add_argument"):
                continue
            options = [s for s in map(_str_const, node.args)
                       if s and s.startswith("-")]
            positional = [s for s in map(_str_const, node.args)
                          if s and not s.startswith("-")]
            dest = None
            for kw in node.keywords:
                if kw.arg == "dest":
                    dest = _str_const(kw.value)
            if dest is None:
                longs = [o for o in options if o.startswith("--")]
                if longs:
                    dest = longs[0].lstrip("-").replace("-", "_")
                elif positional:
                    dest = positional[0]
                elif options:
                    dest = options[0].lstrip("-")
            if dest:
                flags.append(_Flag(dest, options or positional,
                                   sf.relpath, node.lineno))
    return flags


def _module_consumes(sf) -> Tuple[Set[str], bool]:
    """``(names, bulk)`` — attribute/string names the module touches,
    and whether it bulk-forwards a namespace via ``vars(...)``."""
    names: Set[str] = set()
    bulk = False
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Attribute):
            names.add(node.attr)
        elif isinstance(node, ast.Constant) and \
                isinstance(node.value, str):
            names.add(node.value)
        elif isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name) and f.id == "vars":
                bulk = True
    return names, bulk


def check(ws: Workspace,
          cli_scope: Sequence[str] = CLI_SCOPE,
          doc_scope: Sequence[str] = DOC_SCOPE) -> List[Finding]:
    findings: List[Finding] = []
    flags = collect_flags(ws, cli_scope)

    # ------------------------------ CFG401 ----------------------------
    consumes: Dict[str, Tuple[Set[str], bool]] = {}
    for f in flags:
        if f.path not in consumes:
            consumes[f.path] = _module_consumes(ws.get(f.path))
        names, bulk = consumes[f.path]
        if bulk or f.dest in names:
            continue
        opt = f.options[0] if f.options else f.dest
        findings.append(Finding(
            "CFG401", f.path, f.line, f"{f.path}:{opt}",
            f"flag `{opt}` parses into `args.{f.dest}` but nothing "
            f"in {f.path} reads it — the user's setting is silently "
            "ignored; wire it through or delete the flag"))

    # ------------------------------ CFG402 ----------------------------
    declared: Set[str] = set()
    for f in flags:
        for o in f.options:
            if o.startswith("--"):
                declared.add(o)

    # Docs mix dash and underscore spellings; compare normalized.
    def norm(flag: str) -> str:
        return flag.lstrip("-").replace("-", "_")

    declared_norm = {norm(o) for o in declared}
    doc_files: List[Tuple[str, str]] = []
    for entry in doc_scope:
        abspath = os.path.join(ws.root, entry)
        if os.path.isfile(abspath):
            doc_files.append((entry, abspath))
        elif os.path.isdir(abspath):
            for fn in sorted(os.listdir(abspath)):
                if fn.endswith(".md"):
                    doc_files.append((f"{entry}/{fn}",
                                      os.path.join(abspath, fn)))
    for relpath, abspath in doc_files:
        with open(abspath, encoding="utf-8", errors="replace") as fh:
            text = fh.read()
        seen: Set[str] = set()
        for i, line in enumerate(text.splitlines(), start=1):
            for span in _BACKTICK_RE.findall(line):
                for m in _FLAG_RE.findall(span):
                    if norm(m) in declared_norm or m in seen:
                        continue
                    seen.add(m)
                    findings.append(Finding(
                        "CFG402", relpath, i, m,
                        f"doc names flag `{m}` but no argparse "
                        "declaration under "
                        f"{'/'.join(cli_scope)} provides it — "
                        "readers get `unrecognized arguments`"))
    return findings
