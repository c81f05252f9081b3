"""AOT executable export/import: zero-compile replica warm-start.

The engine's compile cache is keyed ``(bucket_hw, lanes, program)``
with ``program`` in ``{"enc", "iter"}`` (the iteration-granular
serving split, ``serve/slots.py``), and each entry is an explicit
``jit.lower(...).compile()`` product (``jax.stages.Compiled``).  XLA
lets those be serialized (``jax.experimental.serialize_executable``),
and — crucially — the executable takes the *variables pytree as a
runtime argument*, so one exported artifact warm-starts a replica with
ANY weights of the same tree structure: a supervised restart after a
crash AND the warming engine of a rolling weight update both import
the same blobs and serve their first request with **zero JIT
compiles** (``CompileCounter``-asserted in ``tests/test_fleet.py``).

Artifact layout (one directory)::

    manifest.json                  # fingerprint + key index (below)
    trees.pkl                      # pickled in/out pytree TEMPLATES
    exe-<H>x<W>-b<B>-<prog>.bin    # one serialized executable per key

``trees.pkl`` holds each blob's input/output tree *structures*
rendered as plain int-leaf templates (``treedef.unflatten(range(n))``)
— plain dicts/tuples, no jax objects — because ``serialize()`` returns
treedefs that are not themselves portable.  Trees are stored PER BLOB
(format v2): the ``enc`` and ``iter`` programs take different pytrees,
and the corr-state structure inside the slot state can vary with the
corr impl/dtype.

Compatibility gate: an artifact is refused (``AOTImportError``) unless
its fingerprint — model config + variables tree structure/shapes/dtypes
+ iters — AND backend AND jax version match the importing engine, and
each key's recorded *call* (``iter_step(variables, state, threshold,
steps)``: the program's function and arguments) is the call the
importing engine makes: an executable built for another argument list
is refused by its program's name, not called with the wrong arguments.
A stale artifact must fall back to lazy JIT compiles, never feed a
request through the wrong program.  The engine treats import failure as
a warm-start miss (``aot_import_error`` event), not a serve failure.

Blobs are pickles (that is the upstream wire format); treat artifact
directories with the same trust as checkpoint directories.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import tempfile
from typing import Callable, Dict, Optional, Tuple

MANIFEST = "manifest.json"
TREES = "trees.pkl"
# v2: (bucket, lanes, program) keys + per-blob tree templates (the
# iteration-granular serving split).  v1 artifacts (whole-forward
# executables) are refused and the engine falls back to lazy compiles.
FORMAT_VERSION = 2

#: What a key that records no ``call`` was built as.  Manifests carry
#: the call since ``iter_step`` took its step count at run time; of the
#: programs exported before that, only this one has changed since.
_UNRECORDED_CALLS = {"iter": "iter_step(variables, state, threshold)"}


class AOTImportError(RuntimeError):
    """Artifact missing/corrupt/incompatible — the importer refuses it
    (the engine falls back to lazy JIT compiles)."""


def _blob_name(key: tuple) -> str:
    (h, w), bs, prog = key
    return f"exe-{h}x{w}-b{bs}-{prog}.bin"


def model_fingerprint(model_cfg, variables, iters: int) -> str:
    """Hash of everything that must match for an exported executable to
    be the RIGHT program: the model config, the variables pytree
    structure + per-leaf shape/dtype, and the iteration count baked
    into the traced call."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(variables)[0]
    shapes = [(jax.tree_util.keystr(path), tuple(x.shape), str(x.dtype))
              for path, x in leaves]
    payload = json.dumps({
        "config": {k: repr(v) for k, v in sorted(
            dataclasses.asdict(model_cfg).items())},
        "shapes": shapes,
        # The full treedef, not just the leaves: an empty container
        # (e.g. a checkpoint layout adding ``batch_stats: {}``) changes
        # the executable's input pytree without changing any leaf, and
        # a structure-blind fingerprint would import an executable the
        # call site then cannot invoke.
        "treedef": str(jax.tree_util.tree_structure(variables)),
        "iters": int(iters),
    }, sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def _env_stamp() -> dict:
    import jax

    return {"jax": jax.__version__,
            "backend": jax.default_backend()}


def export_executables(executables: Dict[tuple, object], path: str, *,
                       fingerprint: str,
                       corr_impl: Optional[Callable[[tuple], str]] = None,
                       arch: Optional[str] = None,
                       calls: Optional[Dict[str, str]] = None) -> dict:
    """Serialize ``{(bucket, lanes, program): Compiled}`` into
    directory ``path`` (atomic per file: tmp + rename, so a concurrent
    importer never sees a torn blob).  Returns the manifest written.
    Keys already exported with identical bytes are overwritten in
    place — export is idempotent and may be re-run as the compile
    cache grows.

    ``corr_impl(bucket)``: the correlation implementation the exporting
    engine's model resolves to at that bucket; it is written beside each
    key, for :func:`import_executables` to hold against the importer's
    own.  ``arch``: the model the programs are of (``RAFTConfig.arch``),
    written beside each key and held against the importer's likewise: a
    program's key is ``(arch, bucket, lanes, program)`` wherever it
    leaves the engine that built it.  ``calls``: ``{program: "fn(args)"}``
    — how the exporting engine calls each program, written beside each
    key for the importer to hold against the call it makes itself."""
    from jax.experimental import serialize_executable as se

    if not executables:
        raise ValueError("nothing to export: empty executable cache "
                         "(warm the engine first)")
    os.makedirs(path, exist_ok=True)
    keys, trees = [], {}
    for key, exe in sorted(executables.items()):
        ser, in_tree, out_tree = se.serialize(exe)
        blob = _blob_name(key)
        trees[blob] = (
            in_tree.unflatten(list(range(in_tree.num_leaves))),
            out_tree.unflatten(list(range(out_tree.num_leaves))))
        _atomic_write(os.path.join(path, blob), ser)
        keys.append({"bucket": list(key[0]), "batch": int(key[1]),
                     "program": str(key[2]), "file": blob,
                     "arch": arch,
                     "call": calls.get(str(key[2])) if calls else None,
                     "corr_impl": (corr_impl(key[0]) if corr_impl
                                   else None),
                     "sha256": hashlib.sha256(ser).hexdigest(),
                     "bytes": len(ser)})
    _atomic_write(os.path.join(path, TREES), pickle.dumps(trees))
    manifest = dict(_env_stamp(), format_version=FORMAT_VERSION,
                    fingerprint=fingerprint, keys=keys)
    _atomic_write(os.path.join(path, MANIFEST),
                  json.dumps(manifest, indent=1).encode())
    return manifest


def _atomic_write(path: str, data: bytes) -> None:
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                               prefix=".aot-tmp-")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def read_manifest(path: str) -> dict:
    mpath = os.path.join(path, MANIFEST)
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except OSError as e:
        raise AOTImportError(f"no AOT manifest at {mpath}: {e}")
    except ValueError as e:
        raise AOTImportError(f"corrupt AOT manifest {mpath}: {e}")
    if manifest.get("format_version") != FORMAT_VERSION:
        raise AOTImportError(
            f"AOT artifact format {manifest.get('format_version')!r} "
            f"!= supported {FORMAT_VERSION}")
    return manifest


def import_executables(path: str, *, fingerprint: str,
                       execution_devices=None,
                       keys: Optional[Tuple[tuple, ...]] = None,
                       corr_impl: Optional[Callable[[tuple], str]] = None,
                       arch: Optional[str] = None,
                       calls: Optional[Dict[str, str]] = None
                       ) -> Dict[tuple, object]:
    """Load ``{(bucket, lanes, program): Compiled}`` from an artifact
    directory, gated on ``fingerprint`` + backend + jax version.
    ``execution_devices``: the device(s) the importing engine runs on
    (the ones its variables live on; default: the first local device,
    where a bare ``jax.device_put`` lands).  Always passed down: left to
    ITS default, ``deserialize_and_load`` spreads a one-device
    executable over every local device and the first call dies with
    "expected args to have N shards" on any host with more than one.
    The device assignment is baked into a blob: it loads on the device
    id it was compiled for and on no other (``KeyError`` from the
    unpickler, surfaced as :class:`AOTImportError`).  ``keys`` restricts
    the import (default: everything in the manifest).
    ``corr_impl(bucket)`` is the correlation implementation the
    importing engine's model resolves to at that bucket: for a
    materialized pyramid it is chosen from platform and shape when a
    program traces (``models.raft.corr_impl_at``), not by the config
    the fingerprint hashes, and the two lookups keep the pyramid in
    different layouts, so a key recorded under another one (or under
    none: an artifact from before the choice existed) is refused — an
    imported ``enc`` beside a freshly built ``iter`` would otherwise
    disagree on the slot state.  ``arch``: the importing engine's model;
    a key recorded under another model is refused by name, before the
    fingerprint is looked at.  ``calls``: ``{program: "fn(args)"}`` as
    the importing engine calls each program; a key recorded under
    another call (an ``iter`` from before it took ``steps``) is refused
    with both spelled out.  Raises
    :class:`AOTImportError` on any mismatch or
    corruption — partial results are never returned (an artifact
    either warm-starts the whole ladder or is refused)."""
    import jax
    from jax.experimental import serialize_executable as se

    manifest = read_manifest(path)
    if arch is not None:
        built = sorted({str(e.get("arch")) for e in manifest["keys"]}
                       - {arch})
        if built:
            raise AOTImportError(
                f"AOT artifact holds programs of model {', '.join(built)}"
                f"; this engine runs {arch!r} and will not reuse them")
    env = _env_stamp()
    for field, want in (("fingerprint", fingerprint),
                        ("jax", env["jax"]),
                        ("backend", env["backend"])):
        got = manifest.get(field)
        if got != want:
            raise AOTImportError(
                f"AOT artifact {field} mismatch: artifact has {got!r}, "
                f"this engine needs {want!r} (stale export? re-run "
                "export on this build)")
    try:
        with open(os.path.join(path, TREES), "rb") as f:
            trees = pickle.load(f)
    except (OSError, pickle.UnpicklingError, ValueError, EOFError) as e:
        raise AOTImportError(f"corrupt AOT tree templates: {e}")
    if not isinstance(trees, dict):
        raise AOTImportError("AOT tree templates are not the per-blob "
                             "v2 layout (stale artifact?)")

    if execution_devices is None:
        execution_devices = jax.local_devices()[:1]
    wanted = None if keys is None else {
        (tuple(b), int(bs), str(prog)) for (b, bs, prog) in keys}
    out: Dict[tuple, object] = {}
    for entry in manifest["keys"]:
        key = (tuple(entry["bucket"]), int(entry["batch"]),
               str(entry["program"]))
        if wanted is not None and key not in wanted:
            continue
        if (corr_impl is not None
                and entry.get("corr_impl") != corr_impl(key[0])):
            raise AOTImportError(
                f"AOT blob {entry['file']} was built with corr_impl "
                f"{entry.get('corr_impl')!r}, this engine builds "
                f"{key[0][0]}x{key[0][1]} with {corr_impl(key[0])!r} "
                "(re-run export on this build)")
        if calls is not None:
            want = calls[key[2]]
            got = entry.get("call") or _UNRECORDED_CALLS.get(key[2], want)
            if got != want:
                raise AOTImportError(
                    f"AOT blob {entry['file']} is the program {key[2]!r} "
                    f"built to be called as {got}; this engine calls "
                    f"{want} (re-run export on this build)")
        blob_path = os.path.join(path, entry["file"])
        try:
            with open(blob_path, "rb") as f:
                ser = f.read()
        except OSError as e:
            raise AOTImportError(f"missing AOT blob {blob_path}: {e}")
        if hashlib.sha256(ser).hexdigest() != entry["sha256"]:
            raise AOTImportError(
                f"AOT blob {entry['file']} checksum mismatch "
                "(torn write?)")
        templates = trees.get(entry["file"])
        if templates is None:
            raise AOTImportError(
                f"AOT blob {entry['file']} has no tree template")
        in_tree = jax.tree_util.tree_structure(templates[0])
        out_tree = jax.tree_util.tree_structure(templates[1])
        try:
            out[key] = se.deserialize_and_load(
                ser, in_tree, out_tree,
                execution_devices=list(execution_devices))
        except Exception as e:
            raise AOTImportError(
                f"AOT blob {entry['file']} failed to deserialize: "
                f"{type(e).__name__}: {e}")
    if not out:
        raise AOTImportError(
            f"AOT artifact at {path} holds none of the requested keys")
    return out
