"""Device mesh + sharding helpers (the XLA-collective replacement for the
reference's ``nn.DataParallel``, train.py:138 / SURVEY.md C16).

Design: a 2-D ``(data, spatial)`` mesh.  Data parallelism shards the batch
over ``data`` (gradient psum rides ICI, inserted by XLA from the sharding
annotations — no hand-written collectives).  The ``spatial`` axis is
reserved for sharding the correlation volume / feature maps over image
height for very large inputs (the long-context analog; SURVEY.md §5);
size 1 until explicitly requested.

Multi-host: each process constructs the same global mesh from
``jax.devices()`` and feeds only its addressable shard of the batch
(``raft_tpu.data.ShardedLoader`` handles the per-host slicing) —
DCN-vs-ICI placement is XLA's job, not ours.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
SPATIAL_AXIS = "spatial"


def make_mesh(num_data: Optional[int] = None, num_spatial: int = 1,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the ``(data, spatial)`` mesh.  Defaults to all devices on the
    data axis — RAFT at 5.3M params wants pure DP (SURVEY.md C16)."""
    devices = list(devices if devices is not None else jax.devices())
    if num_data is None:
        assert len(devices) % num_spatial == 0
        num_data = len(devices) // num_spatial
    n = num_data * num_spatial
    assert n <= len(devices), (num_data, num_spatial, len(devices))
    grid = np.asarray(devices[:n]).reshape(num_data, num_spatial)
    return Mesh(grid, (DATA_AXIS, SPATIAL_AXIS))


_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "raft_kernel_mesh", default=None)
_ROWS_SPLIT: contextvars.ContextVar = contextvars.ContextVar(
    "raft_rows_split", default=False)


@contextlib.contextmanager
def data_parallel_kernels(mesh: Optional[Mesh], rows_split: bool = False):
    """While active, the Pallas entry points (``ops/pallas_util.py``
    ``per_data_shard``) run per ``data`` shard of ``mesh``.  Entered by
    ``make_train_step`` around the trace of a step whose batch is
    sharded over more than one device; a mesh whose ``data`` axis has
    one device is the single-device program and changes nothing.

    ``rows_split``: the step also splits image height over the
    ``spatial`` axis (``shard_spatial``).  A trace sees no shardings, so
    this is how the choice of lookup (``models.raft.corr_impl_at``)
    learns that a whole-image kernel cannot run here."""
    if mesh is not None and mesh.shape[DATA_AXIS] == 1:
        mesh = None
    token = _KERNEL_MESH.set(mesh)
    rows_token = _ROWS_SPLIT.set(bool(rows_split))
    try:
        yield
    finally:
        _ROWS_SPLIT.reset(rows_token)
        _KERNEL_MESH.reset(token)


def kernel_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing :func:`data_parallel_kernels`, if any."""
    return _KERNEL_MESH.get()


def image_rows_split() -> bool:
    """Whether the enclosing :func:`data_parallel_kernels` traces a step
    that splits image rows over devices."""
    return _ROWS_SPLIT.get()


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading (batch) dim sharded over the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def spatial_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch over ``data`` AND image height over ``spatial`` — the
    long-context analog for RAFT (SURVEY.md §5): activations, the
    correlation pyramid's query rows, and the refinement state are split
    across chips by image rows, and GSPMD inserts the conv halo exchanges
    and cross-shard reductions (instance-norm statistics, all-pairs
    fmap2 gathers) automatically.  Use for inputs too large for one
    chip's HBM (720p+ all-pairs volumes)."""
    return NamedSharding(mesh, P(DATA_AXIS, SPATIAL_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def place_replicated(tree, mesh: Mesh):
    """``tree`` with every leaf a global array replicated over ``mesh``:
    the placement, and the type, that the train step gives its state
    back with (``out_shardings`` in ``train/step.py``).  A state placed
    here enters the step's first call under the jit cache key of every
    later call, so the step is traced, lowered and compiled once.

    Single-host: one sharded ``device_put`` (a leaf already on the
    mesh's first device keeps its buffer there).  Multi-host: each
    process holds the whole value, and the global array is assembled
    from those copies as :func:`make_batch_sharder` assembles a batch; a
    leaf that already is a global array on this sharding (a resumed
    state) is kept as it is."""
    sh = replicated_sharding(mesh)
    if jax.process_count() == 1:
        return jax.device_put(tree, sh)
    return jax.tree_util.tree_map(
        lambda x: x if getattr(x, "sharding", None) == sh
        else jax.make_array_from_process_local_data(sh, np.asarray(x)),
        tree)


def mesh_shape(mesh: Mesh) -> Dict[str, int]:
    """``{'data': N, 'spatial': K}`` — the serializable topology stamp
    checkpoints record so a restore on a DIFFERENT mesh can report what
    the run was saved under (docs/ROBUSTNESS.md "Elastic resume")."""
    return {name: int(size)
            for name, size in zip(mesh.axis_names, mesh.devices.shape)}


def abstract_replicated(tree, mesh: Mesh):
    """Abstract (shape/dtype/sharding-only) view of ``tree`` with every
    leaf replicated over ``mesh`` — the reshard-on-restore template.

    Handing orbax an abstract template that CARRIES the target sharding
    makes the restore place bytes directly onto the new topology,
    whatever mesh (or device count) the checkpoint was saved under;
    restoring against concrete arrays instead would pin the layout to
    the template's (old) placement.  Params/opt_state are replicated in
    this repo (train/step.py), so ``P()`` everywhere is exact."""
    sh = replicated_sharding(mesh)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sh),
        tree)


def make_batch_sharder(mesh: Mesh, spatial: bool = False):
    """Build ``put(batch) -> sharded batch``: the host->device placement
    closure with the sharding and the single/multi-host branch resolved
    ONCE (the device-prefetch producer calls it once per batch from a
    background thread; ``raft_tpu/data/prefetch.py``).

    Single-host: a plain sharded ``device_put`` — dispatch is async, so
    the call returns as soon as the transfer is enqueued and the H2D copy
    itself overlaps whatever the device is running.  Multi-host: each
    process passes its *local* batch (its stride of the global shuffle
    from ``ShardedLoader``) and the global array is assembled from the
    process-local shards — the global batch is ``num_hosts * local_batch``.
    """
    sh = spatial_batch_sharding(mesh) if spatial else batch_sharding(mesh)
    if jax.process_count() == 1:
        def put(batch):
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sh), batch)
    else:
        def put(batch):
            return jax.tree_util.tree_map(
                lambda x: jax.make_array_from_process_local_data(sh, x),
                batch)
    return put


def shard_batch(batch: Dict[str, np.ndarray], mesh: Mesh,
                spatial: bool = False):
    """Place a host batch onto the mesh, batch-dim sharded over ``data``
    (and, with ``spatial=True``, image height over ``spatial``).

    One-shot form of :func:`make_batch_sharder` (see there for the
    single/multi-host semantics)."""
    return make_batch_sharder(mesh, spatial=spatial)(batch)
