"""Shared Pallas dispatch helpers.

One home for the two decisions every kernel in this repo used to make
for itself (copy-pasted between ``ops/pallas_corr.py`` and
``ops/pallas_upsample.py`` until PR 13):

- **interpret-mode selection** (:func:`auto_interpret`): Pallas kernels
  run natively only on TPU; on the CPU test backend they run in the
  interpreter, and on other accelerators they warn.
- **compiler-params construction** (:func:`compiler_params` /
  :func:`tpu_pallas_call`): the repo-wide 100 MB ``vmem_limit_bytes``
  default lives here, so a VMEM budget change is one edit, not four.
- **partitioning** (:func:`per_data_shard`): a Mosaic kernel is opaque
  to GSPMD ("Mosaic kernels cannot be automatically partitioned"), so
  under a data-parallel mesh every kernel entry point runs per batch
  shard inside a ``jax.shard_map`` over the mesh's ``data`` axis.
"""

from __future__ import annotations

import warnings

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from raft_tpu.parallel.mesh import DATA_AXIS, kernel_mesh

# Every kernel in the repo declares the same VMEM budget: large enough
# for the beyond-HBM correlation levels, small enough that Mosaic still
# double-buffers block DMA (see pallas_corr.py "VMEM sizing").
_DEFAULT_VMEM_LIMIT_MB = 100

_warned_interpret = False


def auto_interpret() -> bool:
    """Interpret mode for every non-TPU backend.

    Meant for the CPU test backend; on an accelerator backend that is
    not a TPU (e.g. GPU) the interpreter would be pathologically slow,
    so warn once — callers there should prefer the XLA paths
    (``corr_impl='allpairs'``/``'chunked'``, ``convex_upsample_flat`` +
    ``sequence_loss``).
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend != "cpu":
        global _warned_interpret
        if not _warned_interpret:
            _warned_interpret = True
            warnings.warn(
                f"Pallas kernels on backend {backend!r} run in the (very "
                "slow) Pallas interpreter; prefer the XLA implementations "
                "on this backend", stacklevel=3)
    return True


def compiler_params(vmem_limit_mb: int = _DEFAULT_VMEM_LIMIT_MB):
    """The repo-standard TPU compiler params."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=vmem_limit_mb * 1024 * 1024)


def tpu_pallas_call(kernel, *, interpret=None,
                    vmem_limit_mb: int = _DEFAULT_VMEM_LIMIT_MB, **kw):
    """``pl.pallas_call`` with the repo-wide dispatch conventions.

    ``interpret=None`` resolves via :func:`auto_interpret` (native on
    TPU, interpreter on the CPU test backend); an explicit bool is
    passed through untouched (callers that already resolved it — e.g.
    through ``RAFTConfig.pallas_offtpu`` — stay in charge).  All other
    keyword arguments are forwarded to ``pl.pallas_call``.
    """
    if interpret is None:
        interpret = auto_interpret()
    return pl.pallas_call(
        kernel,
        compiler_params=compiler_params(vmem_limit_mb),
        interpret=interpret,
        **kw)


#: ``in_specs``/``out_specs`` entry for an operand whose leading dim is
#: the batch (a pytree prefix: it covers every level of a pyramid).
BATCH = P(DATA_AXIS)
#: ... and for one every shard needs whole (conv weights, biases).
WHOLE = P()


def per_data_shard(fn, in_specs, out_specs=BATCH):
    """``fn``, run once per batch shard when a data-parallel mesh is in
    play (``raft_tpu.parallel.mesh.kernel_mesh`` — set by
    ``make_train_step`` while it traces), else ``fn`` itself.

    GSPMD cannot split a ``tpu_custom_call``: lowering a Pallas kernel
    whose operands are sharded raises ``NotImplementedError: Mosaic
    kernels cannot be automatically partitioned`` (and interpret mode,
    which lowers to ordinary HLO, hides that on the CPU mesh).  Every
    kernel here is independent per batch element, so the partitioning
    is stated instead: each device runs the unchanged kernel on its own
    ``data`` shard.  The wrap goes around the whole ``custom_vjp``
    entry point, so the backward kernels run per shard too and the
    cotangent of a ``WHOLE`` operand is summed over the axis by
    ``shard_map``'s transpose.  Nothing else of the step is under
    ``shard_map`` — BatchNorm statistics and the gradient all-reduce
    stay GSPMD's, over the global batch.

    ``check_vma=False``: ``pallas_call`` outputs carry no varying-axes
    annotation.
    """
    mesh = kernel_mesh()
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
