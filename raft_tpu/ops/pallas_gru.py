"""Fused ConvGRU gate chains as Pallas TPU elementwise kernels.

The ConvGRU update (models/update.py ``ConvGRU``/``SepConvGRU``) is two
convolutions plus two bandwidth-bound elementwise chains:

    z, r = sigmoid(split(convzr([h, x])))      # chain 1 consumes r
    q    = tanh(convq([r * h, x]))
    h'   = (1 - z) * h + z * q                 # chain 2 consumes z, q

The convolutions stay XLA (convq's input depends on r, so conv+gate
cannot be one kernel without reimplementing conv), but each chain
becomes ONE Pallas VMEM pass instead of an XLA elementwise chain with
HBM round-trips between the sigmoid/tanh/blend stages:

- :func:`gru_gate_rh`     — ``sigmoid(r_raw) * h``
- :func:`gru_gate_blend`  — ``(1-sigmoid(z_raw))*h + sigmoid(z_raw)*tanh(q_raw)``

Both compute fp32 in VMEM regardless of the storage dtype and cast the
result back to ``h.dtype`` (the unfused path computes in the compute
dtype throughout, so under bf16 the two paths differ at rounding level;
under fp32 they match to float ulps).  Gradients run a recomputing
``custom_vjp`` (the pallas_upsample.py template): the backward kernel
re-derives sigmoid/tanh from the saved raw activations — nothing but
the primal inputs is kept live.

Layout: operands are flattened, zero-padded to a whole number of
``(256, 128)`` fp32-tile-aligned blocks and processed on a 1-D grid —
elementwise math has no spatial structure worth preserving, and the
flat layout keeps every block full-lane regardless of the (B, H, W, C)
shape.  Gate selection is ``RAFTConfig.fused_gru`` (default off, never
timed on the chip — see docs/PERFORMANCE.md "Fused kernels").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from raft_tpu.ops.pallas_util import (BATCH, auto_interpret,
                                      per_data_shard, tpu_pallas_call)

_LANES = 128
_BLOCK_ROWS = 256


# ---------------------------------------------------------------------------
# layout: NHWC (or any shape) <-> padded (rows, 128)
# ---------------------------------------------------------------------------

def _to_rows(arrays):
    """Flatten same-shape operands to blocked ``(rows, 128)`` layout."""
    shape = arrays[0].shape
    n = 1
    for d in shape:
        n *= d
    rows = -(-n // _LANES)
    rows = -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS
    pad = rows * _LANES - n
    out = [jnp.pad(a.reshape(-1), (0, pad)).reshape(rows, _LANES)
           for a in arrays]
    return out, shape, n


def _rows_call(kernel, inputs, out_dtypes, interpret):
    rows = inputs[0].shape[0]
    spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))
    shapes = [jax.ShapeDtypeStruct((rows, _LANES), d) for d in out_dtypes]
    single = len(out_dtypes) == 1
    return tpu_pallas_call(
        kernel,
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[spec] * len(inputs),
        out_specs=spec if single else [spec] * len(out_dtypes),
        out_shape=shapes[0] if single else shapes,
        interpret=interpret)(*inputs)


# ---------------------------------------------------------------------------
# kernel bodies (fp32 compute in VMEM, cast on the way out)
# ---------------------------------------------------------------------------

def _rh_fwd_kernel(r_ref, h_ref, o_ref):
    r = r_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    o_ref[...] = (jax.nn.sigmoid(r) * h).astype(o_ref.dtype)


def _rh_bwd_kernel(r_ref, h_ref, g_ref, dr_ref, dh_ref):
    r = r_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    s = jax.nn.sigmoid(r)
    dr_ref[...] = (g * h * s * (1.0 - s)).astype(dr_ref.dtype)
    dh_ref[...] = (g * s).astype(dh_ref.dtype)


def _blend_fwd_kernel(z_ref, q_ref, h_ref, o_ref):
    sz = jax.nn.sigmoid(z_ref[...].astype(jnp.float32))
    tq = jnp.tanh(q_ref[...].astype(jnp.float32))
    h = h_ref[...].astype(jnp.float32)
    o_ref[...] = ((1.0 - sz) * h + sz * tq).astype(o_ref.dtype)


def _blend_bwd_kernel(z_ref, q_ref, h_ref, g_ref, dz_ref, dq_ref, dh_ref):
    sz = jax.nn.sigmoid(z_ref[...].astype(jnp.float32))
    tq = jnp.tanh(q_ref[...].astype(jnp.float32))
    h = h_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    dz_ref[...] = (g * (tq - h) * sz * (1.0 - sz)).astype(dz_ref.dtype)
    dq_ref[...] = (g * sz * (1.0 - tq * tq)).astype(dq_ref.dtype)
    dh_ref[...] = (g * (1.0 - sz)).astype(dh_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp cores over the blocked layout
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rh_core(r2, h2, interpret):
    return _rows_call(_rh_fwd_kernel, [r2, h2], [h2.dtype], interpret)


def _rh_core_fwd(r2, h2, interpret):
    return _rh_core(r2, h2, interpret), (r2, h2)


def _rh_core_bwd(interpret, res, g):
    r2, h2 = res
    dr, dh = _rows_call(_rh_bwd_kernel, [r2, h2, g],
                        [r2.dtype, h2.dtype], interpret)
    return dr, dh


_rh_core.defvjp(_rh_core_fwd, _rh_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _blend_core(z2, q2, h2, interpret):
    return _rows_call(_blend_fwd_kernel, [z2, q2, h2], [h2.dtype],
                      interpret)


def _blend_core_fwd(z2, q2, h2, interpret):
    return _blend_core(z2, q2, h2, interpret), (z2, q2, h2)


def _blend_core_bwd(interpret, res, g):
    z2, q2, h2 = res
    dz, dq, dh = _rows_call(_blend_bwd_kernel, [z2, q2, h2, g],
                            [z2.dtype, q2.dtype, h2.dtype], interpret)
    return dz, dq, dh


_blend_core.defvjp(_blend_core_fwd, _blend_core_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def gru_gate_rh(r_raw, h, interpret=None):
    """Fused ``sigmoid(r_raw) * h`` (the reset-gated hidden state).

    ``r_raw`` is the r half of the convzr output BEFORE the sigmoid.
    Output dtype follows ``h``.  ``interpret=None`` auto-selects
    (native on TPU, interpreter on CPU).
    """
    if interpret is None:
        interpret = auto_interpret()

    def gate(r_raw, h):
        (r2, h2), shape, n = _to_rows([r_raw, h])
        out = _rh_core(r2, h2, interpret)
        return out.reshape(-1)[:n].reshape(shape)

    return per_data_shard(gate, (BATCH, BATCH))(r_raw, h)


def gru_gate_blend(z_raw, q_raw, h, interpret=None):
    """Fused GRU hidden-state blend.

    Computes ``(1-sigmoid(z_raw))*h + sigmoid(z_raw)*tanh(q_raw)`` —
    the sigmoid/tanh/lerp tail of the ConvGRU update — in one VMEM
    pass.  ``z_raw``/``q_raw`` are the raw conv outputs (pre-sigmoid /
    pre-tanh).  Output dtype follows ``h``.
    """
    if interpret is None:
        interpret = auto_interpret()

    def blend(z_raw, q_raw, h):
        (z2, q2, h2), shape, n = _to_rows([z_raw, q_raw, h])
        out = _blend_core(z2, q2, h2, interpret)
        return out.reshape(-1)[:n].reshape(shape)

    return per_data_shard(blend, (BATCH, BATCH, BATCH))(z_raw, q_raw, h)
