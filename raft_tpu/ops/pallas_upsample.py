"""Fused convex-upsample + sequence-loss Pallas TPU kernel.

The training-path upsample stage (reference ``core/raft.py:72-83`` +
``train.py:47-60``, fused here the way ``UpsampleLossStep`` fuses them in
XLA) is HBM-bound, not FLOP-bound: profiled on v5e at chairs batch 16,
the XLA chain spends ~10 ms/step writing its softmax intermediates to HBM
for the backward (five ``(6, 32, 46, 62, 64)`` bf16 saves per step with
``remat_upsample=0``) plus ~10 ms/step of scan-stacked softmax/FMA
kernels — against a ~2 ms traffic floor for the tensors it actually has
to touch (the 576-channel mask in, five scalars out).

This kernel computes the whole chain per batch element in VMEM:

    softmax over the 9 taps (64-subpixel groups, flat (c, p, q) layout
    of ``convex_upsample_flat``) -> convex combination of the 9 shifted
    flow windows -> fp32 compare vs space-to-depth ground truth ->
    masked L1 + EPE partial sums.

and the backward (``jax.custom_vjp``) RECOMPUTES the softmax in VMEM
from the saved inputs — no intermediate ever reaches HBM, removing both
the remat-off save traffic and the remat-on recompute kernels.

Numerics contract (same as the XLA path, tests/test_pallas_upsample.py):
- the ground-truth COMPARE runs fp32 (bf16-vs-bf16 compares dead-zone
  the L1 gradient, see ``convex_upsample_flat``); in-kernel arithmetic
  is fp32 throughout (inputs are read once, so bf16 compute would save
  no traffic — unlike the XLA chain where every intermediate round-trips
  HBM).
- loss sums accumulate fp32.
- EPE/1px/3px/5px sums are metrics: non-differentiable (the model wraps
  them in stop_gradient on the XLA path; here the backward simply
  ignores their cotangents).
- flow (the 1/8-res model output) and mask get gradients; ground truth
  and valid mask do not (they are data).

Inputs are pre-arranged by the wrapper:
- ``fb``  (gB, H+2, W+2, 128): flow * 8, ZERO-padded by 1 (matching
  ``convex_upsample_flat`` and the reference's F.unfold), each of x/y
  broadcast to 64 lanes (lane halves) — so every one of the 9 tap
  windows is a static 2-D slice with the subpixel lanes already in
  place (in-kernel lane broadcasts of a width-in-lanes tensor would be
  a relayout; Mosaic lesson from the correlation kernels: keep every
  operand's lanes where the math needs them).
- ``mask`` (gB, H, W, 576): raw mask-head logits, ``k*64 + p*8 + q``
  channel order.
- ``gt128`` (B, H, W, 128), ``vm64`` (B, H, W, 64): space-to-depth
  ground truth / valid mask, broadcast over the g folded iterations via
  the index map (grid step i reads block i % B).

Output: ``sums (gB, 8, 128)`` fp32 (TPU output blocks must tile to
(8, 128)); row 0 lanes 0..4 = [l1, epe, 1px, 3px, 5px] partial sums over
that batch element, everything else zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from raft_tpu.ops.pallas_util import (BATCH, auto_interpret,
                                      per_data_shard, tpu_pallas_call)
from raft_tpu.parallel.mesh import DATA_AXIS


def _softmax_parts(m):
    """Per-tap-group softmax pieces from (H, W, 576) logits: list of 9
    fp32 (H, W, 64) exps and the (H, W, 64) denominator.  Group-wise max
    subtraction (matches ``convex_upsample_flat``: a global per-pixel
    max would underflow far-below-max groups to denom 0)."""
    taps = [m[:, :, k * 64:(k + 1) * 64].astype(jnp.float32)
            for k in range(9)]
    gmax = taps[0]
    for t in taps[1:]:
        gmax = jnp.maximum(gmax, t)
    es = [jnp.exp(t - gmax) for t in taps]
    denom = es[0]
    for e in es[1:]:
        denom = denom + e
    return es, denom


def _convex_out(fb_ref, es, inv, H, W):
    """fp32 (H, W, 64) outx, outy: softmax-weighted 9-tap combination of
    the pre-broadcast flow windows."""
    accx = jnp.zeros((H, W, 64), jnp.float32)
    accy = jnp.zeros((H, W, 64), jnp.float32)
    for k in range(9):
        di, dj = k // 3, k % 3
        fwin = fb_ref[0, di:di + H, dj:dj + W, :].astype(jnp.float32)
        accx = accx + es[k] * fwin[:, :, :64]
        accy = accy + es[k] * fwin[:, :, 64:]
    return accx * inv, accy * inv


def _total(x):
    """Full fp32 sum of (H, W, 64) -> (1, 1) via leading-dim reduce +
    two ones-dots (no 1-D intermediates: Mosaic implicit-dim lesson)."""
    W = x.shape[1]
    wc = jnp.sum(x, axis=0)                                  # (W, 64)
    row = jax.lax.dot_general(jnp.ones((1, W), jnp.float32), wc,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return jax.lax.dot_general(row, jnp.ones((64, 1), jnp.float32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _upsample_loss_fwd_kernel(fb_ref, mask_ref, gt_ref, vm_ref, out_ref,
                              *, H, W):
    es, denom = _softmax_parts(mask_ref[0])
    inv = 1.0 / denom
    outx, outy = _convex_out(fb_ref, es, inv, H, W)
    gt = gt_ref[0].astype(jnp.float32)
    vm = vm_ref[0].astype(jnp.float32)
    dx = outx - gt[:, :, :64]
    dy = outy - gt[:, :, 64:]
    adx, ady = jnp.abs(dx), jnp.abs(dy)
    epe = jnp.sqrt(dx * dx + dy * dy)
    out_ref[...] = jnp.zeros_like(out_ref)
    out_ref[0, 0:1, 0:1] = _total(vm * (adx + ady))
    out_ref[0, 0:1, 1:2] = _total(vm * epe)
    out_ref[0, 0:1, 2:3] = _total(vm * (epe < 1.0).astype(jnp.float32))
    out_ref[0, 0:1, 3:4] = _total(vm * (epe < 3.0).astype(jnp.float32))
    out_ref[0, 0:1, 4:5] = _total(vm * (epe < 5.0).astype(jnp.float32))


def _upsample_loss_bwd_kernel(fb_ref, mask_ref, gt_ref, vm_ref, g_ref,
                              dmask_ref, dfb_ref, scratch_ref, *, H, W):
    """Recompute the softmax chain, then:
    dmask_k = w_k * (ghat_k - ghat_out),  ghat = gx*fx + gy*fy
    dfb accumulates w_k * (gx|gy) into the 9 shifted windows (overlap
    handled in a VMEM scratch, written once)."""
    es, denom = _softmax_parts(mask_ref[0])
    inv = 1.0 / denom
    outx, outy = _convex_out(fb_ref, es, inv, H, W)
    gt = gt_ref[0].astype(jnp.float32)
    vm = vm_ref[0].astype(jnp.float32)
    # Scalar load: a (1, 1) vector would broadcast in both sublanes AND
    # lanes when applied to (H, W, 64) operands, which Mosaic rejects
    # ("Broadcast in both sublanes and lanes"); a rank-0 scalar rides
    # the scalar registers instead.
    dl1 = g_ref[0, 0, 0]
    # d l1 / d out = vm * dabs(out - gt); metrics lanes are
    # non-differentiable by contract (ignored).  dabs uses jnp.abs's VJP
    # convention (+1 at exactly zero) rather than jnp.sign (0 at zero) so
    # the kernel's subgradient matches the XLA loss path bit-for-bit even
    # on exactly-zero residuals (reachable with integer synthetic flows).
    dabs_x = jnp.where(outx >= gt[:, :, :64], 1.0, -1.0)
    dabs_y = jnp.where(outy >= gt[:, :, 64:], 1.0, -1.0)
    gx = vm * dabs_x * dl1
    gy = vm * dabs_y * dl1
    gout = gx * outx + gy * outy
    scratch_ref[...] = jnp.zeros((H + 2, W + 2, 128), jnp.float32)
    for k in range(9):
        di, dj = k // 3, k % 3
        fwin = fb_ref[0, di:di + H, dj:dj + W, :].astype(jnp.float32)
        w_k = es[k] * inv
        ghat = gx * fwin[:, :, :64] + gy * fwin[:, :, 64:]
        dmask_ref[0, :, :, k * 64:(k + 1) * 64] = \
            (w_k * (ghat - gout)).astype(dmask_ref.dtype)
        scratch_ref[di:di + H, dj:dj + W, 0:64] = \
            scratch_ref[di:di + H, dj:dj + W, 0:64] + w_k * gx
        scratch_ref[di:di + H, dj:dj + W, 64:128] = \
            scratch_ref[di:di + H, dj:dj + W, 64:128] + w_k * gy
    dfb_ref[0] = scratch_ref[...].astype(dfb_ref.dtype)


def _specs(gB, B, H, W):
    lane = pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    per_i = lambda shape: pl.BlockSpec(  # noqa: E731
        (1,) + shape, lambda i: (i, 0, 0, 0), memory_space=pltpu.VMEM)
    per_b = lambda shape: pl.BlockSpec(  # noqa: E731
        (1,) + shape, lambda i: (i % B, 0, 0, 0),
        memory_space=pltpu.VMEM)
    return {
        "fb": per_i((H + 2, W + 2, 128)),
        "mask": per_i((H, W, 576)),
        "gt": per_b((H, W, 128)),
        "vm": per_b((H, W, 64)),
        "sums": lane,
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _upsample_loss_core(fb, mask, gt128, vm64, interpret):
    out, _ = _core_fwd(fb, mask, gt128, vm64, interpret)
    return out


def _core_fwd(fb, mask, gt128, vm64, interpret):
    gB, Hp2, Wp2, _ = fb.shape
    H, W = Hp2 - 2, Wp2 - 2
    B = gt128.shape[0]
    s = _specs(gB, B, H, W)
    out = tpu_pallas_call(
        functools.partial(_upsample_loss_fwd_kernel, H=H, W=W),
        grid=(gB,),
        in_specs=[s["fb"], s["mask"], s["gt"], s["vm"]],
        out_specs=s["sums"],
        out_shape=jax.ShapeDtypeStruct((gB, 8, 128), jnp.float32),
        interpret=interpret,
    )(fb, mask, gt128, vm64)
    return out, (fb, mask, gt128, vm64)


def _core_bwd(interpret, residuals, g):
    fb, mask, gt128, vm64 = residuals
    gB, Hp2, Wp2, _ = fb.shape
    H, W = Hp2 - 2, Wp2 - 2
    B = gt128.shape[0]
    s = _specs(gB, B, H, W)
    dmask, dfb = tpu_pallas_call(
        functools.partial(_upsample_loss_bwd_kernel, H=H, W=W),
        grid=(gB,),
        in_specs=[s["fb"], s["mask"], s["gt"], s["vm"], s["sums"]],
        out_specs=[s["mask"], s["fb"]],
        out_shape=[
            jax.ShapeDtypeStruct(mask.shape, mask.dtype),
            jax.ShapeDtypeStruct(fb.shape, fb.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((H + 2, W + 2, 128), jnp.float32)],
        interpret=interpret,
    )(fb, mask, gt128, vm64, g.astype(jnp.float32))
    return dfb, dmask, jnp.zeros_like(gt128), jnp.zeros_like(vm64)


_upsample_loss_core.defvjp(_core_fwd, _core_bwd)


def _auto_interpret() -> bool:
    return auto_interpret()


def pallas_upsample_loss_sums(flow: jax.Array, mask: jax.Array,
                              gt128: jax.Array, vm64: jax.Array,
                              interpret=None) -> jax.Array:
    """Fused flat convex upsample + masked L1/EPE partial sums.

    Args:
      flow:  (gB, H, W, 2) 1/8-res flow (model output; differentiable).
      mask:  (gB, H, W, 576) mask-head logits (differentiable).
      gt128: (B, H, W, 128) space-to-depth fp32 ground truth; gB must be
        a multiple of B (iterations folded batch-major, i % B -> b).
      vm64:  (B, H, W, 64) space-to-depth valid mask.

    Returns:
      (gB, 5) fp32 [l1, epe, 1px, 3px, 5px] sums per batch element
      (sum over B outside for per-iteration values).  EPE/precision
      lanes are metrics: non-differentiable.
    """
    if interpret is None:
        interpret = _auto_interpret()
    gB = flow.shape[0]
    B = gt128.shape[0]
    assert gB % B == 0, (gB, gt128.shape)

    def sums_of(flow, mask, gt128, vm64):
        # (g, b, ...) operands: under a data-parallel mesh ``b`` is this
        # shard's slice of the batch, and folding g back in batch-major
        # keeps the kernel's ``i % b`` pairing with gt/valid intact.
        g, b = flow.shape[:2]
        flow = flow.reshape((g * b,) + flow.shape[2:])
        mask = mask.reshape((g * b,) + mask.shape[2:])
        f8 = jnp.pad(8.0 * flow.astype(jnp.float32),
                     ((0, 0), (1, 1), (1, 1), (0, 0)))
        fb = jnp.concatenate([
            jnp.broadcast_to(f8[..., 0:1], f8.shape[:3] + (64,)),
            jnp.broadcast_to(f8[..., 1:2], f8.shape[:3] + (64,)),
        ], axis=-1)
        sums = _upsample_loss_core(fb, mask, gt128.astype(jnp.float32),
                                   vm64.astype(jnp.float32), interpret)
        return sums[:, 0, :5].reshape(g, b, 5)

    by_iter = P(None, DATA_AXIS)
    sums = per_data_shard(
        sums_of, (by_iter, by_iter, BATCH, BATCH), by_iter)(
        flow.reshape((gB // B, B) + flow.shape[1:]),
        mask.reshape((gB // B, B) + mask.shape[1:]), gt128, vm64)
    return sums.reshape(gB, 5)
