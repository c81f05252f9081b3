"""Recurrent update blocks (NHWC).

Re-designs the reference's ``core/update.py``: motion encoder + ConvGRU +
flow head (+ convex-upsample mask head in the full model).  All convs are
NHWC; the GRU is the natural ``lax.scan`` body (driven from the RAFT model).

Parity notes:
- The mask head output is scaled by 0.25 ("to balence gradients",
  update.py:123-125).
- ``BasicMotionEncoder`` emits 126 channels and appends the raw 2-channel
  flow -> 128 (update.py:91-97); the small variant emits 80 + 2 -> 82
  (update.py:70-77).
- ``SepConvGRU`` runs a horizontal (1x5) then vertical (5x1) GRU pass
  (update.py:33-60).
- Init: the reference applies kaiming only to the encoders; update-block
  convs keep torch's *default* Conv2d init — reproduced via
  ``torch_default_init``.
- ``arch='gma'`` (Jiang et al., ICCV 2021; PAPERS.md has the equations):
  :class:`Attention` builds one ``(N, N)`` content attention a pair from
  the context features, once, before the loop; :class:`GMAUpdateBlock`
  is :class:`BasicUpdateBlock` with :class:`Aggregate` between the motion
  encoder and a GRU whose input is 384 wide.
- ``arch='searaft'`` (Wang et al., ECCV 2024; PAPERS.md):
  :class:`SEARAFTUpdateBlock` is the same motion encoder followed by two
  :class:`ConvNeXtBlock` in place of the GRU; it returns the hidden state
  alone, because the flow head (:class:`FlowHead` with 6 channels: flow,
  mixture logits, log-scales) is also applied before the loop and so
  lives beside the block, not in it (``models/raft.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from raft_tpu.models.layers import (_torch_default_uniform, conv,
                                    torch_bias_init)


def _tconv(features, kernel, cin, dtype, name):
    """Conv with torch's *default* init (the reference applies kaiming only
    to the encoders; update-block convs keep torch defaults)."""
    return conv(features, kernel, 1, dtype, name=name,
                torch_default_init=True, in_features=cin)


@dataclasses.dataclass
class FusedCorrLookup:
    """Deferred correlation lookup (``fused_lookup_encoder`` path).

    When ``RAFTConfig.fused_lookup_encoder`` is on and the map runs the
    Mosaic lookup (``models/raft.py corr_impl_at``), the
    refinement step hands the motion encoder THIS instead of the
    materialized ``(B, H/8, W/8, levels*(2r+1)^2)`` corr-feature tensor;
    the encoder then runs ``ops/pallas_corr.pallas_pyramid_lookup_encode``,
    which samples the pyramid AND applies convc1 (+bias+relu) in one
    Pallas kernel — the tap tensor never reaches HBM.  Plain Python
    container (not a pytree): it is built and consumed inside one scan
    body trace, never crossing a jit/scan boundary itself.
    """

    pyramid: Any        # list of per-level arrays or QuantizedLevel
    coords: Any         # (B, H/8, W/8, 2) fp32 lookup centers
    channels: int       # levels * (2r+1)^2 == convc1 fan-in
    radius: int
    block_q: int
    interpret: Any = None   # None = auto (TPU native / CPU interpreter)


class _Conv1x1Params(nn.Module):
    """Declare a 1x1 conv's parameters without applying it.

    Instantiated under the SAME scope name ("convc1") and with the same
    init/shape/dtype conventions as ``_tconv``'s ``nn.Conv``, so the
    param tree — and any torch-converted checkpoint — is interchangeable
    between the fused and unfused motion-encoder paths.
    """

    features: int
    cin: int

    @nn.compact
    def __call__(self):
        kernel = self.param("kernel", _torch_default_uniform,
                            (1, 1, self.cin, self.features))
        bias = self.param("bias", torch_bias_init(self.cin),
                          (self.features,))
        return kernel, bias


def _fused_corr_encode(fused: "FusedCorrLookup", kernel, bias, features,
                       dtype):
    """convc1(lookup(pyramid)) + relu via the fused Pallas kernel."""
    from raft_tpu.ops.pallas_corr import pallas_pyramid_lookup_encode

    cor = pallas_pyramid_lookup_encode(
        fused.pyramid, fused.coords,
        kernel.reshape(fused.channels, features), bias,
        fused.radius, fused.block_q, fused.interpret, jnp.dtype(dtype))
    # Same remat tag the unfused path puts on the sampled taps
    # (remat_policy='save_corr'): saving the fused conv output skips
    # both the re-lookup and the conv in the backward recompute.
    return checkpoint_name(cor, "corr")


class FlowHead(nn.Module):
    hidden_dim: int = 256
    dtype: Any = jnp.float32
    # 2: a flow update; 6 (arch 'searaft'): a flow update, two mixture
    # logits and two log-scales
    out_channels: int = 2

    @nn.compact
    def __call__(self, x):
        cin = x.shape[-1]
        x = nn.relu(_tconv(self.hidden_dim, 3, cin, self.dtype, "conv1")(x))
        return _tconv(self.out_channels, 3, self.hidden_dim, self.dtype,
                      "conv2")(x)


class ConvGRU(nn.Module):
    """The z and r gates read the same input, so their two convs are fused
    into one double-width conv + split (identical math; the torch->flax
    converter concatenates the reference's convz/convr kernels on the
    output axis).  Wider output channels keep the MXU busier than two
    narrow convs."""

    hidden_dim: int = 128
    dtype: Any = jnp.float32
    fused: bool = False

    @nn.compact
    def __call__(self, h, x):
        hx = jnp.concatenate([h, x], axis=-1)
        cin = hx.shape[-1]
        zr_raw = _tconv(2 * self.hidden_dim, 3, cin, self.dtype,
                        "convzr")(hx)
        if self.fused:
            from raft_tpu.ops.pallas_gru import (gru_gate_blend,
                                                 gru_gate_rh)

            z_raw, r_raw = jnp.split(zr_raw, 2, axis=-1)
            q_raw = _tconv(self.hidden_dim, 3, cin, self.dtype, "convq")(
                jnp.concatenate([gru_gate_rh(r_raw, h), x], axis=-1))
            return gru_gate_blend(z_raw, q_raw, h)
        zr = nn.sigmoid(zr_raw)
        z, r = jnp.split(zr, 2, axis=-1)
        q = jnp.tanh(_tconv(self.hidden_dim, 3, cin, self.dtype, "convq")(
            jnp.concatenate([r * h, x], axis=-1)))
        return (1 - z) * h + z * q


class SepConvGRU(nn.Module):
    """Horizontal (1x5) then vertical (5x1) GRU pass, with the z/r gate
    convs of each pass fused double-width (see ConvGRU)."""

    hidden_dim: int = 128
    dtype: Any = jnp.float32
    fused: bool = False

    def _pass(self, h, x, cin, ksize, zr_name, q_name):
        dt = self.dtype
        hx = jnp.concatenate([h, x], axis=-1)
        zr_raw = _tconv(2 * self.hidden_dim, ksize, cin, dt, zr_name)(hx)
        if self.fused:
            from raft_tpu.ops.pallas_gru import (gru_gate_blend,
                                                 gru_gate_rh)

            z_raw, r_raw = jnp.split(zr_raw, 2, axis=-1)
            q_raw = _tconv(self.hidden_dim, ksize, cin, dt, q_name)(
                jnp.concatenate([gru_gate_rh(r_raw, h), x], axis=-1))
            return gru_gate_blend(z_raw, q_raw, h)
        zr = nn.sigmoid(zr_raw)
        z, r = jnp.split(zr, 2, axis=-1)
        q = jnp.tanh(_tconv(self.hidden_dim, ksize, cin, dt, q_name)(
            jnp.concatenate([r * h, x], axis=-1)))
        return (1 - z) * h + z * q

    @nn.compact
    def __call__(self, h, x):
        cin = h.shape[-1] + x.shape[-1]
        # horizontal (1x5) then vertical (5x1) pass
        h = self._pass(h, x, cin, (1, 5), "convzr1", "convq1")
        return self._pass(h, x, cin, (5, 1), "convzr2", "convq2")


class SmallMotionEncoder(nn.Module):
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, flow, corr):
        dt = self.dtype
        if isinstance(corr, FusedCorrLookup):
            kernel, bias = _Conv1x1Params(96, corr.channels,
                                          name="convc1")()
            cor = _fused_corr_encode(corr, kernel, bias, 96, dt)
        else:
            cor = nn.relu(
                _tconv(96, 1, corr.shape[-1], dt, "convc1")(corr))
        flo = nn.relu(_tconv(64, 7, 2, dt, "convf1")(flow))
        flo = nn.relu(_tconv(32, 3, 64, dt, "convf2")(flo))
        out = nn.relu(_tconv(80, 3, 128, dt, "conv")(
            jnp.concatenate([cor, flo], axis=-1)))
        # Tagged for remat_policy='save_corr' (saved with the corr taps:
        # skipping the motion-encoder recompute in backward is nearly
        # free memory-wise, (B, H/8, W/8, 82|128) per iteration).
        return checkpoint_name(
            jnp.concatenate([out, flow], axis=-1), "motion")


class BasicMotionEncoder(nn.Module):
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, flow, corr):
        dt = self.dtype
        if isinstance(corr, FusedCorrLookup):
            kernel, bias = _Conv1x1Params(256, corr.channels,
                                          name="convc1")()
            cor = _fused_corr_encode(corr, kernel, bias, 256, dt)
        else:
            cor = nn.relu(
                _tconv(256, 1, corr.shape[-1], dt, "convc1")(corr))
        cor = nn.relu(_tconv(192, 3, 256, dt, "convc2")(cor))
        flo = nn.relu(_tconv(128, 7, 2, dt, "convf1")(flow))
        flo = nn.relu(_tconv(64, 3, 128, dt, "convf2")(flo))
        out = nn.relu(_tconv(126, 3, 64 + 192, dt, "conv")(
            jnp.concatenate([cor, flo], axis=-1)))
        # Tagged for remat_policy='save_corr' (saved with the corr taps:
        # skipping the motion-encoder recompute in backward is nearly
        # free memory-wise, (B, H/8, W/8, 82|128) per iteration).
        return checkpoint_name(
            jnp.concatenate([out, flow], axis=-1), "motion")


class SmallUpdateBlock(nn.Module):
    hidden_dim: int = 96
    dtype: Any = jnp.float32
    fused_gru: bool = False

    @nn.compact
    def __call__(self, net, inp, corr, flow):
        motion = SmallMotionEncoder(self.dtype, name="encoder")(flow, corr)
        x = jnp.concatenate([inp, motion], axis=-1)
        net = ConvGRU(self.hidden_dim, self.dtype, fused=self.fused_gru,
                      name="gru")(net, x)
        delta_flow = FlowHead(128, self.dtype, name="flow_head")(net)
        return net, delta_flow


class BasicUpdateBlock(nn.Module):
    """GRU update *without* the mask head: the convex-upsample mask
    (reference update.py:122-125) depends only on ``net``, so it is
    hoisted out of the refinement scan into :class:`MaskHead` (applied per
    iteration for training, final iteration only for inference — the
    reference recomputes it every iteration even in test mode,
    raft.py:127-137)."""

    hidden_dim: int = 128
    dtype: Any = jnp.float32
    fused_gru: bool = False

    @nn.compact
    def __call__(self, net, inp, corr, flow):
        motion = BasicMotionEncoder(self.dtype, name="encoder")(flow, corr)
        x = jnp.concatenate([inp, motion], axis=-1)
        net = SepConvGRU(self.hidden_dim, self.dtype,
                         fused=self.fused_gru, name="gru")(net, x)
        delta_flow = FlowHead(256, self.dtype, name="flow_head")(net)
        return net, delta_flow


class Attention(nn.Module):
    """GMA's content attention (core/gma.py ``Attention``, one head, no
    relative-position term): ``softmax_rows(d^-1/2 q k^T)`` over the
    ``N = H/8 * W/8`` positions of the context features, ``[q, k]`` one
    bias-free 1x1 convolution.  Built once a pair and carried through
    the refinement loop as a loop invariant; products accumulate and the
    softmax runs in float32, the matrix is stored in ``dtype``."""

    dim_head: int = 128
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, inp):
        d = self.dim_head
        qk = nn.Conv(2 * d, (1, 1), use_bias=False, dtype=self.dtype,
                     kernel_init=_torch_default_uniform, name="to_qk")(inp)
        B, H, W, _ = qk.shape
        q, k = jnp.split(qk.reshape(B, H * W, 2 * d), 2, axis=-1)
        with jax.named_scope("gma_attention"):
            sim = jnp.einsum("bnd,bmd->bnm", q * (d ** -0.5), k,
                             preferred_element_type=jnp.float32)
            return nn.softmax(sim, axis=-1).astype(self.dtype)


class Aggregate(nn.Module):
    """GMA's global motion aggregation (core/gma.py ``Aggregate``, one
    head so no output projection): ``m + gamma * (A v)``, ``v`` a
    bias-free 1x1 convolution of the motion features ``m``, ``gamma`` a
    learned scalar that the paper initialises to 0."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, attn, motion):
        B, H, W, C = motion.shape
        v = nn.Conv(C, (1, 1), use_bias=False, dtype=self.dtype,
                    kernel_init=_torch_default_uniform, name="to_v")(motion)
        gamma = self.param("gamma", nn.initializers.zeros, (1,))
        with jax.named_scope("gma_aggregate"):
            out = jnp.einsum("bnm,bmc->bnc", attn, v.reshape(B, H * W, C),
                             preferred_element_type=jnp.float32)
        out = out.astype(self.dtype).reshape(B, H, W, C)
        return motion + gamma.astype(self.dtype) * out


class GMAUpdateBlock(nn.Module):
    """:class:`BasicUpdateBlock` with the aggregated motion features as a
    third part of the GRU's input (GMA core/update.py ``GMAUpdateBlock``;
    the mask head is hoisted into :class:`MaskHead` as for RAFT-full)."""

    hidden_dim: int = 128
    dtype: Any = jnp.float32
    fused_gru: bool = False

    @nn.compact
    def __call__(self, net, inp, corr, flow, attn):
        motion = BasicMotionEncoder(self.dtype, name="encoder")(flow, corr)
        glob = Aggregate(self.dtype, name="aggregator")(attn, motion)
        x = jnp.concatenate([inp, motion, glob], axis=-1)
        net = SepConvGRU(self.hidden_dim, self.dtype,
                         fused=self.fused_gru, name="gru")(net, x)
        delta_flow = FlowHead(256, self.dtype, name="flow_head")(net)
        return net, delta_flow


class ConvNeXtBlock(nn.Module):
    """SEA-RAFT's ``ConvNextBlock`` (core/layer.py):
    ``final(u + gamma * W2 gelu(W1 LN(dw7x7(u))))`` for ``u`` of ``C``
    channels -- a depthwise 7x7 convolution with bias, a LayerNorm over
    the channels (eps 1e-6, statistics in float32), ``W1: C -> 4 out``
    and ``W2: 4 out -> C`` with biases (``nn.Linear`` there, 1x1
    convolutions here: the same product, and a leaf of rank 4 like every
    other kernel), the exact (erf) GELU, a learned vector ``gamma`` that
    the paper initialises to 1e-6, and ``final`` a 1x1 convolution
    ``C -> out``."""

    out_dim: int = 128
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        dt, C = self.dtype, u.shape[-1]
        with jax.named_scope("convnext_block"):
            x = nn.Conv(C, (7, 7), padding=[(3, 3), (3, 3)],
                        feature_group_count=C, dtype=dt,
                        kernel_init=_torch_default_uniform,
                        bias_init=torch_bias_init(49), name="dwconv")(u)
            x = nn.LayerNorm(epsilon=1e-6, dtype=dt, name="norm")(x)
            x = _tconv(4 * self.out_dim, 1, C, dt, "pwconv1")(x)
            x = nn.gelu(x, approximate=False)
            x = _tconv(C, 1, 4 * self.out_dim, dt, "pwconv2")(x)
            gamma = self.param("gamma", nn.initializers.constant(1e-6),
                               (C,))
            return _tconv(self.out_dim, 1, C, dt, "final")(
                u + gamma.astype(dt) * x)


class SEARAFTUpdateBlock(nn.Module):
    """SEA-RAFT's ``BasicUpdateBlock`` (core/update.py): RAFT-full's
    motion encoder, then ``net <- ConvNeXt(cat[net, inp, motion])`` twice
    with separate weights; no gates, and no flow head (see the module
    docstring)."""

    hidden_dim: int = 128
    dtype: Any = jnp.float32
    num_blocks: int = 2

    @nn.compact
    def __call__(self, net, inp, corr, flow):
        motion = BasicMotionEncoder(self.dtype, name="encoder")(flow, corr)
        x = jnp.concatenate([inp, motion], axis=-1)
        for i in range(self.num_blocks):
            net = ConvNeXtBlock(self.hidden_dim, self.dtype,
                                name=f"refine_{i}")(
                jnp.concatenate([net, x], axis=-1))
        return net


class MaskHead(nn.Module):
    """Convex-upsample mask head (reference update.py:122-125,135), with
    the x0.25 scale ("to balence gradients")."""

    hidden_dim: int = 128
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, net):
        mask = nn.relu(_tconv(256, 3, self.hidden_dim, self.dtype,
                              "mask_conv1")(net))
        mask = _tconv(64 * 9, 1, 256, self.dtype, "mask_conv2")(mask)
        return 0.25 * mask
