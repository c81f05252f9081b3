"""Shared building blocks: conv + norm factories, residual blocks (NHWC).

TPU-first re-design of the reference's ``core/extractor.py:6-115`` blocks:
NHWC layout (native for TPU convolutions), flax.linen functional modules,
explicit train/freeze flags instead of module-level ``.eval()`` mutation.

Norm semantics parity:
- 'group'    -> GroupNorm(planes // 8 groups)    (extractor.py:16-20)
- 'batch'    -> BatchNorm (running stats; ``freeze_bn`` pins them, the
                functional equivalent of RAFT.freeze_bn, raft.py:58-61)
- 'instance' -> per-sample, per-channel spatial norm, no affine
                (torch InstanceNorm2d defaults: affine=False)
- 'none'     -> identity
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

# torch kaiming_normal_(mode='fan_out', nonlinearity='relu') equivalent
# (reference extractor.py:150-153).
kaiming_out = nn.initializers.variance_scaling(2.0, "fan_out", "normal")


def _torch_default_uniform(key, shape, dtype=jnp.float32):
    """torch Conv2d default init: kaiming_uniform_(a=sqrt(5)) for weights and
    U(-1/sqrt(fan_in), 1/sqrt(fan_in)) for biases — both reduce to the same
    bound.  Weight shape is HWIO (fan_in = prod(shape[:-1])); bias shape is
    (features,) and the bound must then come from the matching conv's fan_in,
    so biases use :func:`torch_bias_init`."""
    import numpy as np

    fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 else shape[0]
    bound = 1.0 / (fan_in ** 0.5)
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def torch_bias_init(fan_in: int):
    bound = 1.0 / (fan_in ** 0.5)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def conv(features: int, kernel, stride=1, dtype=jnp.float32,
         name: Optional[str] = None, torch_default_init: bool = False,
         in_features: Optional[int] = None) -> nn.Conv:
    """3x3/7x7/... conv with torch-style symmetric padding.

    XLA's ``SAME`` pads stride-2 convs asymmetrically (left-light), while
    torch pads symmetrically by ``k // 2`` — using SAME would shift every
    stride-2 feature map by one input pixel and break weight-conversion
    parity, so padding is always explicit here.

    ``torch_default_init=True`` reproduces torch's default Conv2d init
    (used by the reference everywhere *except* the encoders, which apply
    kaiming fan_out on weights only, extractor.py:150-153).  Bias init
    needs the conv's fan_in, which flax initializers can't see from the
    bias shape alone: pass ``in_features`` to enable torch-parity bias
    init (otherwise biases are zeros).
    """
    if isinstance(kernel, int):
        kernel = (kernel, kernel)
    if isinstance(stride, int):
        stride = (stride, stride)
    padding = [((k - 1) // 2, (k - 1) // 2) for k in kernel]
    kernel_init = _torch_default_uniform if torch_default_init else kaiming_out
    if in_features is not None:
        bias_init = torch_bias_init(in_features * kernel[0] * kernel[1])
    else:
        bias_init = nn.initializers.zeros_init()
    return nn.Conv(features, kernel, strides=stride, padding=padding,
                   kernel_init=kernel_init, bias_init=bias_init,
                   dtype=dtype, name=name)


class Norm(nn.Module):
    """Dispatch over the reference's four norm modes."""

    kind: str
    channels: int
    num_groups: Optional[int] = None  # default: channels // 8
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, freeze_bn: bool = False):
        if self.kind == "group":
            groups = self.num_groups or self.channels // 8
            return nn.GroupNorm(num_groups=groups, epsilon=1e-5,
                                dtype=self.dtype)(x)
        if self.kind == "batch":
            return nn.BatchNorm(
                use_running_average=(not train) or freeze_bn,
                momentum=0.9, epsilon=1e-5, dtype=self.dtype)(x)
        if self.kind == "instance":
            return nn.GroupNorm(num_groups=None, group_size=1,
                                use_bias=False, use_scale=False,
                                epsilon=1e-5, dtype=self.dtype)(x)
        if self.kind == "none":
            return x
        raise ValueError(f"unknown norm kind: {self.kind}")


class ResidualBlock(nn.Module):
    """Two 3x3 convs + skip (reference extractor.py:6-57)."""

    planes: int
    norm: str
    stride: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, freeze_bn: bool = False):
        cin = x.shape[-1]
        y = conv(self.planes, 3, self.stride, self.dtype, name="conv1",
                 in_features=cin)(x)
        y = Norm(self.norm, self.planes, dtype=self.dtype, name="norm1")(
            y, train, freeze_bn)
        y = nn.relu(y)
        y = conv(self.planes, 3, 1, self.dtype, name="conv2",
                 in_features=self.planes)(y)
        y = Norm(self.norm, self.planes, dtype=self.dtype, name="norm2")(
            y, train, freeze_bn)
        y = nn.relu(y)

        if self.stride != 1 or cin != self.planes:
            x = conv(self.planes, 1, self.stride, self.dtype,
                     name="downsample_conv", in_features=cin)(x)
            x = Norm(self.norm, self.planes, dtype=self.dtype,
                     name="norm3")(x, train, freeze_bn)
        return nn.relu(x + y)


def fold_w(x: jax.Array) -> jax.Array:
    """``(B, H, W, C) -> (B, H, W/2, 2C)``: adjacent column pairs packed
    into channels (block 0 = even columns, block 1 = odd).

    The 64-channel layer1 stage only fills half of a TPU (8, 128) lane
    tile, so every tensor moves 2x its bytes through HBM; folding makes
    the tiles dense (profiled: layer1 was ~45 ms/step, ~2/3 HBM-bound at
    half effective bandwidth)."""
    B, H, W, C = x.shape
    return x.reshape(B, H, W // 2, 2 * C)


def unfold_w(x: jax.Array) -> jax.Array:
    """Inverse of :func:`fold_w`."""
    B, H, Wf, C2 = x.shape
    return x.reshape(B, H, Wf * 2, C2 // 2)


def _fold_kernel_3x3(w: jax.Array) -> jax.Array:
    """``(3, 3, C, C)`` -> ``(3, 3, 2C, 2C)`` folded-width kernel.

    A stride-1 3x3 conv on the unfolded image equals a 3x3 conv on the
    folded image with this block-structured kernel: output parity j at
    folded column p is original column ``2p + j``, whose three width taps
    land in folded columns ``p-1..p+1`` at fixed (tap, parity) slots —
    half the folded kernel is structurally zero (2x the nominal FLOPs on
    half the pixels = same math), but every operand tile is lane-dense.
    Built per call from the UNCHANGED (3,3,C,C) parameter, so checkpoints
    and the torch converter never see the folded form; autodiff routes
    the weight gradient back through the slice adjoints."""
    C = w.shape[2]
    kf = jnp.zeros((3, 3, 2 * C, 2 * C), w.dtype)
    # output parity j=0 (orig col 2p): taps at orig cols 2p-1, 2p, 2p+1
    kf = kf.at[:, 0, C:, :C].set(w[:, 0])      # col 2p-1 = (p-1, blk1)
    kf = kf.at[:, 1, :C, :C].set(w[:, 1])      # col 2p   = (p,   blk0)
    kf = kf.at[:, 1, C:, :C].set(w[:, 2])      # col 2p+1 = (p,   blk1)
    # output parity j=1 (orig col 2p+1): taps at orig cols 2p, 2p+1, 2p+2
    kf = kf.at[:, 1, :C, C:].set(w[:, 0])      # col 2p   = (p,   blk0)
    kf = kf.at[:, 1, C:, C:].set(w[:, 1])      # col 2p+1 = (p,   blk1)
    kf = kf.at[:, 2, :C, C:].set(w[:, 2])      # col 2p+2 = (p+1, blk0)
    return kf


class _FoldedConv3x3(nn.Module):
    """3x3/stride-1 conv applied in folded-width layout.  Parameter names
    and shapes ("kernel" (3,3,C,C), "bias" (C,)) are identical to the
    ``conv()`` path, so the param tree is checkpoint-compatible.

    Parity with ``conv()`` is exact only at fp32+: the kernel fold runs
    in fp32 before the cast to self.dtype, while nn.Conv casts params
    first — a bf16-ULP-level difference under bf16 compute (bounded by
    tests/test_layers.py::test_encoder_folded_matches_unfolded_bf16)."""

    channels: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, xf):
        C = self.channels
        kernel = self.param("kernel", kaiming_out, (3, 3, C, C),
                            jnp.float32)
        bias = self.param("bias", torch_bias_init(C * 9), (C,),
                          jnp.float32)
        kf = _fold_kernel_3x3(kernel).astype(self.dtype)
        y = jax.lax.conv_general_dilated(
            xf.astype(self.dtype), kf, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + jnp.tile(bias, 2).astype(self.dtype)


def _pair_stats(x: jax.Array, axes, C: int):
    """Per-ORIGINAL-channel mean/var on the folded layout: lane c and
    lane c+C hold the same original channel (even/odd columns), so the
    per-lane moments combine exactly as the average of the two
    equal-count halves.  Stats run at fp32 minimum (matching flax's
    normalization internals) but never truncate wider inputs."""
    x = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    m = jnp.mean(x, axis=axes)
    m2 = jnp.mean(x * x, axis=axes)
    mean_c = 0.5 * (m[..., :C] + m[..., C:])
    e2_c = 0.5 * (m2[..., :C] + m2[..., C:])
    return mean_c, e2_c - mean_c * mean_c


class _FoldedBatchNorm(nn.Module):
    """flax BatchNorm semantics (momentum 0.9, eps 1e-5, biased var,
    fp32 stats) on the folded layout; param/variable names match
    ``nn.BatchNorm`` for checkpoint compatibility.

    Parity with the unfolded path is exact only at fp32+: this module
    normalizes in fp32 and rounds once at the end, while nn.BatchNorm
    performs the (x-mean)*inv*scale+bias arithmetic in self.dtype — so
    under bf16 compute the two differ at bf16-ULP level (bounded by
    tests/test_layers.py::test_encoder_folded_matches_unfolded_bf16)."""

    channels: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, xf, use_running_average: bool):
        C = self.channels
        scale = self.param("scale", nn.initializers.ones_init(), (C,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (C,),
                          jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((C,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((C,), jnp.float32))
        if use_running_average:
            mean_c, var_c = ra_mean.value, ra_var.value
        else:
            mean_c, var_c = _pair_stats(xf, (0, 1, 2), C)
            if not self.is_initializing() and \
                    self.is_mutable_collection("batch_stats"):
                ra_mean.value = 0.9 * ra_mean.value + 0.1 * mean_c
                ra_var.value = 0.9 * ra_var.value + 0.1 * var_c
        wdt = jnp.promote_types(xf.dtype, jnp.float32)
        inv = jax.lax.rsqrt(var_c + 1e-5) * scale
        y = (xf.astype(wdt) - jnp.tile(mean_c, 2)) \
            * jnp.tile(inv, 2) + jnp.tile(bias, 2)
        return y.astype(self.dtype)


class FoldedNorm(nn.Module):
    """Folded-layout dispatch over the norm modes a folded block
    supports (instance / batch / none; 'group' falls back to the
    unfolded path at the encoder level)."""

    kind: str
    channels: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, xf, train: bool = False, freeze_bn: bool = False):
        if self.kind == "batch":
            return _FoldedBatchNorm(self.channels, self.dtype,
                                    name="BatchNorm_0")(
                xf, (not train) or freeze_bn)
        if self.kind == "instance":
            C = self.channels
            mean_c, var_c = _pair_stats(xf, (1, 2), C)
            wdt = jnp.promote_types(xf.dtype, jnp.float32)
            inv = jax.lax.rsqrt(var_c + 1e-5)
            y = (xf.astype(wdt) - jnp.tile(mean_c, 2)[:, None,
                                                      None, :]) \
                * jnp.tile(inv, 2)[:, None, None, :]
            return y.astype(self.dtype)
        if self.kind == "none":
            return xf
        raise ValueError(f"unfoldable norm kind: {self.kind}")


class FoldedStemConv(nn.Module):
    """Original 7x7/stride-2 stem conv emitting the FOLDED layout
    directly: folded output column p holds original columns 2p (parity
    0, input center 4p, window 4p-3..4p+3) and 2p+1 (parity 1, center
    4p+2, window 4p-1..4p+5) — one (7, 9) kernel at stride (2, 4) whose
    width taps embed the original (7, 7) kernel at offsets 0 and 2.
    Param names/shapes match the unfolded stem ("kernel" (7,7,cin,P),
    "bias" (P,)), and the fold relayout after the stem disappears."""

    cin: int
    planes: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        C, P = self.cin, self.planes
        kernel = self.param("kernel", kaiming_out, (7, 7, C, P),
                            jnp.float32)
        bias = self.param("bias", torch_bias_init(C * 49), (P,),
                          jnp.float32)
        kf = jnp.zeros((7, 9, C, 2 * P), kernel.dtype)
        kf = kf.at[:, 0:7, :, :P].set(kernel)
        kf = kf.at[:, 2:9, :, P:].set(kernel)
        y = jax.lax.conv_general_dilated(
            x.astype(self.dtype), kf.astype(self.dtype), (2, 4),
            [(3, 3), (3, 2)], dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + jnp.tile(bias, 2).astype(self.dtype)


class _FoldedEntryConv(nn.Module):
    """Original 3x3/stride-2 conv consuming the FOLDED layout: output
    column q is original column 2q, whose three width taps live in
    folded columns q-1 (parity 1) and q (parities 0 and 1) — a (3, 2)
    kernel at stride (2, 1) over ``(H, Wf)``.  Param names/shapes match
    ``conv()`` ("kernel" (3,3,C,P), "bias" (P,))."""

    cin: int
    planes: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, xf):
        C, P = self.cin, self.planes
        kernel = self.param("kernel", kaiming_out, (3, 3, C, P),
                            jnp.float32)
        bias = self.param("bias", torch_bias_init(C * 9), (P,),
                          jnp.float32)
        kf = jnp.zeros((3, 2, 2 * C, P), kernel.dtype)
        kf = kf.at[:, 0, C:, :].set(kernel[:, 0])   # col 2q-1 = (q-1, b1)
        kf = kf.at[:, 1, :C, :].set(kernel[:, 1])   # col 2q   = (q,   b0)
        kf = kf.at[:, 1, C:, :].set(kernel[:, 2])   # col 2q+1 = (q,   b1)
        y = jax.lax.conv_general_dilated(
            xf.astype(self.dtype), kf.astype(self.dtype), (2, 1),
            [(1, 1), (1, 0)], dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + bias.astype(self.dtype)


class FoldedEntryResidualBlock(nn.Module):
    """:class:`ResidualBlock` with ``stride=2`` whose INPUT arrives in
    folded-width layout and whose output is standard: the stride-2 width
    step lands exactly on the folded column count, so consuming the fold
    directly removes the unfold relayout and halves the entry conv's
    input reads.  Identical math and parameter tree to the unfolded
    stride-2 block (the 1x1 downsample reads original even columns =
    folded parity-0 channels, even rows via slicing)."""

    planes: int
    norm: str
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, xf, train: bool = False, freeze_bn: bool = False):
        C = xf.shape[-1] // 2
        y = _FoldedEntryConv(C, self.planes, self.dtype, name="conv1")(xf)
        y = Norm(self.norm, self.planes, dtype=self.dtype, name="norm1")(
            y, train, freeze_bn)
        y = nn.relu(y)
        y = conv(self.planes, 3, 1, self.dtype, name="conv2",
                 in_features=self.planes)(y)
        y = Norm(self.norm, self.planes, dtype=self.dtype, name="norm2")(
            y, train, freeze_bn)
        y = nn.relu(y)

        x_even = xf[:, ::2, :, :C]            # orig even rows, even cols
        x = conv(self.planes, 1, 1, self.dtype, name="downsample_conv",
                 in_features=C)(x_even)
        x = Norm(self.norm, self.planes, dtype=self.dtype, name="norm3")(
            x, train, freeze_bn)
        return nn.relu(x + y)


class FoldedResidualBlock(nn.Module):
    """:class:`ResidualBlock` (stride 1) computed entirely in folded-width
    layout — identical math and parameter tree, lane-dense tiles."""

    planes: int
    norm: str
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, xf, train: bool = False, freeze_bn: bool = False):
        y = _FoldedConv3x3(self.planes, self.dtype, name="conv1")(xf)
        y = FoldedNorm(self.norm, self.planes, self.dtype,
                        name="norm1")(y, train, freeze_bn)
        y = nn.relu(y)
        y = _FoldedConv3x3(self.planes, self.dtype, name="conv2")(y)
        y = FoldedNorm(self.norm, self.planes, self.dtype,
                        name="norm2")(y, train, freeze_bn)
        y = nn.relu(y)
        return nn.relu(xf + y)


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck (reference extractor.py:60-115).

    Note the reference's quirk: GroupNorm group count is ``planes // 8``
    even for the ``planes // 4``-channel inner convs (extractor.py:72-74);
    reproduced for weight parity.
    """

    planes: int
    norm: str
    stride: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, freeze_bn: bool = False):
        cin = x.shape[-1]
        p4 = self.planes // 4
        groups = self.planes // 8
        y = conv(p4, 1, 1, self.dtype, name="conv1", in_features=cin)(x)
        y = Norm(self.norm, p4, num_groups=groups, dtype=self.dtype,
                 name="norm1")(y, train, freeze_bn)
        y = nn.relu(y)
        y = conv(p4, 3, self.stride, self.dtype, name="conv2",
                 in_features=p4)(y)
        y = Norm(self.norm, p4, num_groups=groups, dtype=self.dtype,
                 name="norm2")(y, train, freeze_bn)
        y = nn.relu(y)
        y = conv(self.planes, 1, 1, self.dtype, name="conv3",
                 in_features=p4)(y)
        y = Norm(self.norm, self.planes, num_groups=groups, dtype=self.dtype,
                 name="norm3")(y, train, freeze_bn)
        y = nn.relu(y)

        if self.stride != 1 or cin != self.planes:
            x = conv(self.planes, 1, self.stride, self.dtype,
                     name="downsample_conv", in_features=cin)(x)
            x = Norm(self.norm, self.planes, num_groups=groups,
                     dtype=self.dtype, name="norm4")(x, train, freeze_bn)
        return nn.relu(x + y)
