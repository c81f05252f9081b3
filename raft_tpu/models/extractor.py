"""Feature / context encoders (NHWC, 1/8 resolution).

Re-designs the reference's ``core/extractor.py:118-267``:

- ``BasicEncoder``: 7x7/s2 stem -> three residual stages (64, 96/s2, 128/s2)
  -> 1x1 projection (extractor.py:135-148).
- ``SmallEncoder``: bottleneck blocks, 32 -> 64/s2 -> 96/s2
  (extractor.py:212-227).

- ``ResNetEncoder`` (arch 'searaft'; SEA-RAFT core/extractor.py
  ``ResNetFPN``): the first three stages of ResNet-34, 3 + 4 + 6 basic
  blocks of 64 / 128 / 256 channels, batch norm throughout.

The first two take frames stacked on the batch axis for the shared-weight two-frame
encode (the reference's list-input trick, extractor.py:168-174, becomes an
explicit ``jnp.concatenate`` at the caller).  Dropout is channel-wise
(torch Dropout2d, extractor.py:186-187) -> flax Dropout broadcast over the
spatial axes.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_tpu.models.layers import (BottleneckBlock,
                                    FoldedEntryResidualBlock,
                                    FoldedResidualBlock, Norm,
                                    ResidualBlock, FoldedNorm,
                                    FoldedStemConv, conv)


class BasicEncoder(nn.Module):
    output_dim: int = 128
    norm: str = "batch"
    dropout: float = 0.0
    dtype: Any = jnp.float32
    # Run the 64-channel layer1 stage in folded-width layout (column
    # pairs packed into channels -> lane-dense (8, 128) tiles; same math,
    # same param tree — see layers.fold_w).  Auto-disabled when the
    # /2-res width is odd or the norm mode can't fold.
    fold_layer1: bool = True

    @nn.compact
    def __call__(self, x, train: bool = False, freeze_bn: bool = False):
        dt = self.dtype
        x = x.astype(dt)
        stages = [(64, 1), (64, 1), (96, 2), (96, 1), (128, 2), (128, 1)]
        # Stem output width is ceil(W/2) for even W (pad 3, k=7, s=2);
        # folding needs it even, i.e. W % 4 == 0 (InputPadder-padded
        # inputs always are).
        folded = (self.fold_layer1 and x.shape[2] % 4 == 0
                  and self.norm in ("instance", "batch", "none"))
        start = 0
        if folded:
            # Stem emits the folded layout directly — no relayout pass.
            x = FoldedStemConv(3, 64, dt, name="conv1")(x)
            x = FoldedNorm(self.norm, 64, dt, name="norm1")(
                x, train, freeze_bn)
            x = nn.relu(x)
            for i in range(2):
                x = FoldedResidualBlock(64, self.norm, dt,
                                        name=f"layer1_{i}")(
                    x, train, freeze_bn)
            # layer2_0 (stride 2) consumes the folded layout directly —
            # its width step lands exactly on the folded column count,
            # so no unfold relayout is needed anywhere.
            x = FoldedEntryResidualBlock(96, self.norm, dt,
                                         name="layer2_0")(
                x, train, freeze_bn)
            start = 3
        else:
            x = conv(64, 7, 2, dt, name="conv1", in_features=3)(x)
            # stem GroupNorm uses 8 groups, not 64//8 (reference
            # extractor.py:124)
            x = Norm(self.norm, 64, num_groups=8, dtype=dt,
                     name="norm1")(x, train, freeze_bn)
            x = nn.relu(x)
        for i, (planes, stride) in enumerate(stages[start:], start=start):
            x = ResidualBlock(planes, self.norm, stride, dt,
                              name=f"layer{i // 2 + 1}_{i % 2}")(
                x, train, freeze_bn)

        x = conv(self.output_dim, 1, 1, dt, name="conv2", in_features=128)(x)

        if self.dropout > 0:
            x = nn.Dropout(self.dropout, broadcast_dims=(1, 2),
                           deterministic=not train)(x)
        return x


class SmallEncoder(nn.Module):
    output_dim: int = 128
    norm: str = "batch"
    dropout: float = 0.0
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, freeze_bn: bool = False):
        dt = self.dtype
        x = x.astype(dt)
        x = conv(32, 7, 2, dt, name="conv1", in_features=3)(x)
        x = Norm(self.norm, 32, num_groups=8, dtype=dt, name="norm1")(
            x, train, freeze_bn)
        x = nn.relu(x)

        for i, (planes, stride) in enumerate(
                [(32, 1), (32, 1), (64, 2), (64, 1), (96, 2), (96, 1)]):
            x = BottleneckBlock(planes, self.norm, stride, dt,
                                name=f"layer{i // 2 + 1}_{i % 2}")(
                x, train, freeze_bn)

        x = conv(self.output_dim, 1, 1, dt, name="conv2", in_features=96)(x)

        if self.dropout > 0:
            x = nn.Dropout(self.dropout, broadcast_dims=(1, 2),
                           deterministic=not train)(x)
        return x


class ResNetBlock(nn.Module):
    """ResNet's basic block as SEA-RAFT's ``BasicBlock`` has it:
    ``relu(s(x) + bn2(conv3x3(relu(bn1(conv3x3_stride(x))))))``, ``s`` the
    identity, or ``bn(conv1x1_stride(x))`` where the stride or the width
    changes.  Unlike :class:`ResidualBlock` (RAFT's) there is no ReLU
    between the second norm and the sum.  Same leaf names."""

    planes: int
    stride: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, freeze_bn: bool = False):
        cin, dt = x.shape[-1], self.dtype

        def bn(name, y):
            return Norm("batch", self.planes, dtype=dt, name=name)(
                y, train, freeze_bn)

        y = conv(self.planes, 3, self.stride, dt, name="conv1",
                 in_features=cin)(x)
        y = nn.relu(bn("norm1", y))
        y = conv(self.planes, 3, 1, dt, name="conv2",
                 in_features=self.planes)(y)
        y = bn("norm2", y)
        if self.stride != 1 or cin != self.planes:
            x = conv(self.planes, 1, self.stride, dt,
                     name="downsample_conv", in_features=cin)(x)
            x = bn("norm3", x)
        return nn.relu(x + y)


class ResNetEncoder(nn.Module):
    """SEA-RAFT's ``ResNetFPN`` at its (M) setting (``pretrain
    resnet34``, ``initial_dim 64``, ``block_dims [64, 128, 256]``): 7x7/s2
    stem, batch norm, ReLU, then 3 / 4 / 6 :class:`ResNetBlock` at 1/2,
    1/4 and 1/8 resolution, then a 1x1 projection.  Batch norm in every
    call: a caller that wants two images normalised apart calls twice.
    Each stage traces under ``jax.named_scope("resnet_stage<n>")``."""

    output_dim: int = 256
    dtype: Any = jnp.float32
    # Rematerialize the blocks of the first stage in a training call's
    # backward pass: their 64-channel activations at 1/2 resolution are
    # the largest arrays of the step (93 MB each at the chairs crop and
    # batch 16, a dozen a call, three calls a step), and keeping only
    # each block's input is what leaves the step room on a 16 GB chip
    # (PERF.md section 4).  Follows ``RAFTConfig.remat``.
    remat_stage1: bool = False
    stages = ((64, 3, 1), (128, 4, 2), (256, 6, 2))

    @nn.compact
    def __call__(self, x, train: bool = False, freeze_bn: bool = False):
        dt = self.dtype
        x = x.astype(dt)
        block1 = ResNetBlock
        if self.remat_stage1 and train:
            block1 = nn.remat(ResNetBlock, static_argnums=(2, 3))
        with jax.named_scope("resnet_stage1"):
            x = conv(64, 7, 2, dt, name="conv1",
                     in_features=x.shape[-1])(x)
            x = nn.relu(Norm("batch", 64, dtype=dt, name="norm1")(
                x, train, freeze_bn))
        for s, (planes, blocks, stride) in enumerate(self.stages, start=1):
            with jax.named_scope(f"resnet_stage{s}"):
                for i in range(blocks):
                    x = (block1 if s == 1 else ResNetBlock)(
                        planes, stride if i == 0 else 1, dt,
                        name=f"layer{s}_{i}")(x, train, freeze_bn)
        return conv(self.output_dim, 1, 1, dt, name="conv2",
                    in_features=x.shape[-1])(x)
