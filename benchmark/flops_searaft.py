"""Operations and bytes that SEA-RAFT (M) needs, from shapes alone
(PAPERS.md has the equations).

Counted as ``flops.py`` counts: a multiply-add is two operations; norms,
activations, the softmax and the likelihood's elementwise arithmetic are
left out, which can only make a share of the peak read low.  A pair needs

- three passes of the first three ResNet-34 stages (the feature encoder
  once an image, the context encoder once over the pair), ``init_conv`` and
  the all-pairs volume;
- the heads once before the loop and once an iteration;
- every iteration: the lookup, RAFT-full's motion encoder and two ConvNeXt
  blocks over 384 channels (a depthwise 7x7, ``384 -> 512 -> 384``, and
  ``384 -> 128``);
- the mask head and the convex combination of 6 channels a prediction.
"""

from __future__ import annotations

from benchmark import flops
from benchmark.flops import _c

STAGES = ((64, 3), (128, 4), (256, 6))     # ResNet-34's first three
BLOCK_IN = 384                             # cat[net, context, motion]


def resnet_ops(H, W, cin, out_dim):
    """One call of one encoder (stem /2, stages at /2, /4, /8)."""
    h, w = -(-H // 2), -(-W // 2)
    ops, c = _c(h, w, 7, 7, cin, 64), 64
    for s, (planes, blocks) in enumerate(STAGES):
        if s:
            h, w = -(-h // 2), -(-w // 2)
        for b in range(blocks):
            ops += _c(h, w, 3, 3, c, planes) + _c(h, w, 3, 3, planes, planes)
            if c != planes:
                ops += _c(h, w, 1, 1, c, planes)
            c = planes
    return ops + _c(h, w, 1, 1, c, out_dim)


def convnext_ops(cfg, h, w):
    """One ConvNeXt block: depthwise 7x7, W1, W2, final."""
    hd = cfg["hidden_dim"]
    return (2 * h * w * 49 * BLOCK_IN + _c(h, w, 1, 1, BLOCK_IN, 4 * hd)
            + _c(h, w, 1, 1, 4 * hd, BLOCK_IN) + _c(h, w, 1, 1, BLOCK_IN, hd))


def head_ops(cfg, h, w):
    """The flow head (6 channels out) once."""
    hd = cfg["hidden_dim"]
    return _c(h, w, 3, 3, hd, 2 * hd) + _c(h, w, 3, 3, 2 * hd, 6)


def update_ops(cfg, h, w):
    """One refinement iteration: motion encoder, two blocks, the head."""
    planes = cfg["corr_levels"] * (2 * cfg["corr_radius"] + 1) ** 2
    motion = (_c(h, w, 1, 1, planes, 256) + _c(h, w, 3, 3, 256, 192)
              + _c(h, w, 7, 7, 2, 128) + _c(h, w, 3, 3, 128, 64)
              + _c(h, w, 3, 3, 256, 126))
    return (motion + cfg["num_blocks"] * convnext_ops(cfg, h, w)
            + head_ops(cfg, h, w))


def upsample_ops(cfg, h, w):
    """Mask head and the convex combination of flow and info."""
    return (_c(h, w, 3, 3, cfg["hidden_dim"], 256) + _c(h, w, 1, 1, 256, 576)
            + 2 * h * w * 9 * 64 * 6)


def forward_ops(cfg, H, W, iters, upsamples):
    """One pair, forward; ``upsamples`` predictions are brought to full
    resolution (``iters + 1`` in training, the last one when serving)."""
    h, w = H // 8, W // 8
    dim = cfg["hidden_dim"] + cfg["context_dim"]
    enc = (2 * resnet_ops(H, W, 3, cfg["fnet_dim"])
           + resnet_ops(H, W, 6, dim) + _c(h, w, 3, 3, dim, dim))
    look = flops.lookup_cost(cfg, h, w, 2, 2)[0]
    return (enc + flops.volume_ops(cfg, h, w) + head_ops(cfg, h, w)
            + iters * (update_ops(cfg, h, w) + look)
            + upsamples * upsample_ops(cfg, h, w))


def train_ops(cfg, H, W, iters):
    """Forward + backward (twice the forward), recomputation not counted."""
    return 3 * forward_ops(cfg, H, W, iters, iters + 1)


def dwconv_cost(h, w, lanes, channels, nbytes):
    """The least one product of the depthwise convolution over ``lanes``
    pairs can do, as (operations, bytes): each of its three products moves
    two ``(h, w, channels)`` arrays a pair and no more -- the forward reads
    ``u`` and writes the result; the input's cotangent reads ``dy`` and
    writes ``du``; the kernel's cotangent reads ``u`` and ``dy`` and writes
    49 numbers a channel.  49 multiply-adds an element: ~25 operations a
    byte in bfloat16, under the v5e's ~240, so bytes bound all three."""
    n = lanes * h * w * channels
    return 2 * 49 * n, 2 * n * nbytes

