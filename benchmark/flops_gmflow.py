"""Operations and bytes that GMFlow (one scale) needs, from shapes alone
(PAPERS.md has the equations).

Counted as ``flops.py`` counts: a multiply-add is two operations; norms,
activations, every softmax and the bilinear upsampling are left out, which
can only make a share of the peak read low.  With ``N = h*w`` positions at
1/8 resolution, ``C`` channels, ``K x K`` windows of ``n = N / K^2`` tokens,
a pair needs

- the backbone (RAFT-full's feature encoder, ``C`` wide) once an image;
- six blocks over both maps, each: eight ``C x C`` projections (q, k, v and
  merge, for the self- and the cross-attention), two window attentions
  (``q k^T`` and ``P v``: ``4 n^2 C`` a window), and the FFN (``2C -> 8C ->
  C``);
- the matching (``F1 F2^T`` and ``P G``), the propagation (two ``C x C``
  projections, ``q k^T`` and ``P flow``), the upsampler's two convolutions
  and the convex combination.
"""

from __future__ import annotations

from benchmark import flops
from benchmark.flops import _c


def _dims(cfg, H, W):
    h, w = H // 8, W // 8
    k = int(cfg["attn_splits"])
    return h, w, h * w, (h * w) // (k * k), int(cfg["feature_channels"])


def attention_ops(cfg, H, W):
    """One window attention (self or cross) over one image's map: ``q k^T``
    and ``P v`` in each of its windows."""
    _, _, N, n, C = _dims(cfg, H, W)
    return 4 * N * n * C


def block_ops(cfg, H, W):
    """One Transformer block over one image's map."""
    _, _, N, _, C = _dims(cfg, H, W)
    e = int(cfg["ffn_dim_expansion"])
    linears = 8 * 2 * N * C * C + 2 * N * (2 * C) * (2 * C * e) \
        + 2 * N * (2 * C * e) * C
    return linears + 2 * attention_ops(cfg, H, W)


def match_ops(cfg, H, W):
    """The matching, and the propagation that follows it."""
    _, _, N, _, C = _dims(cfg, H, W)
    one = 2 * N * N * C + 4 * N * N
    return 2 * one + 2 * 2 * N * C * C


def upsample_ops(cfg, H, W):
    h, w, _, _, C = _dims(cfg, H, W)
    return (_c(h, w, 3, 3, C + 2, 256) + _c(h, w, 1, 1, 256, 576)
            + 2 * h * w * 9 * 64 * 2)


def forward_ops(cfg, H, W):
    """One pair, forward."""
    C = int(cfg["feature_channels"])
    return (2 * flops.encoder_ops({"small": False}, H, W, C)
            + 2 * int(cfg["num_transformer_layers"]) * block_ops(cfg, H, W)
            + match_ops(cfg, H, W) + upsample_ops(cfg, H, W))


def train_ops(cfg, H, W, iters=0):
    """Forward + backward (twice the forward), recomputation not counted;
    the model has no iterations and ``iters`` is not read."""
    return 3 * forward_ops(cfg, H, W)


def attention_cost(n, windows, channels, nbytes):
    """The least one window attention over ``windows`` windows of ``n``
    tokens can do, forward, as (operations, bytes), whatever implements
    it: ``q``, ``k``, ``v`` read and the result written once, ``4 n^2 C``
    operations a window.  Its backward is four products of the same size
    (``dv``, ``dP``, ``dq``, ``dk``): twice the operations, and ``q``,
    ``k``, ``v`` and the result's cotangent read, three cotangents written
    (7 arrays where the forward moves 4).  At ``n = 768``, ``C = 128`` in
    bfloat16 that is ~380 operations a byte, over the v5e's ~240: by this
    count operations bound it, and a program that writes its ``n x n``
    scores to memory reads far under 100 %."""
    return (windows * 4 * n * n * channels,
            windows * 4 * n * channels * nbytes)


def match_cost(n, lanes, channels, nbytes):
    """The least the matching over ``lanes`` pairs can do, forward, as
    (operations, bytes): ``F1``, ``F2`` read and the flow written,
    ``2 N^2 C + 4 N^2`` operations a pair."""
    return (lanes * (2 * n * n * channels + 4 * n * n),
            lanes * (2 * n * channels + 2 * n) * nbytes)
