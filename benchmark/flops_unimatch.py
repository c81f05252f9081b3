"""Operations that UniMatch's flow model (GMFlow at two scales, six
regression refinements) needs, from shapes alone (PAPERS.md has the
equations).

Counted as ``flops.py`` counts: a multiply-add is two operations; norms,
activations, every softmax, the bilinear upsamplings and the warp's
interpolation are left out, which can only make a share of the peak read
low.  With ``N = h*w`` positions at a scale, ``C`` channels, ``K x K``
windows of ``n = N / K^2`` tokens, a pair needs

- the backbone once an image: the stem and first stage at 1/2, the second
  and third stages at 1/4 (the third at stride 1, its first block with a
  1x1 downsample for the width), the 1x1 projection at 1/4, the trident's
  3x3 at 1/8 and at 1/4;
- at each scale six Transformer blocks over both maps (``flops_gmflow``'s
  block at that scale's ``N`` and ``n``);
- at 1/8 the global matching and propagation (``flops_gmflow.match_ops``);
  at 1/4 the local match (81 dot products and the expectation a
  position) and the local propagation (two ``C x C`` projections, 9 dot
  products, the weighted flow);
- the 1/4 volume once (``2 N^2 C``), ``refine_proj`` once, and in each
  refinement the lookup (8 operations a tap), RAFT's motion encoder, GRU
  and flow head (``flops.update_ops`` with 81 correlation channels), the
  mask head (``9 * 4^2`` logits) and the convex combination.
"""

from __future__ import annotations

from benchmark import flops
from benchmark.flops import _c
from benchmark.flops_gmflow import attention_cost  # noqa: F401 (readers)


def _scale_dims(cfg, H, W, s):
    h, w = H // (8 >> s), W // (8 >> s)
    k = int(cfg["attn_splits"][s])
    return h, w, h * w, (h * w) // (k * k), int(cfg["feature_channels"])


def backbone_ops(cfg, H, W):
    """One image: stem /2, stages (64, /2), (96, /4), (128, /4), the 1x1
    projection at 1/4 and the trident at 1/8 and at 1/4."""
    C = int(cfg["feature_channels"])
    h2, w2, h4, w4 = H // 2, W // 2, H // 4, W // 4
    ops, cin = _c(h2, w2, 7, 7, 3, 64), 64
    for (ho, wo), planes in (((h2, w2), 64), ((h4, w4), 96),
                             ((h4, w4), 128)):
        for b in range(2):
            ops += _c(ho, wo, 3, 3, cin, planes) + _c(ho, wo, 3, 3, planes,
                                                      planes)
            if b == 0 and cin != planes:
                ops += _c(ho, wo, 1, 1, cin, planes)
            cin = planes
    ops += _c(h4, w4, 1, 1, 128, C)
    return ops + _c(H // 8, W // 8, 3, 3, C, C) + _c(h4, w4, 3, 3, C, C)


def attention_ops(cfg, H, W, s):
    """One window attention (self or cross) over one image's map at scale
    ``s``: ``q k^T`` and ``P v`` in each of its windows."""
    _, _, N, n, C = _scale_dims(cfg, H, W, s)
    return 4 * N * n * C


def block_ops(cfg, H, W, s):
    """One Transformer block over one image's map at scale ``s``."""
    _, _, N, _, C = _scale_dims(cfg, H, W, s)
    e = int(cfg["ffn_dim_expansion"])
    linears = 8 * 2 * N * C * C + 2 * N * (2 * C) * (2 * C * e) \
        + 2 * N * (2 * C * e) * C
    return linears + 2 * attention_ops(cfg, H, W, s)


def match_ops(cfg, H, W):
    """Global matching and propagation at 1/8; local matching and
    propagation at 1/4."""
    _, _, N, _, C = _scale_dims(cfg, H, W, 0)
    glob = 2 * (2 * N * N * C + 4 * N * N) + 2 * 2 * N * C * C
    _, _, N, _, C = _scale_dims(cfg, H, W, 1)
    m = (2 * int(cfg["match_radius"][1]) + 1) ** 2
    p = (2 * int(cfg["prop_radius"][1]) + 1) ** 2
    local = (2 * N * m * C + 2 * N * m * 2
             + 2 * 2 * N * C * C + 2 * N * p * C + 2 * N * p * 2)
    return glob + local


def refine_ops(cfg, H, W):
    """One refinement iteration for one pair, forward: lookup, update
    block, mask head and convex x4."""
    h, w = H // 4, W // 4
    look = flops.lookup_cost(cfg, h, w, 2, 2)[0]
    f = int(cfg["upsample_factor"])
    return (look + flops.update_ops(cfg, h, w)
            + _c(h, w, 3, 3, cfg["hidden_dim"], 256)
            + _c(h, w, 1, 1, 256, 9 * f * f) + 2 * h * w * 9 * f * f * 2)


def forward_ops(cfg, H, W, iters):
    """One pair, forward."""
    h, w, C = H // 4, W // 4, int(cfg["feature_channels"])
    layers = int(cfg["num_transformer_layers"])
    return (2 * backbone_ops(cfg, H, W)
            + sum(2 * layers * block_ops(cfg, H, W, s)
                  for s in range(int(cfg["num_scales"])))
            + match_ops(cfg, H, W)
            + 2 * (h * w) ** 2 * C + _c(h, w, 1, 1, C, 2 * cfg["hidden_dim"])
            + iters * refine_ops(cfg, H, W))


def train_ops(cfg, H, W, iters):
    """Forward + backward (twice the forward), recomputation not counted;
    every refinement's mask and convex upsampling are supervised."""
    return 3 * forward_ops(cfg, H, W, iters)
