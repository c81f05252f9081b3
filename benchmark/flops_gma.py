"""Operations and bytes that GMA needs, from shapes alone: RAFT-full's
(``benchmark/flops.py``) plus the attention block (PAPERS.md).

Counted as ``flops.py`` counts: a multiply-add is two operations; softmax,
norms, activations and the elementwise GRU arithmetic are left out, which
can only make a share of the peak read low.  With ``N = h*w`` positions at
1/8 resolution and one head of ``d = context_dim`` channels, a pair needs

- once: the ``[q, k]`` 1x1 convolution (context_dim -> 2d) and ``q k^T``,
  ``2 N^2 d``;
- every iteration: the ``v`` 1x1 convolution (128 -> 128), ``A v``,
  ``2 N^2 128``, and a GRU whose input is 128 channels wider.
"""

from __future__ import annotations

from benchmark import flops
from benchmark.flops import _c

MOTION = 128        # the motion encoder's output channels (126 + flow)


def attention_ops(cfg, h, w):
    """Once a pair: ``[q, k]`` and ``q k^T``."""
    d = cfg["context_dim"]
    return _c(h, w, 1, 1, d, 2 * d) + 2 * (h * w) ** 2 * d


def aggregate_ops(cfg, h, w):
    """One ``A v`` for one pair."""
    return 2 * (h * w) ** 2 * MOTION


def update_ops(cfg, h, w):
    """One refinement iteration: RAFT-full's, the GRU's four convolutions
    over an input 128 channels wider, the ``v`` convolution and ``A v``."""
    hd = cfg["hidden_dim"]
    wider = 2 * (_c(h, w, 1, 5, MOTION, 2 * hd) + _c(h, w, 1, 5, MOTION, hd))
    return (flops.update_ops(cfg, h, w) + wider
            + _c(h, w, 1, 1, MOTION, MOTION) + aggregate_ops(cfg, h, w))


def forward_ops(cfg, H, W, iters, upsamples):
    """One pair, forward; as ``flops.forward_ops``."""
    h, w = H // 8, W // 8
    extra = flops.update_ops(cfg, h, w)
    return (flops.forward_ops(cfg, H, W, iters, upsamples)
            + attention_ops(cfg, h, w)
            + iters * (update_ops(cfg, h, w) - extra))


def train_ops(cfg, H, W, iters):
    """Forward + backward (twice the forward), recomputation not counted."""
    return 3 * forward_ops(cfg, H, W, iters, iters)


def aggregate_cost(n, lanes, nbytes):
    """The least one product of the global aggregate over ``lanes`` pairs
    can do, as (operations, bytes); ``n`` positions, ``nbytes`` an entry.
    Each of the three products moves one ``n*n`` array and two ``(n, 128)``
    ones: ``A v`` (forward, or recomputed in the backward pass) and
    ``dv = A^T dg`` read ``A`` once; ``dA = dg v^T`` writes an ``n*n``
    cotangent once.  So forward is ``A`` once and backward ``A`` twice.
    The backward pass also sums the iterations' ``dA``; XLA fuses that sum
    into the softmax's backward, whose time no matcher can tell apart, so
    the accumulator's read-back is on neither side of the roofline share.
    The ``(n, 128)`` operands are 1/22 of ``A`` at the chairs crop."""
    return (lanes * 2 * n * n * MOTION,
            lanes * (n * n + 2 * n * MOTION) * nbytes)
