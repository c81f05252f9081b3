"""Convex-combination flow upsampling (reference ``core/raft.py:72-83``).

Each full-resolution pixel is a convex combination (softmax weights) of the
3x3 coarse-grid neighborhood of its parent cell.  The reference implements
this with ``F.unfold`` + reshapes in NCHW; here it is a single einsum over
extracted patches in NHWC, which XLA fuses cleanly.

Channel-order contract (for weight conversion parity): the mask produced by
the update block has ``64 * 9`` channels which factorize as
``(k, p, q) -> k * 64 + p * 8 + q`` where ``k`` indexes the 3x3 tap
(row-major: k = (dy+1)*3 + (dx+1), matching ``F.unfold``'s (ki, kj) order)
and ``(p, q)`` is the subpixel position (reference ``raft.py:75``:
``mask.view(N, 1, 9, 8, 8, H, W)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _extract_3x3_patches(x: jax.Array) -> jax.Array:
    """``(B, H, W, C)`` -> ``(B, H, W, 9, C)``, taps in unfold order."""
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    H, W = x.shape[1], x.shape[2]
    taps = []
    for di in range(3):       # row offset (ki)
        for dj in range(3):   # col offset (kj)
            taps.append(xp[:, di:di + H, dj:dj + W, :])
    return jnp.stack(taps, axis=3)


def convex_upsample_data(x: jax.Array, mask: jax.Array, factor: int = 8,
                         scale: float = 1) -> jax.Array:
    """Upsample ``(B, H, W, C)`` coarse values to ``(B, 8H, 8W, C)``: each
    fine pixel a convex combination (softmax of ``mask`` over the 9 taps)
    of the 3x3 coarse neighbours of ``scale * x``.  With ``scale == 1``
    this is what SEA-RAFT's ``upsample_data`` does to the 4 channels of
    ``info`` beside a flow (the flow's weights, no factor on the values).

    ``mask``: ``(B, H, W, 9 * factor * factor)`` unnormalized weights."""
    B, H, W, C = x.shape
    f = factor
    m = mask.reshape(B, H, W, 9, f, f)
    m = jax.nn.softmax(m, axis=3)

    patches = _extract_3x3_patches(x if scale == 1 else scale * x)
    up = jnp.einsum("bhwkpq,bhwkc->bhpwqc", m, patches.astype(m.dtype))
    return up.reshape(B, f * H, f * W, C)


def convex_upsample(flow: jax.Array, mask: jax.Array,
                    factor: int = 8) -> jax.Array:
    """Upsample ``(B, H, W, 2)`` flow to ``(B, 8H, 8W, 2)``.

    Args:
      flow: coarse flow in coarse-pixel units (scaled by ``factor`` inside,
        reference ``raft.py:77``).
      mask: ``(B, H, W, 9 * factor * factor)`` unnormalized weights.
    """
    return convex_upsample_data(flow, mask, factor, scale=factor)


def convex_combine_flat(x: jax.Array, mask: jax.Array, factor: int = 8,
                        compute_dtype=jnp.float32,
                        scale: float = 1) -> jax.Array:
    """:func:`convex_upsample_data` in space-to-depth layout — the
    TPU-native training formulation — for ``C`` channels that share one
    set of weights: ``x`` is ``(B, H, W, C)`` coarse values as they are to
    be combined, times ``scale`` (a flow: ``factor``).

    The 6-D ``(B, H, W, 9, 8, 8)`` shapes of the direct einsum put 2- and
    8-wide trailing dims in the lanes, which on TPU lowers to tiny-tile
    layouts plus relayout copies on every tensor touched (profiled at
    ~250 ms/step, HBM-bound at 5-7%% of peak BW).  Here every intermediate
    stays a channels-last 2-D tile: the softmax over the 9 taps uses
    contiguous 64-channel slices (channel order is ``k*64 + p*8 + q``,
    the converter contract), and the convex combination is 9 broadcast
    multiply-adds a channel; the weights are computed once for all.

    Returns ``(B, H, W, C * factor**2)`` with channel order ``(c, p, q)``
    — ``out[..., c*ff + p*f + q] == convex_upsample_data(...)[..., f*h+p,
    f*w+q, c]`` (see :func:`space_to_depth_flow` for the matching ground
    -truth layout; :func:`depth_to_space_flow` restores pixel space).
    """
    B, H, W, C = x.shape
    ff = factor * factor
    # compute_dtype=bfloat16 halves the HBM traffic of the 9-tap
    # exp/FMA/divide chain (the softmax weights are in [0,1] and the
    # flow taps O(max_flow); rounding is ~0.4% relative on the upsampled
    # flow).  fp32 default preserves the reference's loss numerics (its
    # upsample_flow runs outside autocast, raft.py:72-83).
    m = mask.astype(compute_dtype)
    # Per-tap-group max (elementwise max over the 9 contiguous ff-channel
    # slices) keeps every group's softmax unconditionally stable — a
    # global per-pixel max would underflow denom to 0 (NaN) for any
    # subpixel group sitting far below the pixel's hottest group.
    taps = [m[..., k * ff:(k + 1) * ff] for k in range(9)]
    gmax = taps[0]
    for t in taps[1:]:
        gmax = jnp.maximum(gmax, t)
    gmax = jax.lax.stop_gradient(gmax)
    e = [jnp.exp(t - gmax) for t in taps]
    denom = sum(e)

    x = x.astype(compute_dtype)
    xp = jnp.pad(x if scale == 1 else scale * x,
                 ((0, 0), (1, 1), (1, 1), (0, 0)))
    outs = [0.0] * C
    for k in range(9):
        di, dj = k // 3, k % 3   # unfold tap order (row-major)
        xk = xp[:, di:di + H, dj:dj + W, :]
        for c in range(C):
            outs[c] += e[k] * xk[..., c:c + 1]
    # One reciprocal + C muls instead of C 64-channel divides (TPU
    # divide is a multi-pass VPU op; profiled ~4 ms/step across the 12
    # iterations' forward+backward).
    inv = 1.0 / denom
    return jnp.concatenate([o * inv for o in outs], axis=-1)


def convex_upsample_flat(flow: jax.Array, mask: jax.Array,
                         factor: int = 8,
                         compute_dtype=jnp.float32) -> jax.Array:
    """:func:`convex_upsample` in space-to-depth layout
    (:func:`convex_combine_flat` of ``factor * flow``): returns
    ``(B, H, W, 2 * factor**2)``, channel order ``(c, p, q)``."""
    return convex_combine_flat(flow, mask, factor, compute_dtype,
                               scale=factor)


def space_to_depth_flow(x: jax.Array, factor: int = 8) -> jax.Array:
    """``(B, f*H, f*W, C)`` -> ``(B, H, W, C * f * f)``, channel order
    ``(c, p, q)`` — the ground-truth-side layout matching
    :func:`convex_upsample_flat` (the sequence loss compares the two
    WITHOUT ever materializing full-resolution per-iteration flows)."""
    B, FH, FW, C = x.shape
    H, W = FH // factor, FW // factor
    x = x.reshape(B, H, factor, W, factor, C)
    x = x.transpose(0, 1, 3, 5, 2, 4)
    return x.reshape(B, H, W, C * factor * factor)


def depth_to_space_flow(x: jax.Array, channels: int = 2,
                        factor: int = 8) -> jax.Array:
    """Inverse of :func:`space_to_depth_flow`: ``(B, H, W, C*f*f)`` with
    ``(c, p, q)`` channel order -> ``(B, f*H, f*W, C)``."""
    B, H, W, _ = x.shape
    x = x.reshape(B, H, W, channels, factor, factor)
    x = x.transpose(0, 1, 4, 2, 5, 3)
    return x.reshape(B, H * factor, W * factor, channels)
