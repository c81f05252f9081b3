"""GMFlow at one scale (Xu et al., CVPR 2022, arXiv:2111.13680; PAPERS.md
has the equations): the body :class:`raft_tpu.models.raft.RAFT` runs for
``arch='gmflow'``.

No hidden state, no pyramid, no lookup and no loop.  Both 1/8 feature maps
(RAFT's :class:`BasicEncoder`, 128 wide) get one window's sine position,
pass through six Transformer blocks of shifted-window self- and
cross-attention, and are matched globally: one softmax over the whole
``N x N`` correlation volume (RAFT's own all-pairs product,
``ops/corr.py``) whose expectation over the pixel grid is the flow.  One
global self-attention over image 1's features propagates that flow, and
RAFT's convex combination (``ops/upsample.py``) brings it to full
resolution.  Training makes two predictions: the matched flow upsampled
bilinearly, and the propagated one upsampled convexly, whose gradient
stops at the matched flow.

Precision follows ``RAFTConfig.compute_dtype``: the backbone, the
projections, the FFN, ``P v`` and the upsampler's convolutions run in it;
every softmax with its scores, the LayerNorms, the residual stream between
the Transformer's layers, the correlation, the grid and the flow are
float32.

Each part traces under a ``jax.named_scope``: ``gmflow_backbone``,
``gmflow_self_attn``, ``gmflow_cross_attn``, ``gmflow_ffn``,
``gmflow_match``, ``gmflow_propagate`` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.models.extractor import BasicEncoder
from raft_tpu.models.layers import conv
from raft_tpu.ops import pallas_attention
from raft_tpu.ops.corr import all_pairs_correlation
from raft_tpu.ops.sampler import coords_grid, upflow8
from raft_tpu.ops.upsample import (convex_upsample, convex_upsample_flat,
                                   space_to_depth_flow)
from raft_tpu.parallel.mesh import image_rows_split

CHANNELS = 128          # feature_channels
LAYERS = 6              # num_transformer_layers
SPLITS = 2              # K x K windows (RAFTConfig.attn_splits, for the pad)
FFN_EXPANSION = 4       # ffn_dim_expansion, over the 2C-wide [s, m]
MASK_VALUE = -100.0     # between tokens of different regions
LN_EPS = 1e-5
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


def sine_position(h: int, w: int, channels: int = CHANNELS,
                  temperature: float = 10000.0) -> np.ndarray:
    """DETR's normalised sine embedding of an ``(h, w)`` map, ``(h, w,
    channels)`` float32, channels ``[pos_y, pos_x]``: ``channels / 2``
    frequencies an axis, sin on even and cos on odd indices, the axis
    coordinate a cumulative sum of ones over its last value plus 1e-6,
    times 2 pi."""
    f = channels // 2
    y = np.arange(1, h + 1, dtype=np.float64)
    x = np.arange(1, w + 1, dtype=np.float64)
    y = y / (y[-1] + 1e-6) * 2 * np.pi
    x = x / (x[-1] + 1e-6) * 2 * np.pi
    dim_t = temperature ** (2 * (np.arange(f) // 2) / f)

    def embed(c):
        p = c[:, None] / dim_t
        return np.stack([np.sin(p[:, 0::2]), np.cos(p[:, 1::2])],
                        axis=2).reshape(len(c), f)

    pos = np.concatenate(
        [np.broadcast_to(embed(y)[:, None], (h, w, f)),
         np.broadcast_to(embed(x)[None, :], (h, w, f))], axis=-1)
    return pos.astype(np.float32)


def split_windows(x: jax.Array, splits: int = SPLITS) -> jax.Array:
    """``(B, h, w, C) -> (B * K * K, h/K, w/K, C)``, windows row-major."""
    B, h, w, C = x.shape
    x = x.reshape(B, splits, h // splits, splits, w // splits, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        B * splits * splits, h // splits, w // splits, C)


def merge_windows(x: jax.Array, splits: int = SPLITS) -> jax.Array:
    """Inverse of :func:`split_windows`."""
    BKK, hk, wk, C = x.shape
    B = BKK // (splits * splits)
    x = x.reshape(B, splits, splits, hk, wk, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        B, splits * hk, splits * wk, C)


def shift_regions(h: int, w: int, splits: int = SPLITS) -> np.ndarray:
    """Swin's regions of a map rolled by half a window, a window: ``(K *
    K, n)`` int32, the id of each token's region; the regions are the 3 x 3
    slices ``[0, -win)``, ``[-win, -shift)``, ``[-shift, end)`` of each
    axis.  Tokens of a rolled window attend to each other iff their ids
    agree."""
    wh, ww = h // splits, w // splits
    region = np.zeros((h, w), np.int32)
    n = 0
    for hs in (slice(0, -wh), slice(-wh, -(wh // 2)), slice(-(wh // 2), None)):
        for ws in (slice(0, -ww), slice(-ww, -(ww // 2)),
                   slice(-(ww // 2), None)):
            region[hs, ws] = n
            n += 1
    win = region.reshape(splits, wh, splits, ww).transpose(0, 2, 1, 3)
    return win.reshape(splits * splits, wh * ww)


def shift_mask(h: int, w: int, splits: int = SPLITS) -> np.ndarray:
    """:func:`shift_regions` as the additive mask: ``(K * K, n, n)``
    float32, 0 between tokens of the same region and ``MASK_VALUE``
    otherwise."""
    win = shift_regions(h, w, splits)
    same = win[:, :, None] == win[:, None, :]
    return np.where(same, 0.0, MASK_VALUE).astype(np.float32)


def window_attention_path(h: int, w: int, channels: int, dtype) -> str:
    """``'mosaic'`` or ``'xla'``: which :func:`window_attention` a program
    traced now holds for an ``(h, w)`` map, by
    ``ops.pallas_attention.window_attention_path`` from what this process
    can observe (the backend, whether image rows are split over devices,
    the window's shape)."""
    return pallas_attention.window_attention_path(
        jax.default_backend(), h // SPLITS, w // SPLITS, channels,
        jnp.dtype(dtype).itemsize, rows_split=image_rows_split())


def window_attention(q, k, v, h: int, w: int, shift: bool, dtype,
                     recompute: bool = False) -> jax.Array:
    """Single-head attention inside each of the K x K windows of an ``(h,
    w)`` map: ``q, k, v`` are ``(B, h * w, C)``; with ``shift`` the maps
    are rolled by half a window first and back after, and tokens of
    different regions of a rolled window are masked off each other.
    Scores and softmax float32, ``P v`` in ``dtype``.

    Two bodies, one result (:func:`window_attention_path` says which): the
    Mosaic kernels of ``ops/pallas_attention.py``, which keep a window's
    scores in VMEM and address the windows themselves (only the roll is
    ``jnp.roll`` there too), and the ``jnp`` one below.  ``recompute`` is read by the
    ``jnp`` body alone: keep ``q``, ``k``, ``v`` for the backward pass and
    rebuild the ``(n, n)`` scores and their softmax there; the kernels
    always keep ``q``, ``k``, ``v``, the result and the rows' log-sum-exp,
    and never hold scores outside a grid step."""
    B, _, C = q.shape
    sh, sw = h // (2 * SPLITS), w // (2 * SPLITS)
    if window_attention_path(h, w, C, dtype) == "mosaic":
        out = pallas_attention.window_attention(
            *(x.reshape(B, h, w, C) for x in (q, k, v)), SPLITS,
            (sh, sw) if shift else None,
            shift_regions(h, w) if shift else None, MASK_VALUE)
        return out.reshape(B, h * w, C)
    if recompute:
        return jax.checkpoint(
            lambda q, k, v: window_attention(q, k, v, h, w, shift, dtype))(
                q, k, v)

    def windows(x):
        x = x.reshape(B, h, w, C)
        if shift:
            x = jnp.roll(x, (-sh, -sw), axis=(1, 2))
        x = split_windows(x)
        return x.reshape(x.shape[0], -1, C)

    q, k, v = windows(q), windows(k), windows(v)
    scores = jnp.einsum("bnc,bmc->bnm", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / float(C) ** 0.5)
    if shift:
        n = scores.shape[-1]
        scores = (scores.reshape(B, SPLITS * SPLITS, n, n)
                  + shift_mask(h, w)).reshape(-1, n, n)
    prob = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bnm,bmc->bnc", prob, v,
                     preferred_element_type=jnp.float32).astype(dtype)
    out = merge_windows(out.reshape(-1, h // SPLITS, w // SPLITS, C))
    if shift:
        out = jnp.roll(out, (sh, sw), axis=(1, 2))
    return out.reshape(B, h * w, C)


def _linear(features, dtype, name, use_bias=False):
    return nn.Dense(features, use_bias=use_bias, dtype=dtype, name=name,
                    kernel_init=nn.initializers.xavier_uniform())


class TransformerLayer(nn.Module):
    """``L(s, t)``: ``m = LN1(A(s W_q, t W_k, t W_v) W_o)``; without the
    FFN (the self-attention half of a block) returns ``s + m``, with it
    (the cross-attention half) ``s + LN2(W_2 gelu(W_1 [s, m]))``, erf
    GELU, ``W_1: 2C -> 8C``.  No biases in the ``Linear``s.  The products
    run in ``dtype``; the LayerNorms give float32 and ``source`` keeps its
    own type (float32: the residual stream)."""

    ffn: bool
    shift: bool
    dtype: Any = jnp.float32
    recompute_scores: bool = False

    @nn.compact
    def __call__(self, source, target, h: int, w: int):
        dt, C = self.dtype, source.shape[-1]
        s, t = source.astype(dt), target.astype(dt)
        with jax.named_scope("gmflow_cross_attn" if self.ffn
                             else "gmflow_self_attn"):
            q = _linear(C, dt, "q_proj")(s)
            k = _linear(C, dt, "k_proj")(t)
            v = _linear(C, dt, "v_proj")(t)
            m = window_attention(q, k, v, h, w, self.shift, dt,
                                 self.recompute_scores)
            m = _linear(C, dt, "merge")(m)
            m = nn.LayerNorm(epsilon=LN_EPS, dtype=jnp.float32,
                             name="norm1")(m)
        if self.ffn:
            with jax.named_scope("gmflow_ffn"):
                m = _linear(2 * C * FFN_EXPANSION, dt, "mlp_0")(
                    jnp.concatenate([s, m.astype(dt)], axis=-1))
                m = _linear(C, dt, "mlp_2")(nn.gelu(m, approximate=False))
                m = nn.LayerNorm(epsilon=LN_EPS, dtype=jnp.float32,
                                 name="norm2")(m)
        # the residual stream stays float32: a sum of twelve messages
        # rounded to bfloat16 at every layer would carry that rounding
        # into the correlation
        return source + m


class TransformerBlock(nn.Module):
    """Self-attention, then cross-attention with the FFN."""

    shift: bool
    dtype: Any = jnp.float32
    recompute_scores: bool = False

    @nn.compact
    def __call__(self, x, h: int, w: int):
        # x: [F1; F2] on the batch; the target is the other image's map,
        # re-formed from x after the self-attention of this block
        x = TransformerLayer(False, self.shift, self.dtype,
                             self.recompute_scores,
                             name="self_attn")(x, x, h, w)
        B = x.shape[0] // 2
        other = jnp.concatenate([x[B:], x[:B]], axis=0)
        return TransformerLayer(True, self.shift, self.dtype,
                                self.recompute_scores,
                                name="cross_attn_ffn")(x, other, h, w)


class FeatureTransformer(nn.Module):
    """Six blocks over ``[F1; F2]``; odd blocks shift their windows.
    ``recompute`` says what the backward pass rebuilds: ``"none"``;
    ``"scores"``, each window attention's score matrix and softmax (its
    ``q``, ``k``, ``v`` are kept) where the ``jnp`` body runs -- the Mosaic
    kernels hold no scores to keep or rebuild, so there it is ``"none"``;
    ``"block"``, every block from its input (PERF.md section 4 has what
    each holds and costs)."""

    dtype: Any = jnp.float32
    recompute: str = "none"

    @nn.compact
    def __call__(self, x, h: int, w: int):
        block = TransformerBlock
        if self.recompute == "block":
            block = nn.remat(TransformerBlock, static_argnums=(2, 3))
        for i in range(LAYERS):
            x = block(i % 2 == 1, self.dtype, self.recompute == "scores",
                      name=f"layers_{i}")(x, h, w)
        return x


def global_match(f1: jax.Array, f2: jax.Array, precision) -> jax.Array:
    """``softmax(F1 F2^T / sqrt(C))`` over all of image 2, and the flow as
    the expected displacement: ``P G - G``, ``G`` the (x, y) grid.  Float32
    throughout.  ``(B, h, w, C) x 2 -> (B, h, w, 2)``."""
    B, h, w, _ = f1.shape
    with jax.named_scope("gmflow_match"):
        corr = all_pairs_correlation(f1, f2, precision).reshape(
            B, h * w, h * w)
        prob = jax.nn.softmax(corr, axis=-1)
        grid = coords_grid(B, h, w).reshape(B, h * w, 2)
        match = jnp.einsum("bnm,bmc->bnc", prob, grid,
                           precision=jax.lax.Precision.HIGHEST)
        return (match - grid).reshape(B, h, w, 2)


class FlowPropagation(nn.Module):
    """One global self-attention over image 1's features that carries the
    flow: ``q = F1 U_q + b_q``, ``k = q U_k + b_k`` (the key is projected
    from the projected query, as the public code has it), ``softmax(q k^T
    / sqrt(C)) flow``.  The flow and the softmax stay float32."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, f1, flow):
        B, h, w, C = f1.shape
        dt = self.dtype
        with jax.named_scope("gmflow_propagate"):
            q = _linear(C, dt, "q_proj", True)(
                f1.reshape(B, h * w, C).astype(dt))
            k = _linear(C, dt, "k_proj", True)(q)
            scores = jnp.einsum("bnc,bmc->bnm", q, k,
                                preferred_element_type=jnp.float32)
            prob = jax.nn.softmax(scores * (1.0 / float(C) ** 0.5), axis=-1)
            out = jnp.einsum("bnm,bmc->bnc", prob, flow.reshape(B, h * w, 2),
                             precision=jax.lax.Precision.HIGHEST)
        return out.reshape(B, h, w, 2)


class Upsampler(nn.Module):
    """``conv1x1(relu(conv3x3([flow, F1], 130 -> 256)), 256 -> 576)``: the
    logits of RAFT's convex combination, with no 0.25 in front."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, flow, f1):
        dt = self.dtype
        x = jnp.concatenate([flow.astype(dt), f1.astype(dt)], axis=-1)
        x = nn.relu(conv(256, 3, 1, dt, name="conv1",
                         torch_default_init=True,
                         in_features=x.shape[-1])(x))
        return conv(64 * 9, 1, 1, dt, name="conv2", torch_default_init=True,
                    in_features=256)(x)


def _detached(flow):
    """``flow.detach()``: what the propagation carries is the matched flow
    as a value, so the second prediction sends nothing into the matching
    (a function of its own so that a test can plant its absence)."""
    return jax.lax.stop_gradient(flow)


def _l1_term(pred, flow_gt, vmask):
    """One prediction's full-resolution term: mean(valid * |pred - gt|)
    over every pixel and both channels, and its summed end-point error."""
    err = pred.astype(jnp.float32) - flow_gt
    diff = jax.lax.stop_gradient(err)
    epe = jnp.sum(vmask * jnp.sqrt(jnp.sum(diff * diff, axis=-1)))
    return jnp.mean(vmask[..., None] * jnp.abs(err)), epe


def forward(cfg, image1, image2, test_mode: bool, train: bool,
            freeze_bn: bool, loss_targets):
    """The body of ``RAFT.__call__`` for arch 'gmflow'; called inside its
    compact method, so the modules below are the model's top-level scopes
    (``backbone``, ``transformer``, ``feature_flow_attn``, ``upsampler``:
    the public checkpoint's names).  Returns what the other architectures
    return: ``(flow_low, flow_up)`` in ``test_mode``, ``(per_prediction
    (2,), metrics)`` with ``loss_targets``, else the two full-resolution
    predictions stacked ``(2, B, H, W, 2)``."""
    dt = cfg.dtype
    mean = jnp.asarray(IMAGENET_MEAN, jnp.float32)
    std = jnp.asarray(IMAGENET_STD, jnp.float32)
    B = image1.shape[0]
    both = jnp.concatenate([image1, image2], axis=0).astype(jnp.float32)
    both = (both / 255.0 - mean) / std
    with jax.named_scope("gmflow_backbone"):
        x = BasicEncoder(CHANNELS, "instance", cfg.dropout, dt,
                         name="backbone")(both.astype(dt), train, freeze_bn)
    _, h, w, C = x.shape
    if h % SPLITS or w % SPLITS:
        raise ValueError(
            f"arch 'gmflow' splits its 1/8 map into {SPLITS}x{SPLITS} "
            f"windows: H/8 x W/8 = {h}x{w} must be even; pad the images "
            f"to a multiple of {cfg.pad_multiple} "
            "(RAFTConfig.pad_multiple)")
    # one window's position, added to every window of both maps
    pos = np.tile(sine_position(h // SPLITS, w // SPLITS, C),
                  (SPLITS, SPLITS, 1))
    x = (x.astype(jnp.float32) + pos).reshape(2 * B, h * w, C)
    # RAFTConfig.remat / remat_policy, read for a model with no scan body
    # to apply them to: 'full' rebuilds every block, the other policies
    # (the CLIs' default 'save_corr' among them) only what is large and
    # cheap to rebuild, the score matrices XLA would keep (the kernels of
    # ops/pallas_attention.py keep none: window_attention)
    recompute = ("none" if not (cfg.remat and train) else
                 "block" if cfg.remat_policy == "full" else "scores")
    x = FeatureTransformer(dt, recompute, name="transformer")(x, h, w)
    x = x.reshape(2 * B, h, w, C)
    f1, f2 = x[:B], x[B:]

    flow = global_match(f1, f2, cfg.resolved_corr_precision)
    flow_low = FlowPropagation(dt, name="feature_flow_attn")(
        f1, _detached(flow))
    mask = Upsampler(dt, name="upsampler")(flow_low, f1)

    if test_mode:
        return flow_low, convex_upsample(flow_low, mask.astype(jnp.float32))
    if loss_targets is None:
        return jnp.stack([upflow8(flow), convex_upsample(
            flow_low, mask.astype(jnp.float32))])

    from raft_tpu.train.loss import combined_valid

    flow_gt, valid, max_flow = loss_targets
    flow_gt = flow_gt.astype(jnp.float32)
    vmask = combined_valid(flow_gt, valid, max_flow)
    n_valid = jnp.maximum(jnp.sum(vmask), 1.0)
    loss0, epe0 = _l1_term(upflow8(flow), flow_gt, vmask)
    # the convex combination in space-to-depth layout, compared there with
    # the ground truth in float32 (UpsampleLossStep has why)
    out = convex_upsample_flat(
        flow_low, mask, compute_dtype=jnp.dtype(cfg.resolved_upsample_dtype)
    ).astype(jnp.float32)
    gt128 = space_to_depth_flow(flow_gt)
    vm = space_to_depth_flow(vmask[..., None])
    dx, dy = out[..., :64] - gt128[..., :64], out[..., 64:] - gt128[..., 64:]
    loss1 = jnp.sum(vm * (jnp.abs(dx) + jnp.abs(dy))) / gt128.size
    dx, dy = jax.lax.stop_gradient(dx), jax.lax.stop_gradient(dy)
    epe = jnp.sqrt(dx * dx + dy * dy)
    metrics = {"epe": jnp.sum(vm * epe) / n_valid,
               "epe_iter": jnp.stack([epe0, jnp.sum(vm * epe)]) / n_valid}
    for name, px in (("1px", 1.0), ("3px", 3.0), ("5px", 5.0)):
        metrics[name] = jnp.sum(vm * (epe < px)) / n_valid
    return jnp.stack([loss0, loss1]), metrics
