"""GMFlow (Xu et al., CVPR 2022, arXiv:2111.13680) and UniMatch's flow
model (Xu et al., TPAMI 2023, arXiv:2211.05783; PAPERS.md has the
equations of both): the body :class:`raft_tpu.models.raft.RAFT` runs for
``arch='gmflow'`` and ``arch='unimatch'``.

Arch 'gmflow', one scale.  No hidden state, no pyramid, no lookup and no
loop.  Both 1/8 feature maps (RAFT's :class:`BasicEncoder`, 128 wide) get
one window's sine position, pass through six Transformer blocks of
shifted-window self- and cross-attention, and are matched globally: one
softmax over the whole ``N x N`` correlation volume (RAFT's own all-pairs
product, ``ops/corr.py``) whose expectation over the pixel grid is the
flow.  One global self-attention over image 1's features propagates that
flow, and RAFT's convex combination (``ops/upsample.py``) brings it to
full resolution.  Training makes two predictions: the matched flow
upsampled bilinearly, and the propagated one upsampled convexly, whose
gradient stops at the matched flow.

Arch 'unimatch', two scales and a refinement (``RAFTConfig.attn_splits``
``(2, 8)``, ``local_radii`` ``((-1, -1), (4, 1))``).  The backbone's
third stage keeps 1/4 and one trident 3x3 weight gives the 1/8 and the
1/4 maps.  Scale 1 is GMFlow's.  At scale 2 the flow is upsampled x2 and
stopped, image 2's 1/4 features are warped by it, the SAME Transformer
(its weights, 8x8 windows) runs over both maps, a softmax over the 9x9
neighbours adds a residual flow (local match), and a softmax over the
3x3 neighbours of image 1's keys propagates it (local propagation).
Then ``iters`` refinements at 1/4 under ``scan``: a lookup of radius 4 in
the one-level volume of the backbone's 1/4 maps around ``x + flow``, RAFT's
motion encoder, GRU and flow head from a hidden state projected from
scale 2's Transformer output (the same every iteration: nothing but the
flow crosses one), and a convex x4 upsampling with a mask of 144
channels.  Training makes ``4 + iters`` predictions: two a scale
upsampled bilinearly, one a refinement upsampled convexly.

Precision follows ``RAFTConfig.compute_dtype``: the backbone, the
projections, the FFN, ``P v``, the update block and the upsampler's
convolutions run in it; every softmax with its scores, the LayerNorms,
the residual stream between the Transformer's layers, the correlation,
the grid and the flow are float32.

Each part traces under a ``jax.named_scope``: ``gmflow_backbone``,
``gmflow_self_attn``, ``gmflow_cross_attn``, ``gmflow_ffn``,
``gmflow_match``, ``gmflow_propagate``, and for 'unimatch' besides
``gmflow_warp``, ``gmflow_local_match``, ``gmflow_local_propagate``,
``gmflow_refine`` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from jax.ad_checkpoint import checkpoint_name

from raft_tpu.models.extractor import BasicEncoder
from raft_tpu.models.layers import conv
from raft_tpu.models.update import BasicUpdateBlock, MaskHead
from raft_tpu.ops import pallas_attention
from raft_tpu.ops.corr import all_pairs_correlation
from raft_tpu.ops.sampler import (bilinear_sampler, coords_grid,
                                  resize_bilinear_align_corners, upflow8)
from raft_tpu.ops.upsample import (convex_upsample, convex_upsample_flat,
                                   space_to_depth_flow)
from raft_tpu.parallel.mesh import image_rows_split

CHANNELS = 128          # feature_channels
LAYERS = 6              # num_transformer_layers
SPLITS = 2              # K x K windows at 1/8 (RAFTConfig.attn_splits)
FFN_EXPANSION = 4       # ffn_dim_expansion, over the 2C-wide [s, m]
MASK_VALUE = -100.0     # between tokens of different regions
OUTSIDE = -1e9          # a local match's candidate outside the map
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


def window_attention_path(h: int, w: int, channels: int, dtype,
                          splits: int = SPLITS) -> str:
    """``'mosaic'`` or ``'xla'``: which :func:`window_attention` a program
    traced now holds for an ``(h, w)`` map split into ``splits x splits``
    windows, by
    ``ops.pallas_attention.window_attention_path`` from what this process
    can observe (the backend, whether image rows are split over devices,
    the window's shape)."""
    return pallas_attention.window_attention_path(
        jax.default_backend(), h // splits, w // splits, channels,
        jnp.dtype(dtype).itemsize, rows_split=image_rows_split())


def window_attention(q, k, v, h: int, w: int, shift: bool, dtype,
                     recompute: bool = False,
                     splits: int = SPLITS) -> jax.Array:
    """Single-head attention inside each of the K x K windows of an ``(h,
    w)`` map, K = ``splits``: ``q, k, v`` are ``(B, h * w, C)``; with
    ``shift`` the maps are rolled by half a window first and back after,
    and tokens of different regions of a rolled window are masked off
    each other.
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
    sh, sw = h // (2 * splits), w // (2 * splits)
    if window_attention_path(h, w, C, dtype, splits) == "mosaic":
        out = pallas_attention.window_attention(
            *(x.reshape(B, h, w, C) for x in (q, k, v)), splits,
            (sh, sw) if shift else None,
            shift_regions(h, w, splits) if shift else None, MASK_VALUE)
        return out.reshape(B, h * w, C)
    if recompute:
        return jax.checkpoint(
            lambda q, k, v: window_attention(q, k, v, h, w, shift, dtype,
                                             splits=splits))(q, k, v)

    def windows(x):
        x = x.reshape(B, h, w, C)
        if shift:
            x = jnp.roll(x, (-sh, -sw), axis=(1, 2))
        x = split_windows(x, splits)
        return x.reshape(x.shape[0], -1, C)

    q, k, v = windows(q), windows(k), windows(v)
    scores = jnp.einsum("bnc,bmc->bnm", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / float(C) ** 0.5)
    if shift:
        n = scores.shape[-1]
        scores = (scores.reshape(B, splits * splits, n, n)
                  + shift_mask(h, w, splits)).reshape(-1, n, n)
    prob = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bnm,bmc->bnc", prob, v,
                     preferred_element_type=jnp.float32).astype(dtype)
    out = merge_windows(out.reshape(-1, h // splits, w // splits, C),
                        splits)
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
    def __call__(self, source, target, h: int, w: int, splits: int):
        dt, C = self.dtype, source.shape[-1]
        s, t = source.astype(dt), target.astype(dt)
        with jax.named_scope("gmflow_cross_attn" if self.ffn
                             else "gmflow_self_attn"):
            q = _linear(C, dt, "q_proj")(s)
            k = _linear(C, dt, "k_proj")(t)
            v = _linear(C, dt, "v_proj")(t)
            m = window_attention(q, k, v, h, w, self.shift, dt,
                                 self.recompute_scores, splits)
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
    def __call__(self, x, h: int, w: int, splits: int):
        # x: [F1; F2] on the batch; the target is the other image's map,
        # re-formed from x after the self-attention of this block
        x = TransformerLayer(False, self.shift, self.dtype,
                             self.recompute_scores,
                             name="self_attn")(x, x, h, w, splits)
        B = x.shape[0] // 2
        other = jnp.concatenate([x[B:], x[:B]], axis=0)
        return TransformerLayer(True, self.shift, self.dtype,
                                self.recompute_scores,
                                name="cross_attn_ffn")(x, other, h, w,
                                                       splits)


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
    def __call__(self, x, h: int, w: int, splits: int = SPLITS):
        """One module, one set of weights, called at each scale with that
        scale's map and windows (``splits``)."""
        block = TransformerBlock
        if self.recompute == "block":
            block = nn.remat(TransformerBlock, static_argnums=(2, 3, 4))
        for i in range(LAYERS):
            x = block(i % 2 == 1, self.dtype, self.recompute == "scores",
                      name=f"layers_{i}")(x, h, w, splits)
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


def _neighbours(x: jax.Array, radius: int, fill: float = 0.0):
    """The ``(2r+1)^2`` shifts of an ``(B, h, w, c)`` map, y-major (the
    window grid's order): shift ``(dy, dx)`` holds ``x[y + dy, x + dx]``,
    ``fill`` where that lies outside.  A generator of slices of one padded
    map: no ``(B, h, w, (2r+1)^2, c)`` array is built."""
    _, h, w, _ = x.shape
    r = radius
    xp = jnp.pad(x, ((0, 0), (r, r), (r, r), (0, 0)), constant_values=fill)
    for dy in range(2 * r + 1):
        for dx in range(2 * r + 1):
            yield (dy - r, dx - r), xp[:, dy:dy + h, dx:dx + w]


def local_match(f1: jax.Array, f2: jax.Array, radius: int) -> jax.Array:
    """The residual flow of a softmax over the ``(2r+1)^2`` integer
    neighbours of each position of image 2's (warped) map: ``c_d(x) =
    F1(x) . F2(x + d) / sqrt(C)``, ``OUTSIDE`` where ``x + d`` lies off
    the map, and ``sum_d softmax_d(c) d``.  Float32; each neighbour is a
    shifted slice contracted over the channels.  ``(B, h, w, 2)``."""
    _, h, w, C = f1.shape
    with jax.named_scope("gmflow_local_match"):
        a = f1.astype(jnp.float32)
        inside = jnp.ones((1, h, w, 1), jnp.float32)
        scores, offsets = [], []
        for (dy, dx), b in _neighbours(f2.astype(jnp.float32), radius):
            scores.append(jnp.sum(a * b, axis=-1))
            offsets.append((dx, dy))
        mask = jnp.stack([m[..., 0] for _, m in
                          _neighbours(inside, radius)], axis=-1)
        scores = jnp.stack(scores, axis=-1) * (1.0 / float(C) ** 0.5)
        scores = jnp.where(mask > 0, scores, OUTSIDE)
        prob = jax.nn.softmax(scores, axis=-1)
        d = jnp.asarray(offsets, jnp.float32)
        return jnp.einsum("bhwk,kc->bhwc", prob, d,
                          precision=jax.lax.Precision.HIGHEST)


class FlowPropagation(nn.Module):
    """A self-attention over image 1's features that carries the flow,
    ``q = F1 U_q + b_q``.  Global (``radius`` -1): ``k = q U_k + b_k``
    (the key is projected from the projected query, as the public code
    has it), ``softmax(q k^T / sqrt(C)) flow``.  Local (``radius`` r > 0,
    arch 'unimatch' at 1/4): ``k = F1 U_k + b_k`` and, over the
    ``(2r+1)^2`` neighbours ``d`` of each position, ``sum_d softmax_d(q(x)
    . k(x + d) / sqrt(C)) flow(x + d)``, with ``k`` and the flow zero off
    the map (those keys stay in the softmax, as an unfold pads).  One set
    of weights at every scale.  The flow and the softmax stay float32."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, f1, flow, radius: int = -1):
        B, h, w, C = f1.shape
        dt = self.dtype
        if radius > 0:
            return self._local(f1, flow, radius)
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

    def _local(self, f1, flow, radius):
        C, dt = f1.shape[-1], self.dtype
        with jax.named_scope("gmflow_local_propagate"):
            x = f1.astype(dt)
            q = _linear(C, dt, "q_proj", True)(x).astype(jnp.float32)
            k = _linear(C, dt, "k_proj", True)(x).astype(jnp.float32)
            scores = jnp.stack([jnp.sum(q * kd, axis=-1) for _, kd in
                                _neighbours(k, radius)], axis=-1)
            prob = jax.nn.softmax(scores * (1.0 / float(C) ** 0.5), axis=-1)
            flows = jnp.stack([fd for _, fd in _neighbours(flow, radius)],
                              axis=-2)
            return jnp.einsum("bhwk,bhwkc->bhwc", prob, flows,
                              precision=jax.lax.Precision.HIGHEST)


def warp(f2: jax.Array, flow: jax.Array) -> jax.Array:
    """Image 2's map sampled bilinearly at ``x + flow(x)``, zero off the
    map (``grid_sample`` with ``align_corners=True``)."""
    B, h, w, _ = f2.shape
    with jax.named_scope("gmflow_warp"):
        return bilinear_sampler(f2.astype(jnp.float32),
                                coords_grid(B, h, w) + flow)


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


def _lookup(cfg, corr_state, coords):
    """The refinement's correlation: the ``(2r+1)^2`` taps of the one-level
    volume around ``coords`` (``models/raft.py lookup_corr``, RAFT's
    lookup), in the window grid's y-major order (RAFT's come x-major)."""
    from raft_tpu.models.raft import lookup_corr

    B, h, w, _ = coords.shape
    taps = lookup_corr(cfg, corr_state, coords, cfg.dtype)
    k = 2 * cfg.corr_radius + 1
    return jnp.swapaxes(taps.reshape(B, h, w, k, k), 3, 4).reshape(
        B, h, w, k * k)


class RefineStep(nn.Module):
    """One regression refinement at 1/4 (the scan body): the lookup around
    ``x + flow``, RAFT's update block (motion encoder, ``SepConvGRU``, flow
    head) from the loop-invariant ``net`` and ``inp``, a mask of ``9 *
    4^2`` logits (no 0.25 in front).  Carries the flow alone; emits the new
    flow and its mask."""

    config: Any

    @nn.compact
    def __call__(self, flow, inputs):
        cfg = self.config
        dt = cfg.dtype
        net, inp, corr_state, coords0 = inputs
        with jax.named_scope("gmflow_refine"):
            flow = jax.lax.stop_gradient(flow)
            corr = _lookup(cfg, corr_state, coords0 + flow)
            corr = checkpoint_name(corr.astype(dt), "corr")
            net, delta = BasicUpdateBlock(cfg.hidden_dim, dt,
                                          name="update_block")(
                net, inp, corr, flow.astype(dt))
            mask = MaskHead(cfg.hidden_dim, dt, factor=4, scale=1.0,
                            name="mask_head")(net)
            flow = flow + delta.astype(jnp.float32)
        return flow, (flow, mask)


def _refine(cfg, iters, f1, fmaps, flow, train):
    """``iters`` refinements from ``flow`` at 1/4: ``[net, inp]`` projected
    once from scale 2's Transformer output ``f1`` (UniMatch computes the
    same projection in every iteration), the volume of the backbone's 1/4
    maps ``fmaps`` built once (in a training forward, ``train``, as
    ``models/raft.py _build_corr_state`` builds it for a differentiated
    lookup).  Returns the stacked flows and masks,
    ``(iters, B, h, w, 2)`` and ``(iters, B, h, w, 144)``."""
    from raft_tpu.models.raft import _build_corr_state, _remat_wrap

    if cfg.corr_dtype_is_quantized:
        raise ValueError(f"arch {cfg.arch!r} keeps its volume in the "
                         "compute type: a quantized corr_dtype is not "
                         "built for it")
    dt = cfg.dtype
    B, h, w, C = f1.shape
    with jax.named_scope("gmflow_refine"):
        proj = conv(2 * cfg.hidden_dim, 1, 1, dt, name="refine_proj",
                    torch_default_init=True, in_features=C)(f1.astype(dt))
        net = jnp.tanh(proj[..., :cfg.hidden_dim])
        inp = nn.relu(proj[..., cfg.hidden_dim:])
        corr_state = _build_corr_state(cfg, *fmaps, train)
    scan = nn.scan(_remat_wrap(RefineStep, cfg),
                   variable_broadcast="params",
                   split_rngs={"params": False, "dropout": True},
                   in_axes=nn.broadcast, out_axes=0, length=iters,
                   unroll=max(1, min(cfg.scan_unroll, iters)))(
                       cfg, name="refine")
    _, (flows, masks) = scan(flow, (net, inp, corr_state,
                                    coords_grid(B, h, w)))
    return flows, masks


def _matched_scales(cfg, x, B, dt, recompute):
    """The scale loop: at each scale (1/8, then 1/4 for 'unimatch') the
    position, the Transformer, the match and the propagation; from the
    second scale on the flow is upsampled x2 and stopped, and image 2's
    map warped by it first.  ``x``: the backbone's maps, coarsest first,
    ``[F1; F2]`` on the batch.  Returns ``(preds, flow, f1)``: the flows a
    bilinear upsampling supervises with their factor, the last propagated
    flow and the last Transformer output of image 1."""
    transformer = FeatureTransformer(dt, recompute, name="transformer")
    propagation = FlowPropagation(dt, name="feature_flow_attn")
    preds, flow, f1 = [], None, None
    for s, (splits, (match_r, prop_r)) in enumerate(
            zip(cfg.attn_splits, cfg.local_radii)):
        feat = x[s]
        _, h, w, C = feat.shape
        if h % splits or w % splits:
            raise ValueError(
                f"arch {cfg.arch!r} splits its 1/{8 >> s} map into "
                f"{splits}x{splits} windows: {h}x{w} is not a multiple of "
                f"{splits}; pad the images to a multiple of "
                f"{cfg.pad_multiple} (RAFTConfig.pad_multiple)")
        if flow is not None:
            flow = jax.lax.stop_gradient(
                2.0 * resize_bilinear_align_corners(flow, (h, w)))
            feat = jnp.concatenate(
                [feat[:B].astype(jnp.float32), warp(feat[B:], flow)], axis=0)
        # one window's position, added to every window of both maps
        pos = np.tile(sine_position(h // splits, w // splits, C),
                      (splits, splits, 1))
        feat = (feat.astype(jnp.float32) + pos).reshape(2 * B, h * w, C)
        feat = transformer(feat, h, w, splits).reshape(2 * B, h, w, C)
        f1, f2 = feat[:B], feat[B:]
        if match_r < 0:
            matched = global_match(f1, f2, cfg.resolved_corr_precision)
        else:
            matched = local_match(f1, f2, match_r)
        flow = matched if flow is None else flow + matched
        factor = 8 >> s
        preds.append((flow, factor))
        flow = propagation(f1, _detached(flow), prop_r)
        if cfg.refines:
            preds.append((flow, factor))
    return preds, flow, f1


def _bilinear_up(flow, factor):
    """``factor * up_factor(flow)`` (bilinear, corners aligned)."""
    if factor == 8:
        return upflow8(flow)
    _, h, w, _ = flow.shape
    return factor * resize_bilinear_align_corners(
        flow, (factor * h, factor * w))


def _convex_terms(cfg, flows, masks, flow_gt, vmask, factor):
    """The L1 term and the summed end-point error of each of a stack of
    convexly upsampled predictions, in space-to-depth layout at
    ``factor``, against the ground truth in float32 (``UpsampleLossStep``
    has why): ``flows`` ``(n, B, h, w, 2)``, ``masks`` their logits."""
    n, B, h, w, _ = flows.shape
    f2 = factor * factor
    out = convex_upsample_flat(
        flows.reshape(n * B, h, w, 2),
        masks.reshape(n * B, h, w, -1), factor,
        compute_dtype=jnp.dtype(cfg.resolved_upsample_dtype)
    ).astype(jnp.float32).reshape(n, B, h, w, 2 * f2)
    gt = space_to_depth_flow(flow_gt, factor)
    vm = space_to_depth_flow(vmask[..., None], factor)
    dx, dy = out[..., :f2] - gt[..., :f2], out[..., f2:] - gt[..., f2:]
    l1 = jnp.sum(vm * (jnp.abs(dx) + jnp.abs(dy)), axis=(1, 2, 3, 4)) \
        / gt.size
    dx, dy = jax.lax.stop_gradient(dx), jax.lax.stop_gradient(dy)
    return l1, jnp.sqrt(dx * dx + dy * dy), vm


def _refined_outputs(cfg, iters, preds, flow, f1, fmaps, test_mode,
                     loss_targets, train):
    """What arch 'unimatch' returns after its scales: the refinements, then
    ``(flow_low, flow_up)`` in test mode, the ``4 + iters`` predictions
    stacked, or their loss terms and metrics."""
    flows, masks = _refine(cfg, iters, f1, fmaps, flow, train)
    if test_mode:
        return flows[-1], convex_upsample(
            flows[-1], masks[-1].astype(jnp.float32), factor=4)
    if loss_targets is None:
        return jnp.stack([_bilinear_up(f, k) for f, k in preds]
                         + [convex_upsample(f, m.astype(jnp.float32),
                                            factor=4)
                            for f, m in zip(flows, masks)])

    from raft_tpu.train.loss import combined_valid

    flow_gt, valid, max_flow = loss_targets
    flow_gt = flow_gt.astype(jnp.float32)
    vmask = combined_valid(flow_gt, valid, max_flow)
    n_valid = jnp.maximum(jnp.sum(vmask), 1.0)
    terms, epes = [], []
    for f, k in preds:
        term, epe = _l1_term(_bilinear_up(f, k), flow_gt, vmask)
        terms.append(term)
        epes.append(epe)
    l1, epe, vm = _convex_terms(cfg, flows, masks, flow_gt, vmask, 4)
    epe_iter = jnp.concatenate([jnp.stack(epes),
                                jnp.sum(vm * epe, axis=(1, 2, 3, 4))])
    metrics = {"epe": epe_iter[-1] / n_valid, "epe_iter": epe_iter / n_valid}
    for name, px in (("1px", 1.0), ("3px", 3.0), ("5px", 5.0)):
        metrics[name] = jnp.sum(vm * (epe[-1] < px)) / n_valid
    return jnp.concatenate([jnp.stack(terms), l1]), metrics


def forward(cfg, image1, image2, test_mode: bool, train: bool,
            freeze_bn: bool, loss_targets, iters: int = 0):
    """The body of ``RAFT.__call__`` for archs 'gmflow' and 'unimatch';
    called inside its compact method, so the modules below are the model's
    top-level scopes (``backbone``, ``transformer``, ``feature_flow_attn``,
    then ``upsampler`` or ``refine_proj`` and ``refine``: the public
    checkpoint's names).  Returns what the other architectures return:
    ``(flow_low, flow_up)`` in ``test_mode``, ``(per_prediction (n,),
    metrics)`` with ``loss_targets``, else the full-resolution predictions
    stacked ``(n, B, H, W, 2)``; ``n`` is 2 for 'gmflow' (which does not
    read ``iters``) and ``4 + iters`` for 'unimatch'."""
    dt = cfg.dtype
    mean = jnp.asarray(IMAGENET_MEAN, jnp.float32)
    std = jnp.asarray(IMAGENET_STD, jnp.float32)
    B = image1.shape[0]
    both = jnp.concatenate([image1, image2], axis=0).astype(jnp.float32)
    both = (both / 255.0 - mean) / std
    with jax.named_scope("gmflow_backbone"):
        x = BasicEncoder(CHANNELS, "instance", cfg.dropout, dt,
                         num_output_scales=cfg.num_scales,
                         name="backbone")(both.astype(dt), train, freeze_bn)
    x = x if isinstance(x, tuple) else (x,)
    # RAFTConfig.remat / remat_policy, read for the Transformer, which has
    # no scan body to apply them to: 'full' rebuilds every block, the
    # other policies (the CLIs' default 'save_corr' among them) only what
    # is large and cheap to rebuild, the score matrices XLA would keep
    # (the kernels of ops/pallas_attention.py keep none: window_attention)
    recompute = ("none" if not (cfg.remat and train) else
                 "block" if cfg.remat_policy == "full" else "scores")
    preds, flow_low, f1 = _matched_scales(cfg, x, B, dt, recompute)
    if cfg.refines:
        fmaps = (x[-1][:B], x[-1][B:])
        return _refined_outputs(cfg, iters, preds, flow_low, f1, fmaps,
                                test_mode, loss_targets, train)
    (flow, _), = preds
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
