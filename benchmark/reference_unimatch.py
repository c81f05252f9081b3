"""Plain UniMatch flow model, GMFlow at two scales with six regression
refinements (Xu, Zhang, Cai, Rezatofighi, Yu, Tao, Geiger, TPAMI 2023,
arXiv:2211.05783; PAPERS.md has the equations and the details written from
memory of the public code) in ``jax.numpy`` float32.

UniMatch's own operations, step by step, and none of the program's: a
``grid_sample`` (bilinear, zero padding, corners aligned) built from four
gathers; the warp, the local match and the refinement's correlation as
explicit gathers of image 2's features at each of the window's taps; the
local propagation over an unfold with zero padding; the window grid's
y-major tap order.  No correlation volume and no lookup: the refinement's
correlation is ``F1(x) . bil(F2, x + flow + d) / sqrt(C)`` for each of the
81 offsets ``d``.

Built from ``benchmark/reference.py``'s blocks (``conv``, ``norm``, the
update block, the optimiser) and ``benchmark/reference_gmflow.py``'s
(the sine position, the window split, merge and mask, the global match and
propagation); imports nothing of ``raft_tpu``.  The functions
``kinds/train_arch.py`` calls (``train_steps``, ``QUANTS``) have
``reference.py``'s signatures; ``iters`` is the number of refinements.

``quant`` is the controls' hook, as in ``reference_gmflow.py``: a rounding
goes round every product (the convolutions, the ``Linear``s, the window
attentions' two products, the global correlation and propagation's
products, the local dot products, the refinement's correlation).
``drop_aggregate`` plants the fault "the Transformer's messages left out".
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_gmflow as gm
from benchmark.reference import (_interp, adamw_step, conv, highest, norm,
                                 onecycle, quantised, update_block)
from benchmark.reference_gmflow import (_product, _rounded, _site,
                                        layer_norm, linear, match, merge,
                                        propagate, shift_mask, sine_position,
                                        split)

QUANTS = gm.QUANTS
MEAN, STD = gm.MEAN, gm.STD
SPLITS = (2, 8)
MATCH_RADII = (-1, 4)
PROP_RADII = (-1, 1)
REFINE_RADIUS = 4


# --------------------------------------------------------------------------
# sampling
# --------------------------------------------------------------------------

def grid_sample(img, coords):
    """``F.grid_sample(img, coords normalised, align_corners=True,
    padding_mode='zeros')`` at pixel coordinates: ``img`` ``(B, h, w, C)``,
    ``coords`` ``(B, ..., 2)`` as ``(x, y)``; each of the four corners is a
    gather, zero where it lies off the map."""
    B, h, w, C = img.shape
    flat = img.reshape(B, h * w, C)
    x, y = coords[..., 0], coords[..., 1]
    x0, y0 = jnp.floor(x), jnp.floor(y)
    out = 0.0
    for cx, cy in ((0, 0), (1, 0), (0, 1), (1, 1)):
        ix, iy = x0 + cx, y0 + cy
        wgt = (1.0 - jnp.abs(x - ix)) * (1.0 - jnp.abs(y - iy))
        inside = (ix >= 0) & (ix <= w - 1) & (iy >= 0) & (iy <= h - 1)
        idx = (jnp.clip(iy, 0, h - 1) * w + jnp.clip(ix, 0, w - 1)).astype(
            jnp.int32).reshape(B, -1)
        v = jnp.take_along_axis(flat, idx[..., None], axis=1)
        v = v.reshape(coords.shape[:-1] + (C,))
        out = out + jnp.where(inside, wgt, 0.0)[..., None] * v
    return out


def window_grid(radius):
    """UniMatch's ``generate_window_grid`` flattened: ``((2r+1)^2, 2)``
    offsets ``(dx, dy)``, y-major (``dx`` runs fastest)."""
    r = np.arange(-radius, radius + 1, dtype=np.float32)
    dy, dx = np.meshgrid(r, r, indexing="ij")
    return np.stack([dx.ravel(), dy.ravel()], -1)


def pixel_grid(B, h, w):
    xx, yy = jnp.meshgrid(jnp.arange(w, dtype=jnp.float32),
                          jnp.arange(h, dtype=jnp.float32))
    return jnp.broadcast_to(jnp.stack([xx, yy], -1)[None], (B, h, w, 2))


def sampled_dots(f1, f2, coords, radius, quant=None):
    """``F1(x) . grid_sample(F2, coords(x) + d) / sqrt(C)`` for each window
    offset ``d``, y-major: ``(B, h, w, (2r+1)^2)``.  One tap at a time (a
    ``scan`` over the offsets), so that no ``(B, N, taps, C)`` array is
    built; each tap is recomputed in the backward pass, so that what a
    tap gathers is held for one tap at a time."""
    C = f1.shape[-1]
    dot = _product("bhwc,bhwc->bhw", quant)

    @jax.checkpoint
    def tap(_, d):
        return None, dot(f1, grid_sample(f2, coords + d))

    _, out = jax.lax.scan(tap, None, jnp.asarray(window_grid(radius)))
    return jnp.moveaxis(out, 0, -1) / math.sqrt(C)


def upsample(flow, factor):
    """``factor * F.interpolate(flow, scale_factor=factor, 'bilinear',
    align_corners=True)``."""
    _, H, W, _ = flow.shape
    y = jnp.einsum("ih,bhwc->biwc", _interp(H, factor * H), flow)
    return factor * jnp.einsum("jw,biwc->bijc", _interp(W, factor * W), y)


def resize(flow, hw):
    """``F.interpolate(flow, size=hw, 'bilinear', align_corners=True)``."""
    _, H, W, _ = flow.shape
    y = jnp.einsum("ih,bhwc->biwc", _interp(H, hw[0]), flow)
    return jnp.einsum("jw,biwc->bijc", _interp(W, hw[1]), y)


# --------------------------------------------------------------------------
# backbone, Transformer
# --------------------------------------------------------------------------

def residual_block(x, p, stride, quant):
    """UniMatch's ``ResidualBlock`` with instance norm: a downsample
    convolution wherever the stride or the width changes."""
    y = jax.nn.relu(norm(conv(x, p["conv1"], stride, quant), "instance",
                         {}, {}, True))
    y = jax.nn.relu(norm(conv(y, p["conv2"], 1, quant), "instance", {}, {},
                         True))
    if stride != 1 or x.shape[-1] != y.shape[-1]:
        x = norm(conv(x, p["downsample_conv"], stride, quant), "instance",
                 {}, {}, True)
    return jax.nn.relu(x + y)


def backbone(x, p, quant=None, remat=False):
    """``CNNEncoder(num_output_scales=2)``: 7x7/2 stem, stages of (64, 1),
    (96, 2) and (128, 1): the third keeps 1/4; a 1x1 projection; the trident
    3x3 weight (no bias) at stride 2 and at stride 1 -> ``(1/8, 1/4)``."""
    q = _site(quant)

    def wrap(f):
        return jax.checkpoint(f, static_argnums=(2,)) if remat else f

    x = jax.nn.relu(norm(conv(x, p["conv1"], 2, q), "instance", {}, {},
                         True))
    for i, stride in enumerate((1, 1, 2, 1, 1, 1)):
        x = wrap(lambda x, pp, s: residual_block(x, pp, s, q))(
            x, p[f"layer{i // 2 + 1}_{i % 2}"], stride)
    x = conv(x, p["conv2"], 1, q)
    k = p["trident_conv"]["kernel"]

    def trident(stride):
        return quantised(lambda a, b: jax.lax.conv_general_dilated(
            a, b, (stride, stride), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC")), q)(x, k)

    return trident(2), trident(1)


def window_attention(q, k, v, h, w, shift, splits, quant=None):
    """``softmax(q k^T / sqrt(C) + M) v`` inside each of the ``splits x
    splits`` windows; ``q, k, v``: ``(B, h*w, C)``."""
    B, _, C = q.shape
    sh, sw = h // (2 * splits), w // (2 * splits)

    def windows(x):
        x = x.reshape(B, h, w, C)
        if shift:
            x = jnp.roll(x, (-sh, -sw), (1, 2))
        return split(x, splits).reshape(B * splits * splits, -1, C)

    q, k, v = windows(q), windows(k), windows(v)
    scores = _product("bnc,bmc->bnm", quant)(q, k) / math.sqrt(C)
    scores = _rounded(scores, _site(quant, "scores"))
    if shift:
        scores = scores + jnp.tile(shift_mask(h, w, splits), (B, 1, 1))
    out = _product("bnm,bmc->bnc", quant)(jax.nn.softmax(scores, -1), v)
    out = merge(out.reshape(-1, h // splits, w // splits, C), splits)
    if shift:
        out = jnp.roll(out, (sh, sw), (1, 2))
    return out.reshape(B, h * w, C)


def layer(p, s, t, h, w, shift, splits, quant=None, drop=False):
    if drop:
        return s
    m = window_attention(linear(s, p["q_proj"], quant),
                         linear(t, p["k_proj"], quant),
                         linear(t, p["v_proj"], quant), h, w, shift, splits,
                         quant)
    m = layer_norm(linear(m, p["merge"], quant), p["norm1"])
    if "mlp_0" in p:
        m = linear(jnp.concatenate([s, m], -1), p["mlp_0"], quant)
        m = linear(jax.nn.gelu(m, approximate=False), p["mlp_2"], quant)
        m = layer_norm(m, p["norm2"])
    return s + m


def transformer(p, x, h, w, splits, quant=None, drop=False, remat=False):
    B = x.shape[0] // 2

    def block(p, x, shift):
        x = layer(p["self_attn"], x, x, h, w, shift, splits, quant, drop)
        other = jnp.concatenate([x[B:], x[:B]], 0)
        return layer(p["cross_attn_ffn"], x, other, h, w, shift, splits,
                     quant, drop)

    for i in range(6):
        f = jax.checkpoint(block, static_argnums=(2,)) if remat else block
        x = f(p[f"layers_{i}"], x, i % 2 == 1)
    return x


# --------------------------------------------------------------------------
# local matching, local propagation, refinement
# --------------------------------------------------------------------------

def local_match(f1, f2, radius, quant=None):
    """``local_correlation_softmax``: candidates at integer offsets, those
    off the map at -1e9, the expected offset."""
    B, h, w, _ = f1.shape
    g = pixel_grid(B, h, w)
    d = jnp.asarray(window_grid(radius))
    corr = sampled_dots(f1, f2, g, radius, quant)
    cand = g[..., None, :] + d
    inside = ((cand[..., 0] >= 0) & (cand[..., 0] < w)
              & (cand[..., 1] >= 0) & (cand[..., 1] < h))
    prob = jax.nn.softmax(jnp.where(inside, corr, -1e9), -1)
    return jnp.einsum("bhwk,bhwkc->bhwc", prob, cand) - g


def unfold(x, radius):
    """``F.unfold(x, 2r+1, padding=r)`` as ``(B, h, w, (2r+1)^2, c)``."""
    _, h, w, _ = x.shape
    k = 2 * radius + 1
    xp = jnp.pad(x, ((0, 0), (radius, radius), (radius, radius), (0, 0)))
    return jnp.stack([xp[:, i:i + h, j:j + w] for i in range(k)
                      for j in range(k)], axis=3)


def local_propagate(p, f1, flow, radius, quant=None):
    """``forward_local_window_attn``: ``q`` and ``k`` both projected from
    image 1's features, the keys and the flow unfolded with zero padding."""
    B, h, w, C = f1.shape
    q = linear(f1, p["q_proj"], quant)
    k = unfold(linear(f1, p["k_proj"], quant), radius)
    s = _product("bhwc,bhwkc->bhwk", quant)(q, k) / math.sqrt(C)
    return jnp.einsum("bhwk,bhwkc->bhwc", jax.nn.softmax(s, -1),
                      unfold(flow, radius))


def convex_upsample4(logits, flow):
    """``upsample_flow_with_mask`` at factor 4: a softmax over the 9 taps
    of the unfold of ``4 * flow``."""
    B, h, w, _ = flow.shape
    m = jax.nn.softmax(logits.reshape(B, h, w, 9, 4, 4), axis=3)
    nb = unfold(4.0 * flow, 1)
    return jnp.einsum("bhwkpq,bhwkc->bhpwqc", m, nb).reshape(
        B, 4 * h, 4 * w, 2)


def refine(p, f1_t, f1, f2, flow, iters, quant=None, remat=False):
    """The ``iters`` regression refinements from the 1/4 ``flow``:
    correlation at radius 4 around ``x + flow`` from the backbone's maps,
    ``[net, inp]`` from scale 2's Transformer output ``f1_t`` (the same in
    every iteration), RAFT's update block, the mask head, convex x4."""
    q = _site(quant)
    B, h, w, _ = f1.shape
    g = pixel_grid(B, h, w)
    proj = conv(f1_t, p["refine_proj"], 1, q)
    net, inp = jnp.tanh(proj[..., :128]), jax.nn.relu(proj[..., 128:])
    pr = p["refine"]

    def body(flow, _):
        flow = jax.lax.stop_gradient(flow)
        corr = sampled_dots(f1, f2, g + flow, REFINE_RADIUS, quant)
        h2, d = update_block(pr["update_block"], net, inp, corr, flow,
                             False, q)
        mh = pr["mask_head"]
        logits = conv(jax.nn.relu(conv(h2, mh["mask_conv1"], 1, q)),
                      mh["mask_conv2"], 1, q)
        flow = flow + d
        return flow, convex_upsample4(logits, flow)

    if remat:
        body = jax.checkpoint(body)
    _, ups = jax.lax.scan(body, flow, None, length=iters)
    return ups


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def forward(cfg, variables, image1, image2, iters=6, train=False, quant=None,
            remat=False, drop_aggregate=False, both=False):
    """-> the last full-resolution flow ``(B, H, W, 2)``; with ``both`` the
    ``4 + iters`` predictions training is supervised on, ``(4 + iters, B,
    H, W, 2)``."""
    p = variables["params"]
    C = int(cfg["feature_channels"])
    B = image1.shape[0]
    x = jnp.concatenate([image1, image2], 0).astype(jnp.float32) / 255.0
    x = (x - jnp.asarray(MEAN)) / jnp.asarray(STD)
    feats = backbone(x, p["backbone"], quant, remat)
    preds, flow = [], None
    for s, feat in enumerate(feats):
        k = SPLITS[s]
        _, h, w, _ = feat.shape
        f1o, f2o = feat[:B], feat[B:]
        f1, f2 = f1o, f2o
        if flow is not None:
            flow = jax.lax.stop_gradient(2.0 * resize(flow, (h, w)))
            f2 = grid_sample(f2o, pixel_grid(B, h, w) + flow)
        pos = sine_position(h // k, w // k, C)
        f1 = merge(split(f1, k) + pos, k)
        f2 = merge(split(f2, k) + pos, k)
        t = transformer(p["transformer"],
                        jnp.concatenate([f1, f2], 0).reshape(2 * B, h * w, C),
                        h, w, k, quant, drop_aggregate, remat)
        t = t.reshape(2 * B, h, w, C)
        f1, f2 = t[:B], t[B:]
        if MATCH_RADII[s] < 0:
            m = match(f1, f2, quant)
        else:
            m = local_match(f1, f2, MATCH_RADII[s], quant)
        flow = m if flow is None else flow + m
        factor = 8 >> s
        preds.append(upsample(flow, factor))
        fp = p["feature_flow_attn"]
        if PROP_RADII[s] < 0:
            flow = propagate(fp, f1, jax.lax.stop_gradient(flow), quant)
        else:
            flow = local_propagate(fp, f1, jax.lax.stop_gradient(flow),
                                   PROP_RADII[s], quant)
        preds.append(upsample(flow, factor))
    ups = refine(p, f1, f1o, f2o, flow, iters, quant, remat)
    return jnp.concatenate([jnp.stack(preds), ups]) if both else ups[-1]


def sequence_loss(cfg, variables, batch, iters, gamma=0.9, max_flow=400.0,
                  quant=None, drop_aggregate=False):
    """``flow_loss_func``: ``sum_i gamma^(n - 1 - i) mean(valid * |pred_i -
    gt|)`` over the ``n = 4 + iters`` predictions."""
    gt, valid = batch["flow"], batch["valid"]
    mag = jnp.sqrt(jnp.sum(gt ** 2, -1))
    v = ((valid > 0.5) & (mag < max_flow)).astype(jnp.float32)[..., None]
    preds = forward(cfg, variables, batch["image1"], batch["image2"], iters,
                    train=True, quant=quant, remat=True,
                    drop_aggregate=drop_aggregate, both=True)
    n = preds.shape[0]
    terms = jnp.mean(v * jnp.abs(preds - gt), axis=(1, 2, 3, 4))
    return jnp.sum(gamma ** (n - 1.0 - jnp.arange(n)) * terms)


def train_steps(cfg, variables, batches, iters, lr, num_steps, quant=None,
                block=4, seconds=None, drop_aggregate=False):
    """Follow the first ``len(batches)`` steps from ``variables``; returns
    and arguments as ``reference.train_steps``.  The batch runs in blocks of
    ``block`` rows (no norm spans the batch and the loss is a mean over
    rows, so equal blocks average)."""
    params = variables["params"]
    gamma = float(cfg.get("gamma", 0.9))

    @jax.jit
    def blk(p, b):
        return jax.value_and_grad(lambda p: sequence_loss(
            cfg, {"params": p}, b, iters, gamma, quant=quant,
            drop_aggregate=drop_aggregate))(p)

    upd = jax.jit(adamw_step)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu, losses, g1 = zeros, zeros, [], None
    with highest():
        for k, batch in enumerate(batches):
            t = time.perf_counter()
            batch = {n: np.asarray(v, np.float32) for n, v in batch.items()}
            B = batch["image1"].shape[0]
            rows = min(block, B)
            if B % rows:
                raise ValueError(f"block {rows} does not divide the batch "
                                 f"{B}")
            nb = B // rows
            loss, grads = 0.0, None
            for i in range(nb):
                r = slice(i * rows, (i + 1) * rows)
                l, g = blk(params, {n: v[r] for n, v in batch.items()})
                loss = loss + l / nb
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g)
            grads = jax.tree_util.tree_map(lambda x: x / nb, grads)
            params, mu, nu, g = upd(params, grads, mu, nu, float(k),
                                    onecycle(float(k), lr, num_steps))
            losses.append(float(loss))
            if seconds is not None:
                seconds.append(time.perf_counter() - t)
            if k == 0:
                g1 = g
    return losses, g1, params
