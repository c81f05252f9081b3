"""Plain GMFlow at one scale (Xu, Zhang, Cai, Rezatofighi, Tao, CVPR 2022,
arXiv:2111.13680; PAPERS.md has the equations and every departure) in
``jax.numpy`` float32: RAFT's residual encoder 128 wide, one window's sine
position, six Transformer blocks of shifted-window self- and cross-attention
over both feature maps, one softmax over the whole correlation volume, a
self-attention that propagates the flow, a convex upsampling, and a
gamma-weighted L1 over two predictions.

Built from ``benchmark/reference.py``'s blocks (``encoder``, ``upflow8``,
the optimiser); imports nothing of ``raft_tpu``.  The functions
``kinds/train_arch.py`` calls (``train_steps``, ``QUANTS``) have
``reference.py``'s signatures; the model has no iterations, so ``iters`` is
accepted and not read.

``quant`` is the controls' hook.  A rounding function (``QUANTS["fp8"]``)
goes round every product: the convolutions, the ``Linear``s, ``q k^T``,
``P v``, the correlation, the propagation's two products.  A dict names
sites: ``all`` is that rounding, ``scores`` rounds the window attentions'
scaled scores themselves before their softmax, ``corr`` the correlation
volume before its softmax, forward value and cotangent alike:
``QUANTS["bf16_scores"]`` and ``QUANTS["bf16_corr"]`` are the reference with
every product in bfloat16, as the program computes them, *and* that one array
kept in bfloat16, which the program keeps in float32.

``drop_aggregate`` (the keyword ``kinds/train_arch.py`` passes for ``--fault
no_aggregate``) plants the fault "the Transformer's messages left out":
``L_self`` and ``L_cross`` return their source unchanged, as in a program
that dropped both attentions and the FFN of every block.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import (QUANTS as _QUANTS, adamw_step, conv,
                                 encoder, fake_bf16, grid, highest, onecycle,
                                 quantised, upflow8)

LN_EPS = 1e-5
SPLITS = 2
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)

QUANTS = dict(_QUANTS,
              bf16_scores={"all": fake_bf16, "scores": fake_bf16},
              bf16_corr={"all": fake_bf16, "corr": fake_bf16})


def _site(quant, name=None):
    """The rounding ``quant`` asks for at a site: a product (``name``
    None) or one of the named arrays."""
    if isinstance(quant, dict):
        return quant.get(name or "all")
    return quant if name is None else None


def _rounded(x, fn):
    """``fn(x)``, with the cotangent rounded likewise on its way back."""
    if fn is None:
        return x

    @jax.custom_vjp
    def f(x):
        return fn(x)

    f.defvjp(lambda x: (fn(x), None), lambda _, g: (fn(g),))
    return f(x)


def _product(spec, quant):
    return quantised(lambda a, b: jnp.einsum(spec, a, b), _site(quant))


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def linear(x, p, quant=None):
    y = _product("...i,io->...o", quant)(x, p["kernel"])
    return y + p["bias"] if "bias" in p else y


def layer_norm(x, p):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean((x - m) ** 2, -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + LN_EPS) * p["scale"] + p["bias"]


def sine_position(h, w, channels):
    """DETR's ``PositionEmbeddingSine(channels / 2, normalize=True)``."""
    f = channels // 2
    ones = np.ones((h, w), np.float64)
    y_embed, x_embed = ones.cumsum(0), ones.cumsum(1)
    y_embed = y_embed / (y_embed[-1:, :] + 1e-6) * 2 * math.pi
    x_embed = x_embed / (x_embed[:, -1:] + 1e-6) * 2 * math.pi
    dim_t = 10000.0 ** (2 * (np.arange(f) // 2) / f)
    out = []
    for e in (y_embed, x_embed):
        p = e[:, :, None] / dim_t
        out.append(np.stack([np.sin(p[:, :, 0::2]), np.cos(p[:, :, 1::2])],
                            axis=3).reshape(h, w, f))
    return jnp.asarray(np.concatenate(out, axis=2), jnp.float32)


def split(x, k=SPLITS):
    B, h, w, C = x.shape
    x = x.reshape(B, k, h // k, k, w // k, C).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B * k * k, h // k, w // k, C)


def merge(x, k=SPLITS):
    Bkk, hk, wk, C = x.shape
    x = x.reshape(Bkk // (k * k), k, k, hk, wk, C).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(Bkk // (k * k), k * hk, k * wk, C)


def shift_mask(h, w, k=SPLITS):
    """Swin's mask of a map rolled by half a window, ``(k*k, n, n)``: label
    the nine regions, cut the labels into windows, compare."""
    wh, ww = h // k, w // k
    img = np.zeros((h, w), np.float32)
    cnt = 0
    for hs in (slice(0, -wh), slice(-wh, -(wh // 2)), slice(-(wh // 2), None)):
        for ws in (slice(0, -ww), slice(-ww, -(ww // 2)),
                   slice(-(ww // 2), None)):
            img[hs, ws] = cnt
            cnt += 1
    win = np.stack([img[i * wh:(i + 1) * wh, j * ww:(j + 1) * ww].ravel()
                    for i in range(k) for j in range(k)])
    d = win[:, None, :] - win[:, :, None]
    return jnp.asarray(np.where(d != 0, -100.0, 0.0), jnp.float32)


def window_attention(q, k, v, h, w, shift, quant=None):
    """``softmax(q k^T / sqrt(C) + M) v`` inside each of the 2x2 windows;
    ``q, k, v``: ``(B, h*w, C)``."""
    B, _, C = q.shape
    sh, sw = h // (2 * SPLITS), w // (2 * SPLITS)

    def windows(x):
        x = x.reshape(B, h, w, C)
        if shift:
            x = jnp.roll(x, (-sh, -sw), (1, 2))
        return split(x).reshape(B * SPLITS * SPLITS, -1, C)

    q, k, v = windows(q), windows(k), windows(v)
    scores = _product("bnc,bmc->bnm", quant)(q, k) / math.sqrt(C)
    scores = _rounded(scores, _site(quant, "scores"))
    if shift:
        scores = scores + jnp.tile(shift_mask(h, w), (B, 1, 1))
    out = _product("bnm,bmc->bnc", quant)(jax.nn.softmax(scores, -1), v)
    out = merge(out.reshape(-1, h // SPLITS, w // SPLITS, C))
    if shift:
        out = jnp.roll(out, (sh, sw), (1, 2))
    return out.reshape(B, h * w, C)


def layer(p, s, t, h, w, shift, quant=None, drop=False):
    """``L(s, t)``; with ``mlp_0`` in ``p`` the cross-attention half."""
    if drop:
        return s
    m = window_attention(linear(s, p["q_proj"], quant),
                         linear(t, p["k_proj"], quant),
                         linear(t, p["v_proj"], quant), h, w, shift, quant)
    m = layer_norm(linear(m, p["merge"], quant), p["norm1"])
    if "mlp_0" in p:
        m = linear(jnp.concatenate([s, m], -1), p["mlp_0"], quant)
        m = linear(jax.nn.gelu(m, approximate=False), p["mlp_2"], quant)
        m = layer_norm(m, p["norm2"])
    return s + m


def transformer(p, x, h, w, quant=None, drop=False, remat=False):
    """Six blocks over ``x = [F1; F2]`` ``(2B, h*w, C)``."""
    B = x.shape[0] // 2

    def block(p, x, shift):
        x = layer(p["self_attn"], x, x, h, w, shift, quant, drop)
        other = jnp.concatenate([x[B:], x[:B]], 0)
        return layer(p["cross_attn_ffn"], x, other, h, w, shift, quant, drop)

    for i in range(6):
        f = jax.checkpoint(block, static_argnums=(2,)) if remat else block
        x = f(p[f"layers_{i}"], x, i % 2 == 1)
    return x


def match(f1, f2, quant=None):
    """Global matching: ``softmax(F1 F2^T / sqrt(C)) G - G``."""
    B, h, w, C = f1.shape
    s = _product("bnc,bmc->bnm", quant)(
        f1.reshape(B, h * w, C), f2.reshape(B, h * w, C)) / math.sqrt(C)
    s = _rounded(s, _site(quant, "corr"))
    g = grid(B, h, w).reshape(B, h * w, 2)
    flow = jnp.einsum("bnm,bmc->bnc", jax.nn.softmax(s, -1), g) - g
    return flow.reshape(B, h, w, 2)


def propagate(p, f1, flow, quant=None):
    """``softmax(q k^T / sqrt(C)) flow``, ``k`` projected from ``q``."""
    B, h, w, C = f1.shape
    q = linear(f1.reshape(B, h * w, C), p["q_proj"], quant)
    k = linear(q, p["k_proj"], quant)
    s = _product("bnc,bmc->bnm", quant)(q, k) / math.sqrt(C)
    out = jnp.einsum("bnm,bmc->bnc", jax.nn.softmax(s, -1),
                     flow.reshape(B, h * w, 2))
    return out.reshape(B, h, w, 2)


def convex_upsample(p, flow, f1, quant=None):
    """Logits ``conv1x1(relu(conv3x3([flow, F1])))``, softmax over the 9
    coarse neighbours, applied to ``8 * flow``."""
    q = _site(quant)
    m = conv(jax.nn.relu(conv(jnp.concatenate([flow, f1], -1), p["conv1"],
                              1, q)), p["conv2"], 1, q)
    B, H, W, _ = flow.shape
    m = jax.nn.softmax(m.reshape(B, H, W, 9, 8, 8), axis=3)
    fp = jnp.pad(8.0 * flow, ((0, 0), (1, 1), (1, 1), (0, 0)))
    nb = jnp.stack([fp[:, i:i + H, j:j + W] for i in range(3)
                    for j in range(3)], axis=3)
    return jnp.einsum("bhwkpq,bhwkc->bhpwqc", m, nb).reshape(
        B, 8 * H, 8 * W, 2)


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def forward(cfg, variables, image1, image2, iters=0, train=False, quant=None,
            remat=False, drop_aggregate=False, both=False):
    """-> the full-resolution flow ``(B, H, W, 2)``; with ``both`` the two
    predictions training is supervised on, ``(2, B, H, W, 2)``: the matched
    flow upsampled bilinearly, and the propagated one (whose input is
    detached) upsampled convexly."""
    p = variables["params"]
    C = int(cfg["feature_channels"])
    B = image1.shape[0]
    x = jnp.concatenate([image1, image2], 0).astype(jnp.float32) / 255.0
    x = (x - jnp.asarray(MEAN)) / jnp.asarray(STD)
    x = encoder(x, p["backbone"], None, "instance", False, train,
                _site(quant), remat)
    _, h, w, _ = x.shape
    pos = sine_position(h // SPLITS, w // SPLITS, C)
    x = merge(split(x) + pos).reshape(2 * B, h * w, C)
    x = transformer(p["transformer"], x, h, w, quant, drop_aggregate, remat)
    x = x.reshape(2 * B, h, w, C)
    f1, f2 = x[:B], x[B:]
    flow = match(f1, f2, quant)
    flow2 = propagate(p["feature_flow_attn"], f1,
                      jax.lax.stop_gradient(flow), quant)
    up = convex_upsample(p["upsampler"], flow2, f1, quant)
    return jnp.stack([upflow8(flow), up]) if both else up


def sequence_loss(cfg, variables, batch, gamma=0.9, max_flow=400.0,
                  quant=None, drop_aggregate=False):
    """``sum_i gamma^(1 - i) mean(valid * |pred_i - gt|)``, i = 0, 1."""
    gt, valid = batch["flow"], batch["valid"]
    mag = jnp.sqrt(jnp.sum(gt ** 2, -1))
    v = ((valid > 0.5) & (mag < max_flow)).astype(jnp.float32)[..., None]
    preds = forward(cfg, variables, batch["image1"], batch["image2"],
                    train=True, quant=quant, remat=True,
                    drop_aggregate=drop_aggregate, both=True)
    terms = jnp.mean(v * jnp.abs(preds - gt), axis=(1, 2, 3, 4))
    return jnp.sum(jnp.asarray([gamma, 1.0]) * terms)


def train_steps(cfg, variables, batches, iters, lr, num_steps, quant=None,
                block=4, seconds=None, drop_aggregate=False):
    """Follow the first ``len(batches)`` steps from ``variables``; returns
    and arguments as ``reference.train_steps``.  The batch runs in blocks of
    ``block`` rows, so that the float32 ``N x N`` and window arrays of a
    block fit the chip: no norm spans the batch and the loss is a mean over
    rows, so equal blocks average."""
    params = variables["params"]
    gamma = float(cfg.get("gamma", 0.9))

    @jax.jit
    def blk(p, b):
        return jax.value_and_grad(lambda p: sequence_loss(
            cfg, {"params": p}, b, gamma, quant=quant,
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
