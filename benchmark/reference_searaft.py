"""Plain SEA-RAFT (M) (Wang, Lipson, Deng, ECCV 2024, arXiv:2405.14793) in
``jax.numpy`` float32: ResNet-34 encoders with batch norm, a context read
from both images, a first flow regressed before the loop, two ConvNeXt
blocks where RAFT has a GRU, a 6-channel head beside the update block, and
a mixture-of-Laplace loss over ``iters + 1`` predictions (PAPERS.md has the
equations and every departure).

Built from ``benchmark/reference.py``'s blocks (``conv``, ``norm``, the
pyramid, the lookup, the motion encoder, the optimiser); imports nothing of
``raft_tpu``.  The functions the kinds call (``forward``, ``sequence_loss``,
``train_steps``, ``serve_flows``, ``QUANTS``, ``highest``) have
``reference.py``'s signatures.  ``quant`` reaches the new products too (the
depthwise convolution, ``W1``, ``W2``, ``final``, ``init_conv``, both heads),
so the fp8 and bfloat16 controls round them like every other product.

``drop_aggregate`` (the keyword ``kinds/train_arch.py`` passes for ``--fault
no_aggregate``) plants the fault "``gamma * (...)`` left out of both ConvNeXt
blocks": each block is then ``final(u)``, as in a program that dropped the
depthwise convolution, the LayerNorm and both products.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import (QUANTS, _scale, adamw_step,  # noqa: F401
                                 conv, corr_lookup, corr_pyramid, grid,
                                 highest, motion_encoder, norm, onecycle,
                                 quantised)

VAR_MAX = 10.0
LN_EPS = 1e-6


# --------------------------------------------------------------------------
# encoders
# --------------------------------------------------------------------------

def resnet_block(x, p, s, stride, train, quant):
    """``relu(s(x) + bn2(conv(relu(bn1(conv_stride(x))))))``; ``s`` the
    identity, or ``bn3(conv1x1_stride(x))`` where the block has one."""

    def bn(name, y):
        return norm(y, "batch", p[name], s.get(name, {}), train)

    y = jax.nn.relu(bn("norm1", conv(x, p["conv1"], stride, quant)))
    y = bn("norm2", conv(y, p["conv2"], 1, quant))
    if "downsample_conv" in p:
        x = bn("norm3", conv(x, p["downsample_conv"], stride, quant))
    return jax.nn.relu(x + y)


def resnet(x, p, s, train, quant, remat):
    """7x7/2 stem, batch norm, ReLU; 3, 4 and 6 basic blocks of 64, 128 and
    256 channels, the first of the second and third stage with stride 2;
    1x1 out.  Batch statistics are this call's own."""
    s = s or {}

    def wrap(f):
        return jax.checkpoint(f) if remat else f

    def stem(x, p, s):
        y = conv(x, p["conv1"], 2, quant)
        return jax.nn.relu(norm(y, "batch", p["norm1"], s.get("norm1", {}),
                                train))

    x = wrap(stem)(x, {k: p[k] for k in ("conv1", "norm1")},
                   {k: s[k] for k in ("norm1",) if k in s})
    for stage, blocks in ((1, 3), (2, 4), (3, 6)):
        for i in range(blocks):
            stride = 2 if (i == 0 and stage > 1) else 1
            name = f"layer{stage}_{i}"
            x = wrap(lambda x, p, s, stride=stride: resnet_block(
                x, p, s, stride, train, quant))(x, p[name], s.get(name, {}))
    return conv(x, p["conv2"], 1, quant)


def encode(variables, image1, image2, train=False, quant=None, remat=False):
    """-> (f1, f2, c): the feature encoder once an image, the context
    encoder over the pair stacked on the channels.  Three calls, three sets
    of batch statistics."""
    p, s = variables["params"], variables.get("batch_stats", {})
    a, b = _scale(image1), _scale(image2)
    f1 = resnet(a, p["fnet"], s.get("fnet"), train, quant, remat)
    f2 = resnet(b, p["fnet"], s.get("fnet"), train, quant, remat)
    c = resnet(jnp.concatenate([a, b], -1), p["cnet"], s.get("cnet"),
               train, quant, remat)
    return f1, f2, c


# --------------------------------------------------------------------------
# the update block and the heads
# --------------------------------------------------------------------------

def depthwise(x, p, quant=None):
    """7x7 depthwise convolution with bias; kernel (7, 7, 1, C)."""
    C = x.shape[-1]

    def plain(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), [(3, 3), (3, 3)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=C)

    return quantised(plain, quant)(x, p["kernel"]) + p["bias"]


def layer_norm(x, p):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean((x - m) ** 2, -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + LN_EPS) * p["scale"] + p["bias"]


def convnext(u, p, quant=None, drop=False):
    """``final(u + gamma * W2 gelu(W1 LN(dw7x7(u))))``, erf GELU."""
    if drop:
        return conv(u, p["final"], 1, quant)
    x = layer_norm(depthwise(u, p["dwconv"], quant), p["norm"])
    x = jax.nn.gelu(conv(x, p["pwconv1"], 1, quant), approximate=False)
    x = conv(x, p["pwconv2"], 1, quant)
    return conv(u + p["gamma"] * x, p["final"], 1, quant)


def update_block(p, net, ctx, corr, flow, quant, drop):
    m = motion_encoder(p["encoder"], flow, corr, False, quant)
    x = jnp.concatenate([ctx, m], -1)
    for name in ("refine_0", "refine_1"):
        net = convnext(jnp.concatenate([net, x], -1), p[name], quant, drop)
    return net


def flow_head(p, net, quant):
    """6 channels: flow update, 2 mixture logits, 2 raw log-scales."""
    return conv(jax.nn.relu(conv(net, p["conv1"], 1, quant)), p["conv2"], 1,
                quant)


def upsample(p, net, flow, info, quant):
    """The mask head (x0.25), softmax over the 9 coarse neighbours, applied
    to ``8 * flow`` and, with the same weights and no factor, to ``info``.
    -> (B, 8H, 8W, 2), (B, 8H, 8W, 4)."""
    m = conv(jax.nn.relu(conv(net, p["mask_conv1"], 1, quant)),
             p["mask_conv2"], 1, quant) * 0.25
    B, H, W, _ = flow.shape
    m = jax.nn.softmax(m.reshape(B, H, W, 9, 8, 8), axis=3)
    x = jnp.pad(jnp.concatenate([8.0 * flow, info], -1),
                ((0, 0), (1, 1), (1, 1), (0, 0)))
    nb = jnp.stack([x[:, i:i + H, j:j + W] for i in range(3)
                    for j in range(3)], axis=3)          # (B,H,W,9,6)
    up = jnp.einsum("bhwkpq,bhwkc->bhpwqc", m, nb).reshape(
        B, 8 * H, 8 * W, 6)
    return up[..., :2], up[..., 2:]


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def forward(cfg, variables, image1, image2, iters, train=False, quant=None,
            remat=False, per_iter=None, feats=None, drop_aggregate=False,
            with_info=False):
    """Run SEA-RAFT.  ``per_iter(flow_up_i, info_up_i, i)`` maps each of
    the ``iters + 1`` predictions to what the caller stacks (the training
    loss term); without it the last full-resolution flow is returned (and
    its ``info`` with ``with_info``).  ``feats``: ``(f1, f2, c)`` where the
    caller has run the encoders (whose batch norm spans the batch)."""
    p = variables["params"]
    hdim = int(cfg["hidden_dim"])
    levels, radius = int(cfg["corr_levels"]), int(cfg["corr_radius"])
    if feats is None:
        feats = encode(variables, image1, image2, train, quant, remat)
    f1, f2, c = feats
    pyramid = corr_pyramid(f1, f2, levels, quant)
    c = conv(c, p["init_conv"], 1, quant)
    net, ctx = c[..., :hdim], c[..., hdim:]          # no tanh, no ReLU
    B, H, W, _ = f1.shape
    c0 = grid(B, H, W)
    up_p, head_p = p["upsampler"]["mask_head"], p["flow_head"]

    def predict(net, flow, info, i):
        fu, iu = upsample(up_p, net, flow, info, quant)
        return per_iter(fu, iu, i) if per_iter is not None else None

    first = flow_head(head_p, net, quant)
    flow, info = first[..., :2], first[..., 2:]
    out0 = predict(net, flow, info, 0)

    def body(carry, i):
        net, flow = carry
        flow = jax.lax.stop_gradient(flow)
        corr = corr_lookup(pyramid, c0 + flow, radius)
        net = update_block(p["refine"]["update_block"], net, ctx, corr, flow,
                           quant, drop_aggregate)
        d = flow_head(head_p, net, quant)
        flow, info = flow + d[..., :2], d[..., 2:]
        return (net, flow), (predict(net, flow, info, i + 1), info)

    if remat:
        body = jax.checkpoint(body)
    (net, flow), (outs, infos) = jax.lax.scan(body, (net, flow),
                                              jnp.arange(iters))
    if per_iter is not None:
        return jnp.concatenate([out0[None], outs])
    fu, iu = upsample(up_p, net, flow, infos[-1] if iters else info, quant)
    return (fu, iu) if with_info else fu


def mixture_nll(err, a1, a2, b1):
    """Per element and flow channel: ``logsumexp_k(a_k) - logsumexp_k(a_k -
    log 2 - log b_k - err / b_k)``, ``log b_1 = clip(b1, 0, 10)``, ``log b_2
    = clip(., 0, 0) = 0``."""
    lb1 = jnp.clip(b1, 0.0, VAR_MAX)
    t1 = a1 - math.log(2.0) - lb1 - err * jnp.exp(-lb1)
    t2 = a2 - math.log(2.0) - err
    lse = jax.scipy.special.logsumexp
    return lse(jnp.stack([a1, a2]), axis=0) - lse(jnp.stack([t1, t2]), axis=0)


def loss_terms(cfg, variables, batch, iters, max_flow=400.0, quant=None,
               feats=None, drop_aggregate=False):
    """-> (sums (iters + 1,), counts (iters + 1,)): each prediction's summed
    likelihood over the elements (pixel, flow channel) that are valid,
    under ``max_flow`` and finite, and how many those are."""
    gt, valid = batch["flow"], batch["valid"]
    mag = jnp.sqrt(jnp.sum(gt ** 2, -1))
    v = ((valid > 0.5) & (mag < max_flow))[..., None]

    def term(flow_up, info_up, i):
        nll = mixture_nll(jnp.abs(gt - flow_up), info_up[..., 0:1],
                          info_up[..., 1:2], info_up[..., 2:3])
        keep = v & jnp.isfinite(jax.lax.stop_gradient(nll))
        return jnp.stack([jnp.sum(jnp.where(keep, nll, 0.0)),
                          jnp.sum(keep).astype(jnp.float32)])

    out = forward(cfg, variables, batch["image1"], batch["image2"], iters,
                  train=True, quant=quant, remat=True, per_iter=term,
                  feats=feats, drop_aggregate=drop_aggregate)
    return out[:, 0], out[:, 1]


def sequence_loss(cfg, variables, batch, iters, gamma=0.85, max_flow=400.0,
                  quant=None, feats=None, drop_aggregate=False):
    """``sum_i gamma^(n - 1 - i) * sum(mask * nll_i) / sum(mask)`` over the
    ``n = iters + 1`` predictions (train.py ``sequence_loss``, ``use_var``)."""
    sums, counts = loss_terms(cfg, variables, batch, iters, max_flow, quant,
                              feats, drop_aggregate)
    n = iters + 1
    w = gamma ** (n - 1.0 - jnp.arange(n, dtype=jnp.float32))
    return jnp.sum(w * sums / jnp.maximum(counts, 1.0))


def make_loss_and_grad(cfg, iters, block, quant=None, drop_aggregate=False,
                       gamma=0.85):
    """-> f(variables, batch) = (loss of the whole batch, its gradient).

    The three encoder calls run over all rows at once (batch norm spans
    the batch in each); the rest runs in blocks of ``block`` rows so that
    float32 activations fit beside each other, and the blocks' cotangents
    of the three feature maps are pulled back through the encoders at the
    end.  A prediction's term is a ratio of sums over the batch: the
    denominators are counted first (the valid mask, two channels a pixel),
    and every block divides by them."""
    n = iters + 1
    w = gamma ** (n - 1.0 - jnp.arange(n, dtype=jnp.float32))

    def enc_of(pe, stats, image1, image2):
        return encode({"params": pe, "batch_stats": stats}, image1, image2,
                      train=True, quant=quant, remat=True)

    enc_fwd = jax.jit(enc_of)

    @jax.jit
    def enc_bwd(pe, stats, image1, image2, g):
        return jax.vjp(lambda q: enc_of(q, stats, image1, image2), pe)[1](
            g)[0]

    @jax.jit
    def blk(p, feats, b, denom):
        def loss(p, feats):
            sums, counts = loss_terms(cfg, {"params": p}, b, iters,
                                      quant=quant, feats=feats,
                                      drop_aggregate=drop_aggregate)
            return jnp.sum(w * sums / denom), counts

        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            p, feats)

    def f(variables, batch):
        params = variables["params"]
        stats = variables.get("batch_stats", {})
        B = batch["image1"].shape[0]
        rows = min(block, B)
        if B % rows:
            raise ValueError(f"block {rows} does not divide the batch {B}")
        pe = {k: params[k] for k in ("fnet", "cnet")}
        rest = {k: v for k, v in params.items() if k not in pe}
        feats = enc_fwd(pe, stats, batch["image1"], batch["image2"])
        mag = np.sqrt(np.sum(np.asarray(batch["flow"]) ** 2, -1))
        denom = 2.0 * float(np.sum((np.asarray(batch["valid"]) > 0.5)
                                   & (mag < 400.0)))
        denom = jnp.full((n,), max(denom, 1.0), jnp.float32)
        loss, grads, g_feats, counted = 0.0, None, [], 0.0
        for i in range(B // rows):
            r = slice(i * rows, (i + 1) * rows)
            (l, counts), (g, gf) = blk(rest, tuple(x[r] for x in feats),
                                       {k: v[r] for k, v in batch.items()},
                                       denom)
            loss = loss + l
            counted = counted + counts
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
            g_feats.append(gf)
        if not np.array_equal(np.asarray(counted), np.asarray(denom)):
            raise FloatingPointError(
                "a likelihood term was not finite: the denominators "
                f"{np.asarray(denom)} counted {np.asarray(counted)}")
        g_enc = enc_bwd(pe, stats, batch["image1"], batch["image2"],
                        tuple(jnp.concatenate(x) for x in zip(*g_feats)))
        return loss, dict(grads, **g_enc)

    return f


def train_steps(cfg, variables, batches, iters, lr, num_steps, quant=None,
                block=4, seconds=None, drop_aggregate=False):
    """Follow the first ``len(batches)`` steps from ``variables``; returns
    and arguments as ``reference.train_steps``."""
    params = variables["params"]
    stats = variables.get("batch_stats", {})
    vg = make_loss_and_grad(cfg, iters, block, quant, drop_aggregate)
    upd = jax.jit(adamw_step)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu, losses, g1 = zeros, zeros, [], None
    with highest():
        for k, batch in enumerate(batches):
            t = time.perf_counter()
            batch = {n: np.asarray(v, np.float32) for n, v in batch.items()}
            loss, grads = vg({"params": params, "batch_stats": stats},
                             batch)
            params, mu, nu, g = upd(params, grads, mu, nu, float(k),
                                    onecycle(float(k), lr, num_steps))
            losses.append(float(loss))
            if seconds is not None:
                seconds.append(time.perf_counter() - t)
            if k == 0:
                g1 = g
    return losses, g1, params


def serve_flows(cfg, variables, pairs, iters, pad_to, quant=None):
    """Full-resolution flow (the last prediction) of each pair, edge-padded
    to ``pad_to`` and cut back, as ``reference.serve_flows``."""
    h, w = pairs[0][0].shape[:2]
    ph, pw = pad_to[0] - h, pad_to[1] - w
    t, l = ph // 2, pw // 2
    widths = ((t, ph - t), (l, pw - l), (0, 0))
    variables = jax.device_put(variables)

    @jax.jit
    def one(variables, a, b):
        return forward(cfg, variables, a[None], b[None], iters,
                       quant=quant)[0]

    out = []
    with highest():
        for a, b in pairs:
            a = np.pad(np.asarray(a, np.float32), widths, mode="edge")
            b = np.pad(np.asarray(b, np.float32), widths, mode="edge")
            f = np.asarray(one(variables, a, b))
            out.append(f[t:t + h, l:l + w])
    return out
