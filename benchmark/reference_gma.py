"""Plain GMA (Jiang et al., ICCV 2021, arXiv:2104.02409) in ``jax.numpy``
float32: RAFT-full with a content attention built once from the context and
a global aggregate of the motion features in every iteration (PAPERS.md has
the equations and every departure).

Built from ``benchmark/reference.py``'s blocks (encoder, pyramid, lookup,
motion encoder, GRU pass, convex upsampling, optimiser); imports nothing of
``raft_tpu``.  The functions the kinds call (``forward``, ``sequence_loss``,
``train_steps``, ``serve_flows``, ``QUANTS``, ``highest``) have
``reference.py``'s signatures.  ``quant`` reaches the new products too
(``q k^T``, ``A v``, the two 1x1 convolutions), so the fp8 and bfloat16
controls round them like every other product.

``drop_aggregate`` plants the fault "``gamma * (A v)`` left out": the global
term never reaches the GRU, as in a program that dropped the block.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import (QUANTS, _scale, adamw_step,  # noqa: F401
                                 context, conv, convex_upsample, corr_lookup,
                                 corr_pyramid, encoder, grid, gru_pass,
                                 highest, motion_encoder, onecycle,
                                 quantised)


def _conv1x1(x, kernel, quant):
    """A bias-free 1x1 convolution through ``reference.conv``."""
    return conv(x, {"kernel": kernel, "bias": 0.0}, 1, quant)


def attention(p, inp, quant=None):
    """``softmax_rows(d^-1/2 q k^T)`` of one head over the ``N = H*W``
    positions of ``inp`` (B, H, W, C), a sample at a time (the ``N x N``
    temporaries of one sample fit beside the program's state; those of a
    block of samples need not).  -> (B, N, N)."""
    qk = _conv1x1(inp, p["to_qk"]["kernel"], quant)
    B, H, W, d2 = qk.shape
    d = d2 // 2
    q, k = jnp.split(qk.reshape(B, H * W, d2), 2, axis=-1)
    product = quantised(lambda a, b: jnp.einsum("nd,md->nm", a, b), quant)

    def one(qk):
        return jax.nn.softmax(product(qk[0] * d ** -0.5, qk[1]), axis=-1)

    return jax.lax.map(one, (q, k))


def aggregate(p, attn, motion, quant=None, drop=False):
    """``m + gamma * (A v)``, ``v`` a bias-free 1x1 convolution of ``m``."""
    if drop:
        return motion
    B, H, W, C = motion.shape
    v = _conv1x1(motion, p["to_v"]["kernel"], quant).reshape(B, H * W, C)
    out = quantised(lambda a, v: jnp.einsum("bnm,bmc->bnc", a, v),
                    quant)(attn, v)
    return motion + p["gamma"] * out.reshape(B, H, W, C)


def update_block(p, net, inp, corr, flow, attn, quant, drop_aggregate):
    m = motion_encoder(p["encoder"], flow, corr, False, quant)
    g = aggregate(p["aggregator"], attn, m, quant, drop_aggregate)
    x = jnp.concatenate([inp, m, g], -1)
    gru = p["gru"]
    net = gru_pass(net, x, gru["convzr1"], gru["convq1"], quant)
    net = gru_pass(net, x, gru["convzr2"], gru["convq2"], quant)
    fh = p["flow_head"]
    d = conv(jax.nn.relu(conv(net, fh["conv1"], 1, quant)), fh["conv2"], 1,
             quant)
    return net, d


def forward(cfg, variables, image1, image2, iters, train=False, quant=None,
            remat=False, per_iter=None, ctx=None, drop_aggregate=False):
    """Run GMA.  Arguments as ``reference.forward``'s."""
    p, s = variables["params"], variables.get("batch_stats", {})
    hdim = int(cfg["hidden_dim"])
    levels, radius = int(cfg["corr_levels"]), int(cfg["corr_radius"])

    def enc(image):
        return encoder(_scale(image), p["fnet"], s.get("fnet"),
                       cfg["fnet_norm"], False, train, quant, remat)

    f1, f2 = enc(image1), enc(image2)
    if ctx is None:
        ctx = context(cfg, variables, image1, train, quant, remat)
    pyramid = corr_pyramid(f1, f2, levels, quant)
    net, inp = jnp.tanh(ctx[..., :hdim]), jax.nn.relu(ctx[..., hdim:])
    attn = attention(p["att"], inp, quant)      # once, before the loop
    B, H, W, _ = f1.shape
    c0 = grid(B, H, W)
    up_p = p["upsampler"]["mask_head"]

    def body(carry, i):
        net, c1 = carry
        c1 = jax.lax.stop_gradient(c1)
        corr = corr_lookup(pyramid, c1, radius)
        net, d = update_block(p["refine"]["update_block"], net, inp, corr,
                              c1 - c0, attn, quant, drop_aggregate)
        c1 = c1 + d
        out = None
        if per_iter is not None:
            out = per_iter(convex_upsample(up_p, net, c1 - c0, quant), i)
        return (net, c1), out

    if remat:
        body = jax.checkpoint(body)
    (net, c1), outs = jax.lax.scan(body, (net, c0), jnp.arange(iters))
    if per_iter is not None:
        return outs
    return convex_upsample(up_p, net, c1 - c0, quant)


def sequence_loss(cfg, variables, batch, iters, gamma=0.8, max_flow=400.0,
                  quant=None, ctx=None, drop_aggregate=False):
    """The paper's training loss, as ``reference.sequence_loss``."""
    gt, valid = batch["flow"], batch["valid"]
    mag = jnp.sqrt(jnp.sum(gt ** 2, -1))
    v = ((valid > 0.5) & (mag < max_flow)).astype(jnp.float32)[..., None]

    def term(flow_up, i):
        return jnp.mean(v * jnp.abs(flow_up - gt))

    terms = forward(cfg, variables, batch["image1"], batch["image2"], iters,
                    train=True, quant=quant, remat=True, per_iter=term,
                    ctx=ctx, drop_aggregate=drop_aggregate)
    w = gamma ** (iters - 1.0 - jnp.arange(iters, dtype=jnp.float32))
    return jnp.sum(w * terms)


def make_loss_and_grad(cfg, iters, block, quant=None, drop_aggregate=False):
    """``reference.make_loss_and_grad`` over this file's loss: the context
    encoder (batch norm over the whole batch) once, the rest in blocks of
    ``block`` rows, the blocks' context cotangents pulled back at the end."""

    def ctx_of(pc, stats, image1):
        return context(cfg, {"params": {"cnet": pc}, "batch_stats": stats},
                       image1, train=True, quant=quant, remat=True)

    ctx_fwd = jax.jit(ctx_of)

    @jax.jit
    def ctx_bwd(pc, stats, image1, g):
        return jax.vjp(lambda q: ctx_of(q, stats, image1), pc)[1](g)[0]

    @jax.jit
    def blk(p, stats, ctx, b):
        return jax.value_and_grad(
            lambda p, c: sequence_loss(
                cfg, {"params": p, "batch_stats": stats}, b, iters,
                quant=quant, ctx=c, drop_aggregate=drop_aggregate),
            argnums=(0, 1))(p, ctx)

    def f(variables, batch):
        params = variables["params"]
        stats = variables.get("batch_stats", {})
        B = batch["image1"].shape[0]
        n = min(block, B)
        if B % n:
            raise ValueError(f"block {n} does not divide the batch {B}")
        nb = B // n
        ctx = ctx_fwd(params["cnet"], stats, batch["image1"])
        loss, grads, g_ctx = 0.0, None, []
        for i in range(nb):
            rows = slice(i * n, (i + 1) * n)
            l, (g, gc) = blk(params, stats, ctx[rows],
                             {k: v[rows] for k, v in batch.items()})
            loss = loss + l / nb
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
            g_ctx.append(gc)
        g_cnet = ctx_bwd(params["cnet"], stats, batch["image1"],
                         jnp.concatenate(g_ctx) / nb)
        grads = jax.tree_util.tree_map(lambda x: x / nb, grads)
        grads = dict(grads, cnet=jax.tree_util.tree_map(
            jnp.add, grads["cnet"], g_cnet))
        return loss, grads

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
    """Full-resolution flow of each pair, edge-padded to ``pad_to`` and cut
    back, as ``reference.serve_flows``."""
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
