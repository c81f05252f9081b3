"""Plain RAFT (Teed & Deng, ECCV 2020) in ``jax.numpy`` float32.

The yardstick the benchmark holds the program to.  It imports nothing of
``raft_tpu``: it reads a parameter tree by the names a checkpoint has
(``fnet/layer1_0/conv1/kernel`` ...), which is the program's file format,
not its code.  One implementation for both published widths, parameterised
by the configuration file (``benchmark/configs/*.json``).

Everything runs under ``jax.default_matmul_precision("highest")`` (callers
enter :func:`highest`): on a TPU a float32 matmul is otherwise one bf16 pass.

``quant`` is the control's hook: a rounding (e.g. :func:`fake_int8`) that
every convolution and the correlation product are computed through
(:func:`quantised`), which puts the reference in the nearest precision
*below* the configured bfloat16.  ``None`` is the reference proper.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5


def highest():
    return jax.default_matmul_precision("highest")


def fake_int8(x):
    """Symmetric per-tensor int8 rounding, kept in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / s) * s


def fake_fp8(x):
    """Per-tensor scaled float8 (e4m3) rounding, kept in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def fake_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


QUANTS = {"none": None, "int8": fake_int8, "fp8": fake_fp8,
          "bfloat16": fake_bf16}


def quantised(fn, quant):
    """``fn(a, b)`` (a convolution, the correlation product) computed in the
    precision ``quant`` rounds to: both operands rounded, and in the backward
    pass the cotangent rounded too before the two transposed products, as a
    step computed in that precision would have them."""
    if quant is None:
        return fn

    @jax.custom_vjp
    def f(a, b):
        return fn(quant(a), quant(b))

    def fwd(a, b):
        return jax.vjp(fn, quant(a), quant(b))

    def bwd(vjp, g):
        return vjp(quant(g))

    f.defvjp(fwd, bwd)
    return f


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def conv(x, p, stride=1, quant=None):
    w = p["kernel"]
    kh, kw = w.shape[:2]

    def plain(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride),
            [((kh - 1) // 2, (kh - 1) // 2), ((kw - 1) // 2, (kw - 1) // 2)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    return quantised(plain, quant)(x, w) + p["bias"]


def norm(x, kind, p, stats, train):
    """instance | batch | none.  Batch norm in training normalises with the
    batch's own biased statistics (chairs stage: not frozen)."""
    if kind == "none":
        return x
    if kind == "instance":
        m = jnp.mean(x, axis=(1, 2), keepdims=True)
        v = jnp.mean((x - m) ** 2, axis=(1, 2), keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + EPS)
    if kind == "batch":
        bn = p["BatchNorm_0"]
        if train:
            m = jnp.mean(x, axis=(0, 1, 2))
            v = jnp.mean((x - m) ** 2, axis=(0, 1, 2))
        else:
            m, v = stats["BatchNorm_0"]["mean"], stats["BatchNorm_0"]["var"]
        return (x - m) * jax.lax.rsqrt(v + EPS) * bn["scale"] + bn["bias"]
    raise ValueError(kind)


def _norm_of(p, s, name, kind, train):
    return lambda x: norm(x, kind, p.get(name, {}), (s or {}).get(name, {}),
                          train)


def residual_block(x, p, s, kind, stride, train, quant):
    n = functools.partial(_norm_of, p, s, kind=kind, train=train)
    y = jax.nn.relu(n("norm1")(conv(x, p["conv1"], stride, quant)))
    y = jax.nn.relu(n("norm2")(conv(y, p["conv2"], 1, quant)))
    if stride != 1:
        x = n("norm3")(conv(x, p["downsample_conv"], stride, quant))
    return jax.nn.relu(x + y)


def bottleneck_block(x, p, s, kind, stride, train, quant):
    n = functools.partial(_norm_of, p, s, kind=kind, train=train)
    y = jax.nn.relu(n("norm1")(conv(x, p["conv1"], 1, quant)))
    y = jax.nn.relu(n("norm2")(conv(y, p["conv2"], stride, quant)))
    y = jax.nn.relu(n("norm3")(conv(y, p["conv3"], 1, quant)))
    if stride != 1:
        x = n("norm4")(conv(x, p["downsample_conv"], stride, quant))
    return jax.nn.relu(x + y)


def encoder(x, p, s, kind, small, train, quant, remat):
    """7x7/2 stem, three stages of two blocks (strides 1, 2, 2), 1x1 out."""
    block = bottleneck_block if small else residual_block
    s = s or {}

    def stem(x, p, s):
        y = conv(x, p["conv1"], 2, quant)
        return jax.nn.relu(_norm_of(p, s, "norm1", kind, train)(y))

    def wrap(f):
        return jax.checkpoint(f) if remat else f

    x = wrap(stem)(x, {k: p[k] for k in ("conv1", "norm1") if k in p},
                   {k: s[k] for k in ("norm1",) if k in s})
    for i, stride in enumerate((1, 1, 2, 1, 2, 1)):
        name = f"layer{i // 2 + 1}_{i % 2}"
        f = functools.partial(block, kind=kind, stride=stride, train=train,
                              quant=quant)
        x = wrap(lambda x, p, s, f=f: f(x, p, s))(x, p[name],
                                                  s.get(name, {}))
    return conv(x, p["conv2"], 1, quant)


# --------------------------------------------------------------------------
# correlation
# --------------------------------------------------------------------------

def corr_pyramid(f1, f2, levels, quant=None):
    """Level l: (B, N, H/2^l, W/2^l); 2x2 mean pooling, odd edge dropped."""
    B, H, W, C = f1.shape
    a, b = f1.reshape(B, H * W, C), f2.reshape(B, H * W, C)
    c = quantised(lambda a, b: jnp.einsum("bnc,bmc->bnm", a, b),
                  quant)(a, b) / np.sqrt(C)
    c = c.reshape(B, H * W, H, W)
    out = [c]
    for _ in range(levels - 1):
        h2, w2 = c.shape[2] // 2, c.shape[3] // 2
        c = c[:, :, :h2 * 2, :w2 * 2].reshape(B, H * W, h2, 2, w2, 2)
        c = c.mean(axis=(3, 5))
        out.append(c)
    return out


def _hat(c, n, radius):
    """Linear-interpolation weights of each window tap over positions
    0..n-1: max(0, 1 - |c + t - r - p|); a tap outside gets zeros."""
    taps = jnp.arange(2 * radius + 1, dtype=jnp.float32) - radius
    pos = jnp.arange(n, dtype=jnp.float32)
    return jnp.maximum(0.0, 1.0 - jnp.abs(c[..., None, None]
                                          + taps[:, None] - pos))


def corr_lookup(pyramid, coords, radius):
    """(2r+1)^2 bilinear taps round coords/2^l per level, zero outside;
    channel = level, then x offset, then y offset."""
    B, H, W, _ = coords.shape
    c = coords.reshape(B, H * W, 2)
    out = []
    for lvl, corr in enumerate(pyramid):
        cl = c / (2 ** lvl)
        wx = _hat(cl[..., 0], corr.shape[3], radius)
        wy = _hat(cl[..., 1], corr.shape[2], radius)
        t = jnp.einsum("bnjy,bnyx->bnjx", wy, corr)
        t = jnp.einsum("bnix,bnjx->bnij", wx, t)
        out.append(t.reshape(B, H * W, -1))
    return jnp.concatenate(out, axis=-1).reshape(B, H, W, -1)


# --------------------------------------------------------------------------
# update block, upsampling
# --------------------------------------------------------------------------

def motion_encoder(p, flow, corr, small, quant):
    r = jax.nn.relu
    cor = r(conv(corr, p["convc1"], 1, quant))
    if not small:
        cor = r(conv(cor, p["convc2"], 1, quant))
    flo = r(conv(flow, p["convf1"], 1, quant))
    flo = r(conv(flo, p["convf2"], 1, quant))
    out = r(conv(jnp.concatenate([cor, flo], -1), p["conv"], 1, quant))
    return jnp.concatenate([out, flow], -1)


def gru_pass(h, x, pzr, pq, quant):
    zr = jax.nn.sigmoid(conv(jnp.concatenate([h, x], -1), pzr, 1, quant))
    z, r = jnp.split(zr, 2, axis=-1)
    q = jnp.tanh(conv(jnp.concatenate([r * h, x], -1), pq, 1, quant))
    return (1 - z) * h + z * q


def update_block(p, net, inp, corr, flow, small, quant):
    x = jnp.concatenate([inp, motion_encoder(p["encoder"], flow, corr,
                                             small, quant)], -1)
    g = p["gru"]
    if small:
        net = gru_pass(net, x, g["convzr"], g["convq"], quant)
    else:
        net = gru_pass(net, x, g["convzr1"], g["convq1"], quant)
        net = gru_pass(net, x, g["convzr2"], g["convq2"], quant)
    fh = p["flow_head"]
    d = conv(jax.nn.relu(conv(net, fh["conv1"], 1, quant)), fh["conv2"], 1,
             quant)
    return net, d


def convex_upsample(p, net, flow, quant):
    """Mask head (x0.25), softmax over the 9 neighbours, 8x8 sub-pixels."""
    m = conv(jax.nn.relu(conv(net, p["mask_conv1"], 1, quant)),
             p["mask_conv2"], 1, quant) * 0.25
    B, H, W, _ = flow.shape
    m = jax.nn.softmax(m.reshape(B, H, W, 9, 8, 8), axis=3)
    fp = jnp.pad(8.0 * flow, ((0, 0), (1, 1), (1, 1), (0, 0)))
    nb = jnp.stack([fp[:, i:i + H, j:j + W] for i in range(3)
                    for j in range(3)], axis=3)          # (B,H,W,9,2)
    up = jnp.einsum("bhwkpq,bhwkc->bhpwqc", m, nb)
    return up.reshape(B, 8 * H, 8 * W, 2)


def _interp(src, dst):
    pos = np.arange(dst, dtype=np.float64) * (src - 1) / max(dst - 1, 1)
    lo = np.clip(np.floor(pos), 0, src - 2).astype(np.int64)
    m = np.zeros((dst, src), np.float64)
    m[np.arange(dst), lo] += 1.0 - (pos - lo)
    m[np.arange(dst), lo + 1] += pos - lo
    return jnp.asarray(m, jnp.float32)


def upflow8(flow):
    """Bilinear x8 with corners aligned, values x8 (the small model)."""
    _, H, W, _ = flow.shape
    y = jnp.einsum("ih,bhwc->biwc", _interp(H, 8 * H), flow)
    return 8.0 * jnp.einsum("jw,biwc->bijc", _interp(W, 8 * W), y)


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def grid(B, H, W):
    xx, yy = jnp.meshgrid(jnp.arange(W, dtype=jnp.float32),
                          jnp.arange(H, dtype=jnp.float32))
    return jnp.broadcast_to(jnp.stack([xx, yy], -1)[None], (B, H, W, 2))


def _scale(image):
    return 2.0 * (image.astype(jnp.float32) / 255.0) - 1.0


def context(cfg, variables, image1, train=False, quant=None, remat=False):
    """The context encoder's output for frame 1.  With batch norm in
    training its statistics run over the whole batch, so the caller gives
    it every row at once even where the rest runs in blocks of rows."""
    p, s = variables["params"], variables.get("batch_stats", {})
    return encoder(_scale(image1), p["cnet"], s.get("cnet"),
                   cfg["cnet_norm"], bool(cfg["small"]), train, quant, remat)


def forward(cfg, variables, image1, image2, iters, train=False, quant=None,
            remat=False, per_iter=None, ctx=None):
    """Run RAFT.  ``per_iter(flow_up_i, i)`` maps each iteration's
    full-resolution flow to whatever the caller stacks (the training loss
    term); without it the last full-resolution flow is returned.  ``ctx``:
    the context encoder's output where the caller has computed it."""
    p, s = variables["params"], variables.get("batch_stats", {})
    small, hdim = bool(cfg["small"]), int(cfg["hidden_dim"])
    levels, radius = int(cfg["corr_levels"]), int(cfg["corr_radius"])
    enc = functools.partial(encoder, small=small, train=train, quant=quant,
                            remat=remat)
    # instance norm: the two frames are independent, encode them apart
    f1 = enc(_scale(image1), p["fnet"], s.get("fnet"), cfg["fnet_norm"])
    f2 = enc(_scale(image2), p["fnet"], s.get("fnet"), cfg["fnet_norm"])
    if ctx is None:
        ctx = context(cfg, variables, image1, train, quant, remat)
    pyramid = corr_pyramid(f1, f2, levels, quant)
    net, inp = jnp.tanh(ctx[..., :hdim]), jax.nn.relu(ctx[..., hdim:])
    B, H, W, _ = f1.shape
    c0 = grid(B, H, W)
    up_p = p.get("upsampler", {}).get("mask_head")

    def upsample(net, flow):
        if small:
            return upflow8(flow)
        return convex_upsample(up_p, net, flow, quant)

    def body(carry, i):
        net, c1 = carry
        c1 = jax.lax.stop_gradient(c1)
        corr = corr_lookup(pyramid, c1, radius)
        net, d = update_block(p["refine"]["update_block"], net, inp, corr,
                              c1 - c0, small, quant)
        c1 = c1 + d
        out = None
        if per_iter is not None:
            out = per_iter(upsample(net, c1 - c0), i)
        return (net, c1), out

    if remat:
        body = jax.checkpoint(body)
    (net, c1), outs = jax.lax.scan(body, (net, c0), jnp.arange(iters))
    if per_iter is not None:
        return outs
    return upsample(net, c1 - c0)


def sequence_loss(cfg, variables, batch, iters, gamma=0.8, max_flow=400.0,
                  quant=None, ctx=None):
    """sum_i gamma^(n-i-1) * mean(valid * |flow_i - gt|), the mean over all
    pixels and both channels (the paper's training loss)."""
    gt, valid = batch["flow"], batch["valid"]
    mag = jnp.sqrt(jnp.sum(gt ** 2, -1))
    v = ((valid > 0.5) & (mag < max_flow)).astype(jnp.float32)[..., None]

    def term(flow_up, i):
        return jnp.mean(v * jnp.abs(flow_up - gt))

    terms = forward(cfg, variables, batch["image1"], batch["image2"], iters,
                    train=True, quant=quant, remat=True, per_iter=term,
                    ctx=ctx)
    w = gamma ** (iters - 1.0 - jnp.arange(iters, dtype=jnp.float32))
    return jnp.sum(w * terms)


def make_loss_and_grad(cfg, iters, block, quant=None):
    """-> f(variables, batch) = (loss of the whole batch, its gradient), in
    blocks of ``block`` rows so that float32 activations fit beside each
    other: the context encoder (whose batch norm spans the batch) runs once
    over all rows, the rest block by block, and the blocks' cotangents of
    the context are pulled back through the context encoder at the end.
    The loss is a mean over rows, so equal blocks average."""

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
            lambda p, c: sequence_loss(cfg, {"params": p,
                                             "batch_stats": stats},
                                       b, iters, quant=quant, ctx=c),
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


# --------------------------------------------------------------------------
# the optimiser the chairs stage uses: clip to norm 1, AdamW, one-cycle
# --------------------------------------------------------------------------

def onecycle(step, peak, num_steps, pct=0.05, div=25.0, final_div=1e4):
    total = num_steps + 100
    init, final = peak / div, peak / div / final_div
    warm = max(int(round(pct * total)) - 1, 1)
    up = init + (peak - init) * step / warm
    down = peak + (final - peak) * (step - warm) / (total - 1 - warm)
    return jnp.where(step < warm, up, down)


def tree_norm(t):
    return jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                        for x in jax.tree_util.tree_leaves(t)))


def adamw_step(params, grads, mu, nu, step, lr, clip=1.0, b1=0.9, b2=0.999,
               eps=1e-8, wd=1e-4):
    """One update; returns (params, mu, nu, clipped grads)."""
    g_norm = tree_norm(grads)
    scale = jnp.minimum(1.0, clip / jnp.maximum(g_norm, 1e-30))
    g = jax.tree_util.tree_map(lambda x: x * scale, grads)
    mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree_util.tree_map(lambda n, x: b2 * n + (1 - b2) * x * x, nu, g)
    t = step + 1.0
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new = jax.tree_util.tree_map(
        lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps)
                                  + wd * p), params, mu, nu)
    return new, mu, nu, g


def train_steps(cfg, variables, batches, iters, lr, num_steps, quant=None,
                block=4, seconds=None):
    """Follow the first ``len(batches)`` steps from ``variables``.

    Returns per-step losses, the first clipped gradient, and the parameters
    after the last step; ``seconds`` (a list) gets each step's wall time."""
    params = variables["params"]
    stats = variables.get("batch_stats", {})

    vg = make_loss_and_grad(cfg, iters, block, quant)
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
    """Full-resolution flow of each (image1, image2) pair of one shape,
    edge-padded (centred, as the paper's evaluation pads Sintel) to
    ``pad_to`` and cut back."""
    h, w = pairs[0][0].shape[:2]
    ph, pw = pad_to[0] - h, pad_to[1] - w
    t, l = ph // 2, pw // 2
    widths = ((t, ph - t), (l, pw - l), (0, 0))
    variables = jax.device_put(variables)

    @jax.jit
    def one(variables, a, b):      # the weights are arguments, not constants
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
