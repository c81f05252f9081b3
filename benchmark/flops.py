"""Operations and bytes that RAFT needs, from shapes alone.

The benchmark's own count: it is not XLA's cost analysis (which counts
recomputation and counts a Mosaic call as nothing).  A multiply-add is two
operations.  Norms, activations and the elementwise GRU arithmetic are left
out: they are a few percent of the convolutions' work and leaving them out
can only make a share of the peak read low, never high.
"""

from __future__ import annotations


def _c(h, w, kh, kw, cin, cout):
    return 2 * h * w * kh * kw * cin * cout


def encoder_ops(cfg, H, W, out_dim):
    """One image through one encoder (stem /2, stages /2 /4 /8)."""
    h2, w2 = -(-H // 2), -(-W // 2)
    h4, w4 = -(-h2 // 2), -(-w2 // 2)
    h8, w8 = -(-h4 // 2), -(-w4 // 2)
    res = [(h2, w2), (h4, w4), (h8, w8)]
    if cfg["small"]:
        ops, cin = _c(h2, w2, 7, 7, 3, 32), 32
        for s, planes in enumerate((32, 64, 96)):
            (ho, wo), (hi, wi) = res[s], res[max(s - 1, 0)]
            for b in range(2):
                first = b == 0 and s > 0
                ih, iw = (hi, wi) if first else (ho, wo)
                p4 = planes // 4
                ops += _c(ih, iw, 1, 1, cin, p4)       # 1x1 at input res
                ops += _c(ho, wo, 3, 3, p4, p4)
                ops += _c(ho, wo, 1, 1, p4, planes)
                if first:
                    ops += _c(ho, wo, 1, 1, cin, planes)
                cin = planes
        return ops + _c(h8, w8, 1, 1, 96, out_dim)
    ops, cin = _c(h2, w2, 7, 7, 3, 64), 64
    for s, planes in enumerate((64, 96, 128)):
        ho, wo = res[s]
        for b in range(2):
            ops += _c(ho, wo, 3, 3, cin, planes) + _c(ho, wo, 3, 3, planes,
                                                      planes)
            if b == 0 and s > 0:
                ops += _c(ho, wo, 1, 1, cin, planes)
            cin = planes
    return ops + _c(h8, w8, 1, 1, 128, out_dim)


def update_ops(cfg, h, w):
    """One refinement iteration (motion encoder, GRU, flow head)."""
    planes = cfg["corr_levels"] * (2 * cfg["corr_radius"] + 1) ** 2
    hd, cd = cfg["hidden_dim"], cfg["context_dim"]
    if cfg["small"]:
        x = hd + cd + 82
        return (_c(h, w, 1, 1, planes, 96) + _c(h, w, 7, 7, 2, 64)
                + _c(h, w, 3, 3, 64, 32) + _c(h, w, 3, 3, 128, 80)
                + _c(h, w, 3, 3, x, 2 * hd) + _c(h, w, 3, 3, x, hd)
                + _c(h, w, 3, 3, hd, 128) + _c(h, w, 3, 3, 128, 2))
    x = hd + cd + 128
    return (_c(h, w, 1, 1, planes, 256) + _c(h, w, 3, 3, 256, 192)
            + _c(h, w, 7, 7, 2, 128) + _c(h, w, 3, 3, 128, 64)
            + _c(h, w, 3, 3, 256, 126)
            + 2 * (_c(h, w, 1, 5, x, 2 * hd) + _c(h, w, 1, 5, x, hd))
            + _c(h, w, 3, 3, hd, 256) + _c(h, w, 3, 3, 256, 2))


def upsample_ops(cfg, h, w):
    if cfg["small"]:
        return 2 * 2 * (8 * h) * w * h + 2 * 2 * (8 * h) * (8 * w) * w
    return (_c(h, w, 3, 3, cfg["hidden_dim"], 256) + _c(h, w, 1, 1, 256, 576)
            + 2 * h * w * 9 * 64 * 2)


def lookup_cost(cfg, h, w, store_bytes, tap_bytes, backward=False):
    """The least one pyramid lookup can do for one pair: every tap is a
    bilinear mix of 4 stored values out of a (2r+2)^2 window per level.
    Returns (operations, bytes).  Backward reads the tap cotangents and
    adds into the same windows (read and write)."""
    n, k = h * w, 2 * cfg["corr_radius"] + 1
    taps = n * cfg["corr_levels"] * k * k
    window = n * cfg["corr_levels"] * (k + 1) ** 2
    ops = taps * 8
    nbytes = window * store_bytes + taps * tap_bytes + n * 2 * 4
    if backward:
        nbytes += window * store_bytes
    return ops, nbytes


def volume_ops(cfg, h, w):
    return 2 * (h * w) ** 2 * cfg["fnet_dim"]


def forward_ops(cfg, H, W, iters, upsamples):
    """One pair, forward: two feature encodes, one context encode, the
    all-pairs volume, ``iters`` lookups + updates, ``upsamples`` upsamples
    (every iteration in training, the last one only when serving)."""
    h, w = H // 8, W // 8
    enc = (2 * encoder_ops(cfg, H, W, cfg["fnet_dim"])
           + encoder_ops(cfg, H, W, cfg["hidden_dim"] + cfg["context_dim"]))
    look = lookup_cost(cfg, h, w, 2, 2)[0]
    return (enc + volume_ops(cfg, h, w) + iters * (update_ops(cfg, h, w)
                                                   + look)
            + upsamples * upsample_ops(cfg, h, w))


def train_ops(cfg, H, W, iters):
    """Forward + backward (twice the forward), recomputation not counted."""
    return 3 * forward_ops(cfg, H, W, iters, iters)
