"""Correlation volumes, TPU-first.

Reference semantics (``core/corr.py``):

- ``CorrBlock`` (corr.py:12-60): materialize the all-pairs volume
  ``<f1(x), f2(y)> / sqrt(C)`` for every pair of 1/8-res pixels, average-pool
  it into a 4-level pyramid over the *target* dims, then per refinement step
  bilinearly sample a ``(2r+1)^2`` window around ``coords / 2^l`` at each
  level.
- ``AlternateCorrBlock`` + the CUDA kernel (corr.py:63-91,
  alt_cuda_corr/correlation_kernel.cu): never materialize the volume;
  compute windowed dot products on demand.  Because average pooling is
  linear, pooling the volume over target dims equals correlating against a
  pooled ``f2`` — the two reference paths are mathematically equivalent, and
  both are reproduced here by a single window-tap ordering contract.

TPU design:

- The all-pairs volume is one big einsum -> MXU.  Stored as
  ``(B, H1*W1, H2_l, W2_l)`` fp32 per level (reference casts corr to fp32,
  corr.py:50).
- Window lookup: bilinear sampling is linear in the correlation rows, so
  the ``(2r+1)^2`` taps factorize into two dense 1-D interpolation-weight
  mat-muls (``_sample_windows``) — no gathers (TPU gathers lower to serial
  loops).  Semantics match ``align_corners=True`` zeros-padding
  ``bilinear_sampler`` (corr.py:45) exactly.
- The memory-efficient path (``chunked_corr_lookup``) is blockwise: for a
  block of query pixels, compute its corr rows against pooled ``f2`` levels
  (small MXU matmuls), sample the windows, and discard the rows — the
  blockwise-attention pattern.  Fully differentiable (unlike the reference's
  CUDA path, whose backward is exposed but never wired: no autograd.Function
  exists, see correlation.cpp:51-54).

Window-tap ordering contract (weight-conversion parity): the reference
builds ``delta = stack(meshgrid(dy, dx))`` and adds it to ``(x, y)``
centroids (corr.py:36-41), so tap ``(i, j)`` of the window samples
displacement ``(dx = i - r, dy = j - r)`` — the *first* window axis walks x.
We reproduce that exactly; channel layout of the lookup output is
``level-major, then i (x-offset), then j (y-offset)``.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Quantized pyramid storage (int8 today, fp8 = a dtype swap)
#
# The correlation volume is pure *data* between its producer (one einsum)
# and its consumer (a linear window-sampling pass), so storage can drop
# below bf16 as long as the sampling re-accumulates fp32: store
# ``q = round(corr / scale)`` in int8 with a per-level symmetric scale
# calibrated from the level's correlation row maxima, and because the
# bilinear window sampling is LINEAR in the stored values, dequantization
# fuses into the lookup as one multiply on the sampled taps —
# ``taps_fp32 = sample(q) * scale`` — instead of ever materializing a
# dequantized volume.  fp8 variants reuse the identical path with a
# different ``(dtype, qmax)`` pair (no rounding to an integer grid; the
# cast itself rounds), which is why the spec table below is the ONLY
# place a new storage dtype has to be added.
#
# The quantize boundary is wrapped in stop_gradient: the stored values
# are integers (no tangent space), so gradients do not flow through the
# volume to the feature encoder — mirroring the reference's alternate
# CUDA path, whose backward kernel exists but is never wired
# (correlation.cpp:51-54).  Quantized storage is therefore an
# inference/serving optimization first; training with it keeps finite
# grads everywhere (the context encoder + update block still learn) but
# freezes fnet's correlation gradient.  See RAFTConfig.corr_dtype.
# ---------------------------------------------------------------------------

class QuantizedLevel(NamedTuple):
    """One quantized pyramid level: raw codes + the dequant scale.

    ``values``: same layout as the fp32 level it replaces (either
    ``(B, N, H, W)`` query-major or ``(B, H, W, Npad)`` query-minor),
    stored in the quantized dtype.
    ``scale``: ``(B, 1, 1, 1)`` fp32 per-batch-element dequant scale
    (symmetric: ``corr ≈ values * scale``), broadcastable against
    ``values`` in either layout.
    """

    values: jax.Array
    scale: jax.Array


CorrLevel = Union[jax.Array, QuantizedLevel]

# name -> (jnp dtype, qmax).  qmax is the largest magnitude the code
# space represents: 127 for int8; the fp8 formats use their finite max
# (448 for e4m3fn, 57344 for e5m2) so the scale maps the calibrated row
# maximum onto the top of the representable range.
_QUANT_SPECS = {
    "int8": (jnp.int8, 127.0),
    "float8_e4m3fn": (getattr(jnp, "float8_e4m3fn", None), 448.0),
    "float8_e5m2": (getattr(jnp, "float8_e5m2", None), 57344.0),
}


def corr_quant_spec(name: str):
    """``(dtype, qmax)`` for a quantized corr storage dtype name, or
    ``None`` when ``name`` is a plain (non-quantized) dtype.  Accepts
    strings, numpy/jnp dtype objects, and dtype classes."""
    try:
        name = str(np.dtype(name))   # normalize classes + instances
    except TypeError:
        name = str(name)             # 'auto' etc. — not a dtype at all
    spec = _QUANT_SPECS.get(name)
    if spec is None:
        return None
    dtype, qmax = spec
    if dtype is None:
        raise ValueError(
            f"corr_dtype={name!r} needs jax.numpy.{name} which this "
            "jax/ml_dtypes build does not provide")
    return dtype, qmax


def quantize_corr_level(corr: jax.Array, spec) -> QuantizedLevel:
    """Calibrate + quantize one fp32 pyramid level.

    Calibration is the per-level symmetric scale from the row maxima:
    ``scale = max_rows(max_x |corr_row|) / qmax`` per batch element (the
    max over rows of per-row maxima == the level max; computed that way
    so a future per-row scale refinement is a reduction-axis change).
    Wrapped in stop_gradient — see the module section comment.
    """
    dtype, qmax = spec
    c = jax.lax.stop_gradient(corr.astype(jnp.float32))
    if corr.size == 0:
        # Empty (over-pooled) trailing level: nothing to calibrate; the
        # lookups zero-fill its taps regardless of the scale.
        return QuantizedLevel(
            jnp.zeros(corr.shape, dtype),
            jnp.ones((corr.shape[0], 1, 1, 1), jnp.float32))
    # Row maxima (max |corr| over the trailing target axes), then the
    # level max over rows; keepdims so the scale broadcasts in both the
    # query-major and query-minor layouts.
    amax = jnp.max(jnp.abs(c), axis=(1, 2, 3), keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    q = c / scale
    if jnp.issubdtype(dtype, jnp.integer):
        q = jnp.round(q)
    q = jnp.clip(q, -qmax, qmax).astype(dtype)
    return QuantizedLevel(q, scale)


def dequantize_level(level: CorrLevel) -> jax.Array:
    """fp32 view of a pyramid level (tests/debugging; the hot lookups
    never call this — they fuse the scale into the sampled taps)."""
    if isinstance(level, QuantizedLevel):
        return level.values.astype(jnp.float32) * level.scale
    return level.astype(jnp.float32)


def _level_array(level: CorrLevel) -> jax.Array:
    return level.values if isinstance(level, QuantizedLevel) else level


def _store_level(corr: jax.Array, out_dtype, quant_spec) -> CorrLevel:
    """Round one fp32 level into its storage form (cast or quantize)."""
    if quant_spec is not None:
        return quantize_corr_level(corr, quant_spec)
    return corr.astype(out_dtype)


def resolve_precision(precision) -> jax.lax.Precision:
    """'default' | 'high' | 'highest' -> lax.Precision.

    'highest' (fp32) is the config default (RAFTConfig.corr_precision):
    the reference keeps fp32 correlation too (corr.py:50), and every
    cell of PERF.md runs it; no cell times 'high' or 'default'.
    """
    if isinstance(precision, jax.lax.Precision):
        return precision
    return {"default": jax.lax.Precision.DEFAULT,
            "high": jax.lax.Precision.HIGH,
            "highest": jax.lax.Precision.HIGHEST}[precision]


def all_pairs_correlation(fmap1: jax.Array, fmap2: jax.Array,
                          precision="highest") -> jax.Array:
    """``(B, H, W, C) x (B, H, W, C) -> (B, H1*W1, H2, W2)`` fp32 volume."""
    B, H, W, C = fmap1.shape
    f1 = fmap1.reshape(B, H * W, C).astype(jnp.float32)
    f2 = fmap2.reshape(B, H * W, C).astype(jnp.float32)
    corr = jnp.einsum("bnc,bmc->bnm", f1, f2,
                      precision=resolve_precision(precision),
                      preferred_element_type=jnp.float32)
    # Reciprocal-MULTIPLY, not divide: TPU divide is a multi-pass VPU op
    # and XLA does not strength-reduce fp division by a constant; the
    # divide over the full (HW)^2 volume profiled at ~3.5 ms/step
    # (fwd+transpose) at the chairs bench shape.
    corr = corr * (1.0 / float(C) ** 0.5)
    return corr.reshape(B, H * W, H, W)


def _avg_pool_2x2(x: jax.Array) -> jax.Array:
    """2x2/stride-2 average pool over the last two spatial dims of
    ``(B, N, H, W)``; odd trailing row/col dropped (torch avg_pool2d)."""
    B, N, H, W = x.shape
    H2, W2 = H // 2, W // 2
    x = x[:, :, : H2 * 2, : W2 * 2]
    x = x.reshape(B, N, H2, 2, W2, 2)
    return x.sum(axis=(3, 5)) * 0.25   # sum*0.25: no divide pass


def _split_bf16(x: jax.Array):
    """``x`` (float32) as ``hi + lo``, both bfloat16: ``hi`` its rounding,
    ``lo`` the rounding of the rest (16 bits of ``x``'s mantissa)."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _bf16_product(spec, a, b, precision):
    return jnp.einsum(spec, a, b, precision=precision,
                      preferred_element_type=jnp.bfloat16)


def _bf16_product_fwd(spec, a, b, precision):
    return _bf16_product(spec, a, b, precision), (a, b)


def _bf16_product_bwd(spec, precision, res, g):
    a, b = res
    ins, out = spec.split("->")
    sa, sb = ins.split(",")

    def grad(other, s_other, s_self):
        hi, lo = _split_bf16(other)
        f = f"{out},{s_other}->{s_self}"
        return (jnp.einsum(f, g, hi, preferred_element_type=jnp.float32)
                + jnp.einsum(f, g, lo, preferred_element_type=jnp.float32))

    return grad(b, sb, sa), grad(a, sa, sb)


_bf16_product.defvjp(_bf16_product_fwd, _bf16_product_bwd)


def _bf16_level(fmap1: jax.Array, fmap2: jax.Array, precision,
                pad_q=None) -> jax.Array:
    """The level of a ONE-level pyramid stored in bfloat16, query-major
    (``pad_q`` None) or query-minor and padded: ``F1 F2^T / sqrt(C)``
    from float32 features, written in bfloat16 by the product itself.
    With no pooling after it, nothing needs the float32 volume that the
    multi-level builders round from: at 1/4 of a 384x512 crop that is
    4.8 GB at batch 8.  The backward multiplies the bfloat16 cotangent
    with each float32 operand as two bfloat16 halves, so no array of the
    volume's size is float32 there either; the gradients agree with the
    float32 build's to ~1e-5."""
    B, H, W, C = fmap1.shape
    a = fmap1.reshape(B, H * W, C).astype(jnp.float32) * (1.0 / float(C) ** 0.5)
    b = fmap2.astype(jnp.float32)
    precision = resolve_precision(precision)
    if pad_q is None:
        return _bf16_product("bqc,byxc->bqyx", a, b, precision)
    a = jnp.pad(a, ((0, 0), (0, (-H * W) % pad_q), (0, 0)))
    return _bf16_product("byxc,bqc->byxq", b, a, precision)


def _one_bf16_level(num_levels: int, out_dtype, quant) -> bool:
    return (num_levels == 1 and quant is None
            and jnp.dtype(out_dtype) == jnp.bfloat16)


def build_corr_pyramid(fmap1: jax.Array, fmap2: jax.Array,
                       num_levels: int = 4,
                       precision="highest",
                       out_dtype=jnp.float32) -> List[CorrLevel]:
    """Materialized pyramid: level l is ``(B, H1*W1, H/2^l, W/2^l)``.

    ``out_dtype``: STORAGE dtype of the levels (``RAFTConfig.corr_dtype``
    semantics, same as :func:`build_corr_pyramid_flat` — pooling math
    stays fp32 and the lookup re-accumulates fp32; only stored values
    round).  Quantized names ('int8', fp8) yield
    :class:`QuantizedLevel` pairs with a per-level calibrated scale.
    One bfloat16 level is written in bfloat16 directly
    (:func:`_bf16_level`)."""
    quant = corr_quant_spec(out_dtype)
    if _one_bf16_level(num_levels, out_dtype, quant):
        return [_bf16_level(fmap1, fmap2, precision)]
    corr = all_pairs_correlation(fmap1, fmap2, precision)
    pyramid = [_store_level(corr, out_dtype, quant)]
    for _ in range(num_levels - 1):
        corr = _avg_pool_2x2(corr)
        pyramid.append(_store_level(corr, out_dtype, quant))
    return pyramid


def _avg_pool_2x2_qminor(x: jax.Array) -> jax.Array:
    """2x2/stride-2 average pool over the LEADING spatial dims of
    ``(B, H, W, N)``; odd trailing row/col dropped (torch avg_pool2d)."""
    B, H, W, N = x.shape
    H2, W2 = H // 2, W // 2
    x = x[:, : H2 * 2, : W2 * 2, :]
    x = x.reshape(B, H2, 2, W2, 2, N)
    return x.sum(axis=(2, 4)) * 0.25   # sum*0.25: no divide pass


def build_corr_pyramid_flat(fmap1: jax.Array, fmap2: jax.Array,
                            num_levels: int = 4, precision="highest",
                            pad_q: int = 128,
                            out_dtype=jnp.float32) -> List[CorrLevel]:
    """Materialized pyramid in QUERY-MINOR layout: level l is
    ``(B, H/2^l, W/2^l, Npad)`` with the flattened query dim zero-padded
    to a multiple of ``pad_q``.

    Same math as :func:`build_corr_pyramid` (padding ``fmap1`` with zero
    rows just appends all-zero correlation columns); the layout feeds
    :func:`raft_tpu.ops.pallas_corr.pallas_pyramid_lookup`.  Query-minor
    matters on TPU: with the target width in the minor dim, a chairs-crop
    level 2 is (.., 11, 15) and every (8, 128) tile is >8x padding —
    profiled round 2 at 66 GiB/s effective on the dcorr writes.  With
    queries minor the lane dim is Npad (a multiple of 128) and every
    level tiles densely.  One bfloat16 level is written in bfloat16
    directly (:func:`_bf16_level`)."""
    if _one_bf16_level(num_levels, out_dtype, corr_quant_spec(out_dtype)):
        return [_bf16_level(fmap1, fmap2, precision, pad_q)]
    B, H, W, C = fmap1.shape
    N = H * W
    n_pad = (-N) % pad_q
    f1 = fmap1.reshape(B, N, C).astype(jnp.float32)
    if n_pad:
        f1 = jnp.pad(f1, ((0, 0), (0, n_pad), (0, 0)))
    f2 = fmap2.astype(jnp.float32)
    corr = jnp.einsum("byxc,bqc->byxq", f2, f1,
                      precision=resolve_precision(precision),
                      preferred_element_type=jnp.float32)
    corr = corr * (1.0 / float(C) ** 0.5)   # mul, not divide (see above)
    # Pyramid math (pooling) stays fp32; only the STORED levels round to
    # ``out_dtype`` (XLA fuses the casts into the einsum/pool epilogues).
    # Quantized storage rides the same seam: the calibration max and the
    # round-to-code also fuse into the pool epilogue.
    quant = corr_quant_spec(out_dtype)
    pyramid = [_store_level(corr, out_dtype, quant)]
    for _ in range(num_levels - 1):
        corr = _avg_pool_2x2_qminor(corr)
        pyramid.append(_store_level(corr, out_dtype, quant))
    return pyramid


def _interp_weights_1d(c: jax.Array, n: int, radius: int) -> jax.Array:
    """Dense bilinear interpolation weights along one axis.

    ``w[..., t, p] = max(0, 1 - |c + t - r - p|)`` for positions
    ``p in [0, n)`` — each window tap has <=2 nonzero weights (the two
    neighboring pixels) and out-of-bounds taps get all-zero rows, which is
    exactly ``grid_sample(align_corners=True, padding='zeros')``
    (reference utils.py:57-65).
    """
    k = 2 * radius + 1
    taps = jnp.arange(k, dtype=jnp.float32) - radius
    pos = jnp.arange(n, dtype=jnp.float32)
    return jnp.maximum(
        0.0, 1.0 - jnp.abs(c[..., None, None] + taps[:, None] - pos))


def _sample_windows(corr: jax.Array, coords: jax.Array,
                    radius: int, precision="highest") -> jax.Array:
    """Bilinear window sampling as two batched mat-muls (MXU-friendly).

    Bilinear interpolation is linear in the image, so the ``(2r+1)^2``
    window taps factorize into dense 1-D weight matrices contracted
    against the correlation rows — no gathers (TPU gathers lower to serial
    loops; this formulation is the reason the lookup is fast on TPU, and
    the same math the Pallas kernel uses).

    Args:
      corr: ``(B, N, H, W)`` one pyramid level (N query pixels).
      coords: ``(B, N, 2)`` query centroids in this level's pixel units.

    Returns:
      ``(B, N, (2r+1)^2)`` sampled taps, x-major tap order.
    """
    B, N, H, W = corr.shape
    K = 2 * radius + 1
    c = coords.astype(jnp.float32)
    wx = _interp_weights_1d(c[..., 0], W, radius)     # (B, N, K, W)
    wy = _interp_weights_1d(c[..., 1], H, radius)     # (B, N, K, H)
    # a(b,n,j,x) = sum_y wy(b,n,j,y) corr(b,n,y,x)
    prec = resolve_precision(precision)
    a = jnp.einsum("bnjy,bnyx->bnjx", wy, corr.astype(jnp.float32),
                   precision=prec, preferred_element_type=jnp.float32)
    # tap(b,n,i,j) = sum_x wx(b,n,i,x) a(b,n,j,x)
    taps = jnp.einsum("bnix,bnjx->bnij", wx, a,
                      precision=prec, preferred_element_type=jnp.float32)
    return taps.reshape(B, N, K * K)


def corr_lookup(pyramid: Sequence[CorrLevel], coords: jax.Array,
                radius: int, precision="highest") -> jax.Array:
    """Sample the materialized pyramid (reference ``CorrBlock.__call__``).

    Args:
      pyramid: from :func:`build_corr_pyramid`.  Levels may be plain
        arrays (fp32/bf16 storage) or :class:`QuantizedLevel` pairs —
        sampling is linear in the stored values, so dequantization is
        ONE multiply on the sampled taps (never a dequantized volume).
      coords: ``(B, H1, W1, 2)`` target coordinates in level-0 pixel units,
        last axis ``(x, y)``.

    Returns:
      ``(B, H1, W1, levels * (2r+1)^2)`` fp32 features.
    """
    B, H1, W1, _ = coords.shape
    c = coords.reshape(B, H1 * W1, 2).astype(jnp.float32)
    outs = []
    for lvl, corr in enumerate(pyramid):
        taps = _sample_windows(_level_array(corr), c / (2.0 ** lvl),
                               radius, precision)
        if isinstance(corr, QuantizedLevel):
            # Fused dequant: taps are a linear map of the codes.
            taps = taps * corr.scale.reshape(B, 1, 1)
        outs.append(taps)
    out = jnp.concatenate(outs, axis=-1)
    return out.reshape(B, H1, W1, -1)


def pool_fmap_pyramid(fmap2: jax.Array, num_levels: int) -> List[jax.Array]:
    """Pooled target features ``(B, H_l, W_l, C)`` per level.  By linearity,
    correlating against pooled f2 == pooling the corr volume (reference
    AlternateCorrBlock pools fmaps, corr.py:68-72)."""
    levels = [fmap2]
    cur = fmap2
    for _ in range(num_levels - 1):
        t = cur.transpose(0, 3, 1, 2)          # (B, C, H, W)
        t = _avg_pool_2x2(t)
        cur = t.transpose(0, 2, 3, 1)
        levels.append(cur)
    return levels


def chunked_corr_lookup(fmap1: jax.Array, fmap2_pyramid: Sequence[jax.Array],
                        coords: jax.Array, radius: int,
                        block_size: int = 256,
                        precision="highest") -> jax.Array:
    """On-demand blockwise correlation lookup (memory-efficient path).

    Never materializes the ``O((HW)^2)`` volume: for each block of query
    pixels, computes its correlation rows against each pooled ``f2`` level
    (an MXU matmul), samples the ``(2r+1)^2`` window, and moves on.  The TPU
    analogue of the reference's ``alt_cuda_corr`` kernel (C6), but
    differentiable end-to-end via autodiff through the blockwise scan.

    Args:
      fmap1: ``(B, H1, W1, C)`` query features (always full resolution,
        reference corr.py:82).
      fmap2_pyramid: from :func:`pool_fmap_pyramid`.
      coords: ``(B, H1, W1, 2)`` in level-0 pixel units.
      block_size: query pixels per block.

    Returns:
      ``(B, H1, W1, levels * (2r+1)^2)`` fp32 features.
    """
    B, H1, W1, C = fmap1.shape
    N = H1 * W1
    K = 2 * radius + 1
    L = len(fmap2_pyramid)
    scale = 1.0 / jnp.sqrt(jnp.float32(C))

    f1 = fmap1.reshape(B, N, C).astype(jnp.float32)
    c = coords.reshape(B, N, 2).astype(jnp.float32)

    # Pad N up to a multiple of block_size so the scan has static shape.
    nblocks = -(-N // block_size)
    pad = nblocks * block_size - N
    f1 = jnp.pad(f1, ((0, 0), (0, pad), (0, 0)))
    c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    f1 = f1.reshape(B, nblocks, block_size, C)
    c = c.reshape(B, nblocks, block_size, 2)

    f2_flat = [lvl.astype(jnp.float32) for lvl in fmap2_pyramid]

    def block_fn(carry, blk):
        f1_b, c_b = blk  # (B, bs, C), (B, bs, 2)
        outs = []
        for lvl, f2 in enumerate(f2_flat):
            Bf, Hl, Wl, _ = f2.shape
            rows = jnp.einsum("bnc,bhwc->bnhw", f1_b, f2,
                              precision=resolve_precision(precision),
                              preferred_element_type=jnp.float32) * scale
            outs.append(_sample_windows(
                rows.reshape(B, block_size, Hl, Wl),
                c_b / (2.0 ** lvl), radius, precision))
        return carry, jnp.concatenate(outs, axis=-1)

    _, out = jax.lax.scan(
        block_fn, None,
        (f1.transpose(1, 0, 2, 3), c.transpose(1, 0, 2, 3)))
    # out: (nblocks, B, bs, L*K*K) -> (B, N, L*K*K)
    out = out.transpose(1, 0, 2, 3).reshape(B, nblocks * block_size, -1)
    out = out[:, :N]
    return out.reshape(B, H1, W1, L * K * K)
