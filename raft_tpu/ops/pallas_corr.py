"""Fused on-demand correlation lookup as Pallas TPU kernels.

The TPU-native replacement for the reference's ``alt_cuda_corr`` extension
(``alt_cuda_corr/correlation_kernel.cu:19-256``, SURVEY.md C6) — and,
unlike the reference (whose backward kernel exists but is never wired into
autograd, correlation.cpp:51-54), fully differentiable via
``jax.custom_vjp``.

Math redesign for the MXU (no gathers, no scatters, no atomics):

For one pyramid level with pooled target features ``f2 (Hl*Wl, C)``, query
features ``f1 (N, C)`` and window centroids ``c = coords / 2^l``:

    rows(q, y, x) = <f1_q, f2[y, x]> / sqrt(C)          (MXU matmul)
    tap(q, i, j)  = bilinear(rows(q), c_q + (i - r, j - r))

Bilinear sampling with zeros padding is a *linear* map of the row image, so
it factorizes into two dense 1-D interpolation matrices:

    wx(q, i, x) = max(0, 1 - |c_q.x + i - r - x|)       (BQ, K, Wl)
    wy(q, j, y) = max(0, 1 - |c_q.y + j - r - y|)       (BQ, K, Hl)
    tap(q,i,j)  = sum_{y,x} wy(q,j,y) * rows(q,y,x) * wx(q,i,x)

i.e. two batched mat-muls per level — the gather-heavy CUDA design
(dynamic ``floor(coords)`` windows + bilinear *scatter* with shared-memory
staging, correlation_kernel.cu:55-114) becomes three MXU contractions, and
out-of-bounds taps fall out as zero weights (the sampler's zeros-padding
semantics, utils.py:57-65).  The backward pass is the transpose of the
same contractions; ``coords`` gets zero gradient by design, matching the
per-iteration ``coords1.detach()`` truncation (raft.py:123) and the CUDA
kernel's never-filled ``coords_grad`` (correlation_kernel.cu:307).

Layout contract: tap order is x-major (``i`` walks x), levels concatenated
level-major — identical to ``raft_tpu.ops.corr`` and the reference
(corr.py:36-41).

Blocking: queries are processed in ``block_q`` chunks (grid = (B, N/BQ));
one fused kernel instance holds EVERY level's ``f2`` and one query block's
rows in VMEM.  The correlation volume never exists in HBM.

Compile-time lesson (round 2, RESOLVED): the original kernels took
>10-40 minutes of Mosaic compile at every shape.  The cause was
1-D vector layouts — deriving ``cx/cy`` as ``(BQ,)`` vectors gives
Mosaic "implicit dimension" layouts whose reductions it either rejects
("unsupported output implicit dimension") or compiles pathologically
slowly.  With every tap-center kept 2-D ``(1, BQ)`` (coords passed
query-minor ``(B, 2, Npad)``, exactly like the pyramid kernels), the
fused forward compiles in ~3 s and the backward in ~8 s.  Two related
Mosaic constraints learned on the way and kept in the code: mat-muls
must stay OUT of fori_loop bodies (one hoisted dot per level into a
VMEM scratch ref), and values cannot be dynamic_slice'd — tile passes
over materialized blocks need scratch REFS.

VMEM sizing: beyond-HBM shapes auto-drop the ``f2`` blocks to bf16
(fp32 accumulation) once fp32 ``f2`` + correlation scratch would
exceed ~48 MB (``_odm_f2_dtype``) — at the 1440x2560 target the fp32
form (~118 MB) cannot fit the budget.

Backward tiling (round 4 — removes the round-3 VMEM ceiling): the
FUSED backward holds every level's ``f2`` + ``df2`` + drows scratch in
VMEM per instance, which stops compiling at >=1088x1920 (level 0 alone
is 33-56 MB fp32, BENCH_BEYOND_HBM_r03.json).  ``_corr_bwd`` therefore
estimates the fused residency and moves oversized levels onto a BLOCKED
per-level pair (the TPU answer to the CUDA backward's tiled atomicAdd
accumulation, correlation_kernel.cu:123-256):

- ``_odm_bwd_df1_blocked_kernel``  grid (B, QB, TY): ``f2`` streams
  through VMEM in ``(tile_h, Wl)`` row tiles; the ``df1`` block (index
  constant across the innermost tile dim) accumulates in VMEM.
- ``_odm_bwd_df2_blocked_kernel``  grid (B, TY, QB): one ``df2``
  spatial tile (index constant across the innermost query dim)
  accumulates in VMEM while f1/coords/g stream.

Both kernels skip a (query block, row tile) pair entirely when no query
window can overlap the tile's rows (``_tile_overlaps`` — a min/max
bound on the block's ``cy``): ``drows`` for such a pair is exactly
zero, so the skip is lossless, and because query blocks are
raster-ordered their windows cluster in y — at bounded flow the dense
contraction sparsifies by roughly Hl / (window + flow extent).
"""

from __future__ import annotations

import functools
import os
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops.pallas_util import (BATCH, WHOLE, per_data_shard,
                                      tpu_pallas_call)


# Image rows per inner mat-mul tile; statically unrolled inside, fori_loop
# across tiles (full unroll over Hl explodes Mosaic compile time, per-row
# mat-muls are latency-bound).
_Y_TILE = 8


def _tap_weight(c: jax.Array, offset, pos) -> jax.Array:
    """Bilinear weight ``max(0, 1 - |c + offset - pos|)`` (zeros padding
    falls out as all-zero weights for out-of-range taps)."""
    return jnp.maximum(0.0, 1.0 - jnp.abs(c + offset - pos))


def _odm_fwd_level_body(f2_ref, f1, c_ref, out_ref, scratch_ref, lvl, off,
                        hl, wl, k, inv_scale):
    """One level of the fused on-demand forward: ONE (Hl*Wl, C) x
    (C, BQ) mat-mul materializes this level's correlation block into a
    VMEM scratch ref (<=1.5 MB at block_q=128), then the tap pass is
    the same pure-VPU tile loop as the pyramid kernel.  Mat-muls must
    stay OUT of the fori_loop bodies: the original row-streamed design
    (a dot per y-tile) made Mosaic compile time explode past 10-minute
    budgets even for a single standalone lookup.  (The scratch ref is
    needed because Mosaic cannot dynamic_slice VALUES, only refs.)"""
    bq = f1.shape[0]
    r = (k - 1) // 2
    lvl_div = 1.0 / (2.0 ** lvl)
    # Keep the tap centers 2-D (1, BQ): Mosaic represents 1-D vectors
    # with an implicit dim and rejects reductions mixing those layouts
    # ("unsupported output implicit dimension") — the pyramid kernel
    # compiles exactly this math with everything 2-D.
    cx = c_ref[0, 0:1, :] * lvl_div     # (1, BQ)
    cy = c_ref[0, 1:2, :] * lvl_div
    posx = jax.lax.broadcasted_iota(jnp.int32, (wl, bq), 0) \
        .astype(jnp.float32)            # (Wl, BQ)
    C = f1.shape[-1]

    f2m = f2_ref[0].reshape(hl * wl, C)
    scratch_ref[...] = jax.lax.dot_general(
        f2m, f1.astype(f2m.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * inv_scale     # (Hl*Wl, BQ)

    t_y = min(_Y_TILE, hl)
    n_tiles = hl // t_y

    def _tile_taps(y0f, yis, blk, acc):
        for yi in yis:
            row = blk[yi * wl:(yi + 1) * wl, :]
            for j in range(k):
                acc[j] += _tap_weight(cy, j - r - yi, y0f) * row
        return acc

    def tile_body(t, acc):
        blk = scratch_ref[pl.ds(t * t_y * wl, t_y * wl), :]
        return _tile_taps((t * t_y).astype(jnp.float32), range(t_y), blk,
                          acc)

    acc = jax.lax.fori_loop(
        0, n_tiles, tile_body,
        [jnp.zeros((wl, bq), jnp.float32) for _ in range(k)])
    if hl % t_y:  # static remainder rows
        rem = hl - hl % t_y
        acc = _tile_taps(jnp.float32(rem), range(hl - rem),
                         scratch_ref[rem * wl:, :], acc)

    # Contract x with a ones-row mat-mul: the keepdims sublane-sum form
    # hits Mosaic "unsupported output implicit dimension" at some widths
    # (observed at wl=120 — a common 960-px-frame level-0 width) while
    # (1, Wl) @ (Wl, BQ) compiles at every width; with the 2-D tap-center
    # layouts above, compile time is seconds either way.
    ones_row = jnp.ones((1, wl), jnp.float32)
    for i in range(k):
        wx_i = _tap_weight(cx, float(i - r), posx)  # (Wl, BQ)
        for j in range(k):
            out_ref[0, off + i * k + j:off + i * k + j + 1, :] = \
                jax.lax.dot_general(
                    ones_row, wx_i * acc[j], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)


def _odm_fwd_kernel(*refs, levels, k, kk_total, inv_scale):
    """Fused on-demand forward over every non-empty level (ONE
    pallas_call per lookup instead of one per level — the per-call
    overhead dominated the small levels).  refs =
    [f2_0..f2_{n-1}, f1, c, out, scratch_0..scratch_{n-1}];
    out: (1, L*k*k, BQ) query-minor."""
    nl = len(levels)
    f1_ref, c_ref, out_ref = refs[nl], refs[nl + 1], refs[nl + 2]
    scratch_refs = refs[nl + 3:]
    f1 = f1_ref[0]                      # (BQ, C)
    covered = 0
    for (lvl, off, hl, wl), f2_ref, scratch_ref in zip(levels, refs[:nl],
                                                       scratch_refs):
        _odm_fwd_level_body(f2_ref, f1, c_ref, out_ref, scratch_ref, lvl,
                            off, hl, wl, k, inv_scale)
        covered += k * k
    if covered < kk_total:  # empty (over-pooled) trailing levels
        out_ref[0, covered:, :] = jnp.zeros(
            (kk_total - covered, f1.shape[0]), jnp.float32)


def _odm_bwd_level_body(f2_ref, df2_ref, scratch_ref, f1, c_ref, g_ref,
                        lvl, off, hl, wl, k, inv_scale, is_first_block,
                        df1):
    """One level of the fused on-demand backward: per image row y,
    ``drows_y(x, q) = sum_ij g(i,j,q) wx_i(x,q) wy_j(y,q)`` feeds two
    mat-muls — ``df1 += drows @ f2`` and ``df2[y-tile] += drows^T-style
    contraction over queries`` (accumulated across query blocks; the TPU
    grid runs sequentially, so no atomics are needed — unlike the
    reference's atomicAdd scatter, correlation_kernel.cu:237)."""
    bq = f1.shape[0]
    r = (k - 1) // 2
    lvl_div = 1.0 / (2.0 ** lvl)
    cx = c_ref[0, 0:1, :] * lvl_div     # (1, BQ) — 2-D, see fwd body
    cy = c_ref[0, 1:2, :] * lvl_div
    posx = jax.lax.broadcasted_iota(jnp.int32, (wl, bq), 0) \
        .astype(jnp.float32)

    # b_j(x, q) = sum_i wx_i(x, q) g(i*k+j, q)
    b = [
        sum(_tap_weight(cx, float(ti - r), posx)
            * g_ref[0, off + ti * k + tj:off + ti * k + tj + 1, :]
            for ti in range(k))
        for tj in range(k)
    ]                                    # K_j x (Wl, BQ)

    @pl.when(is_first_block)
    def _():
        df2_ref[0] = jnp.zeros_like(df2_ref[0])

    C = f1.shape[-1]
    t_y = min(_Y_TILE, hl)
    n_tiles = hl // t_y

    # Assemble the full (Hl*Wl, BQ) drows image into a VMEM scratch ref
    # with a pure-VPU tile loop, then TWO mat-muls for the whole level —
    # mat-muls in fori bodies blow up Mosaic compile time (see forward
    # body), and Mosaic cannot dynamic_update_slice VALUES, only refs.
    def _tile_rows(y0f, yis):
        return jnp.concatenate([
            sum(_tap_weight(cy, tj - r - yi, y0f) * b[tj]
                for tj in range(k))
            for yi in yis
        ], axis=0) * inv_scale                           # (T*Wl, BQ)

    def tile_body(t, _):
        scratch_ref[pl.ds(t * t_y * wl, t_y * wl), :] = _tile_rows(
            (t * t_y).astype(jnp.float32), range(t_y))
        return 0

    jax.lax.fori_loop(0, n_tiles, tile_body, 0)
    if hl % t_y:  # static remainder rows
        rem = hl - hl % t_y
        scratch_ref[rem * wl:, :] = _tile_rows(jnp.float32(rem),
                                               range(hl - rem))

    drows = scratch_ref[...]
    f2_flat = f2_ref[0].reshape(hl * wl, C).astype(jnp.float32)
    df1 = df1 + jax.lax.dot_general(
        drows, f2_flat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (BQ, C)
    df2_ref[0] += jax.lax.dot_general(
        drows, f1, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(hl, wl, C)
    return df1


def _odm_bwd_kernel(*refs, levels, k, inv_scale):
    """Fused on-demand backward; refs = [f2_0.., f1, c, g, df1, df2_0..,
    scratch_0..].  ``df1`` accumulates across levels in registers and is
    written once; each level's ``df2`` accumulates across query blocks
    in HBM (sequential grid)."""
    nl = len(levels)
    f1_ref, c_ref, g_ref, df1_ref = refs[nl], refs[nl + 1], refs[nl + 2], \
        refs[nl + 3]
    df2_refs = refs[nl + 4:nl + 4 + nl]
    scratch_refs = refs[nl + 4 + nl:]
    f1 = f1_ref[0]
    is_first = pl.program_id(1) == 0
    df1 = jnp.zeros((f1.shape[0], f1.shape[1]), jnp.float32)
    for (lvl, off, hl, wl), f2_ref, df2_ref, scr in zip(
            levels, refs[:nl], df2_refs, scratch_refs):
        df1 = _odm_bwd_level_body(f2_ref, df2_ref, scr, f1, c_ref, g_ref,
                                  lvl, off, hl, wl, k, inv_scale,
                                  is_first, df1)
    df1_ref[0] = df1


# --- Blocked backward (beyond-HBM shapes) --------------------------------

# Per-instance VMEM budget above which the fused backward stops being
# offered a level (round 3 measured compile OOM at ~111 MB estimated
# residency against the 100 MB limit; 736x1280 at ~51 MB compiles).
_FUSED_BWD_BUDGET = 78 * 1024 * 1024
_BWD_TILE_H = 8          # f2 rows per streamed tile
_BWD_BLOCK_Q = 512       # query block of the blocked kernels (bigger than
                         # the fused 128: f2 re-streams once per query
                         # block in the df1 kernel, so fewer blocks =
                         # proportionally less DMA.  The block fetch is
                         # unconditional — _tile_overlaps skips COMPUTE,
                         # not DMA — so at 1440x2560 level 0 (bf16 f2 =
                         # 29.5 MB, 113 blocks at 512) the df1 kernel
                         # moves ~3.3 GB/call; 1024 halves that for
                         # ~24 MB more VMEM working set (drows + b_j
                         # doubling), still under the 100 MB limit.
                         # Override per-run with RAFT_ODM_BWD_BLOCK_Q to
                         # sweep on hardware (never swept on a chip).


def _fused_bwd_est(nonempty, block_q, k):
    """Estimated per-instance VMEM bytes of the FUSED backward: every
    level's f2 + df2 + drows scratch resident, plus query blocks and the
    b_j working set at the widest level."""
    if not nonempty:
        return 0
    C = nonempty[0][1].shape[-1]
    rows = sum(f2.shape[1] * f2.shape[2] for _, f2 in nonempty)
    f2b = 2 if _odm_f2_dtype(nonempty, block_q) == jnp.bfloat16 else 4
    wl0 = max(f2.shape[2] for _, f2 in nonempty)
    return (rows * C * (f2b + 4)                 # f2 + fp32 df2
            + rows * block_q * 4                 # drows scratch
            + (k + 2) * wl0 * block_q * 4        # b_j + posx working set
            + block_q * 4 * (2 * C + 2 + len(nonempty) * k * k))


def _partition_bwd_levels(nonempty, block_q, k):
    """Partition levels for the backward: fused while the whole set fits
    the VMEM budget, biggest levels (level 0 first — pyramid sizes
    descend) onto the blocked per-level pair beyond it.  At <=736x1280
    everything stays fused; 1088x1920+ moves level 0 (and, if ever
    needed, more) out — the round-3 compile ceiling.

    Returns ``(blocked, fused)`` lists of ``(lvl, f2)`` pairs."""
    fused = list(nonempty)
    blocked = []
    while fused and _fused_bwd_est(fused, block_q, k) > _FUSED_BWD_BUDGET:
        blocked.append(fused.pop(0))
    return blocked, fused


def _tile_overlaps(c_ref, lvl, r, tile_h, t):
    """True iff ANY query in this block has a window row intersecting
    f2 rows [t*tile_h, (t+1)*tile_h).  Each query touches rows
    [cy - r - 1, cy + r + 1] (bilinear spreads one row past the tap
    radius); padded queries sit at -1e6 and never extend the max."""
    cy = c_ref[0, 1:2, :] * (1.0 / 2.0 ** lvl)
    y0 = (t * tile_h).astype(jnp.float32)
    return jnp.logical_and(jnp.max(cy) + (r + 1.0) >= y0,
                           jnp.min(cy) - (r + 1.0) <= y0 + (tile_h - 1.0))


def _bwd_window_rows(c_ref, g_ref, g_off, lvl, k, wl, tile_h, t,
                     inv_scale):
    """``drows`` for f2 rows [t*tile_h, (t+1)*tile_h) of one level:
    (tile_h*wl, BQ).  Same math as the fused backward's ``_tile_rows``
    but over a single streamed tile (tile_h is small and static, so no
    fori loop and no scratch ref — one concatenate feeds one mat-mul).
    ``g_off`` is this level's sublane offset into the full (L*k*k, BQ)
    cotangent block (Mosaic requires sublane block dims divisible by 8
    or whole, so the level can't be sliced by the block spec)."""
    bq = c_ref.shape[2]
    r = (k - 1) // 2
    lvl_div = 1.0 / (2.0 ** lvl)
    cx = c_ref[0, 0:1, :] * lvl_div     # (1, BQ) — 2-D, see fwd body
    cy = c_ref[0, 1:2, :] * lvl_div
    posx = jax.lax.broadcasted_iota(jnp.int32, (wl, bq), 0) \
        .astype(jnp.float32)
    b = [
        sum(_tap_weight(cx, float(ti - r), posx)
            * g_ref[0, g_off + ti * k + tj:g_off + ti * k + tj + 1, :]
            for ti in range(k))
        for tj in range(k)
    ]                                    # K_j x (Wl, BQ)
    y0f = (t * tile_h).astype(jnp.float32)
    return jnp.concatenate([
        sum(_tap_weight(cy, float(tj - r - yi), y0f) * b[tj]
            for tj in range(k))
        for yi in range(tile_h)
    ], axis=0) * inv_scale               # (tile_h*Wl, BQ)


def _odm_bwd_df1_blocked_kernel(f2_ref, c_ref, g_ref, df1_ref, *, lvl,
                                g_off, wl, k, inv_scale, tile_h):
    """df1 contribution of ONE blocked level; grid (B, QB, TY).  The df1
    block index is constant across the innermost tile dim, so it
    accumulates in VMEM while f2 streams tile by tile."""
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        df1_ref[0] = jnp.zeros_like(df1_ref[0])

    @pl.when(_tile_overlaps(c_ref, lvl, (k - 1) // 2, tile_h, t))
    def _():
        drows = _bwd_window_rows(c_ref, g_ref, g_off, lvl, k, wl,
                                 tile_h, t, inv_scale)
        f2t = f2_ref[0].reshape(tile_h * wl, -1).astype(jnp.float32)
        df1_ref[0] += jax.lax.dot_general(
            drows, f2t, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (BQ, C)


def _odm_bwd_df2_blocked_kernel(f1_ref, c_ref, g_ref, df2_ref, *, lvl,
                                g_off, wl, k, inv_scale, tile_h):
    """df2 of ONE blocked level; grid (B, TY, QB).  The df2 spatial tile
    is constant across the innermost query dim, so it accumulates in
    VMEM (sequential grid — no atomics, unlike correlation_kernel.cu:237)
    while f1/coords/g stream."""
    t = pl.program_id(1)
    q = pl.program_id(2)

    @pl.when(q == 0)
    def _():
        df2_ref[0] = jnp.zeros_like(df2_ref[0])

    @pl.when(_tile_overlaps(c_ref, lvl, (k - 1) // 2, tile_h, t))
    def _():
        drows = _bwd_window_rows(c_ref, g_ref, g_off, lvl, k, wl,
                                 tile_h, t, inv_scale)
        f1 = f1_ref[0].astype(jnp.float32)               # (BQ, C)
        df2_ref[0] += jax.lax.dot_general(
            drows, f1, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(
                tile_h, wl, -1)


def _odm_bwd_blocked_level(lvl, f2, f1p, cpt, gp, k, inv_scale, block_q,
                           interpret, f2_dtype=jnp.float32):
    """Run the blocked kernel pair for one oversized level.

    Args:
      f2: this level's pooled target features ``(B, Hl, Wl, C)``.
      f1p / cpt / gp: query features ``(B, Npad, C)``, centroids
        ``(B, 2, Npad)`` and taps cotangent ``(B, L*k*k, Npad)``, all
        padded to a multiple of ``block_q``.
      f2_dtype: streaming dtype for f2 (the df1 kernel re-streams the
        whole level once per query block, so bf16 — what the fused path
        stores at beyond-HBM shapes anyway — halves the dominant DMA;
        the kernel accumulates fp32 regardless).

    Returns:
      ``(df1_level (B, Npad, C), df2_level (B, Hl, Wl, C))`` fp32.
    """
    B, Hl, Wl, C = f2.shape
    tile_h = min(_BWD_TILE_H, Hl)
    Hp = -(-Hl // tile_h) * tile_h
    TY = Hp // tile_h
    Npad = f1p.shape[1]
    QB = Npad // block_q
    f2p = f2.astype(f2_dtype)
    if Hp != Hl:
        # Zero rows contribute zero to df1 regardless of tap weights, and
        # the padded df2 rows are sliced away below — no in-kernel masks.
        f2p = jnp.pad(f2p, ((0, 0), (0, Hp - Hl), (0, 0), (0, 0)))
    lkk = gp.shape[1]
    kern1 = functools.partial(_odm_bwd_df1_blocked_kernel, lvl=lvl,
                              g_off=lvl * k * k, wl=Wl, k=k,
                              inv_scale=inv_scale, tile_h=tile_h)
    df1 = tpu_pallas_call(
        kern1,
        grid=(B, QB, TY),
        in_specs=[
            pl.BlockSpec((1, tile_h, Wl, C), lambda b, q, t: (b, t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, block_q), lambda b, q, t: (b, 0, q),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lkk, block_q), lambda b, q, t: (b, 0, q),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, C), lambda b, q, t: (b, q, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, Npad, C), jnp.float32),
        interpret=interpret,
    )(f2p, cpt, gp)

    kern2 = functools.partial(_odm_bwd_df2_blocked_kernel, lvl=lvl,
                              g_off=lvl * k * k, wl=Wl, k=k,
                              inv_scale=inv_scale, tile_h=tile_h)
    df2p = tpu_pallas_call(
        kern2,
        grid=(B, TY, QB),
        in_specs=[
            pl.BlockSpec((1, block_q, C), lambda b, t, q: (b, q, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, block_q), lambda b, t, q: (b, 0, q),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lkk, block_q), lambda b, t, q: (b, 0, q),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tile_h, Wl, C),
                               lambda b, t, q: (b, t, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, Hp, Wl, C), jnp.float32),
        interpret=interpret,
    )(f1p, cpt, gp)
    return df1, df2p[:, :Hl]


def _pad_coords_oor(coords, npad):
    """Pad the query dim to ``npad`` with far-out-of-range centers — every
    window weight becomes zero (the sampler's zeros-padding semantics), so
    padded queries contribute nothing in forward or backward."""
    pad = npad - coords.shape[1]
    if not pad:
        return coords
    return jnp.pad(coords, ((0, 0), (0, pad), (0, 0)),
                   constant_values=-1e6)


def _pad_queries(f1, coords, block_q):
    B, N, C = f1.shape
    nblocks = -(-N // block_q)
    pad = nblocks * block_q - N
    if pad:
        f1 = jnp.pad(f1, ((0, 0), (0, pad), (0, 0)))
        coords = _pad_coords_oor(coords, nblocks * block_q)
    return f1, coords, nblocks


def _auto_interpret() -> bool:
    from raft_tpu.ops.pallas_util import auto_interpret

    return auto_interpret()


# ---------------------------------------------------------------------------
# Fused lookup over the MATERIALIZED pyramid (the allpairs training path)
#
# The XLA window-sampling einsums (raft_tpu.ops.corr._sample_windows) are
# batched (K, Hl) x (Hl, Wl) mat-muls per query — M=9 streaming rows and a
# 46-of-128 contraction leave the MXU mostly idle.  Here the same math runs
# on the VPU with queries in the lanes: for each image row y, each of
# the K vertical taps accumulates ``wy_j(y) * row_y`` as one (Wl, BQ)
# fused-multiply-add, then the K horizontal taps contract x with a
# sublane reduction — both interpolation stages fused in VMEM, the
# (K, Wl, BQ) intermediate never touches HBM (the forward a differentiated
# call runs, and the transpose below).  A call no gradient is asked of
# runs a forward that gathers instead (``_pyr_fwd_level_rolled``):
# 0.21-0.27 ms an iteration at 55x128 on a v5e against the einsum pair's
# 1.5 (PERF.md section 6, PR 27 and PR 33).
#
# The backward is the exact transpose, and is race-free by construction:
# each query owns its correlation row, so ``dcorr`` blocks never overlap
# (grid = (B, N/BQ) writes disjoint (BQ, Hl, Wl) slabs) — no atomics, no
# sequential-grid accumulation.
# ---------------------------------------------------------------------------

_ROW_TILE = 8

# Per-instance VMEM the fused lookup may plan on, by the estimate below,
# under the 100 MiB ``vmem_limit_bytes`` every kernel here declares.  Held
# against Mosaic's own accounting for a v5e at a 136x240 bf16 map
# (``tests/test_chip_compile.py``): 71.5 MiB estimated (block 384)
# compiles; 95.4 and 119 MiB (block 512, 640) are refused "in memory
# space vmem" by the rolled forward, whose window rows (``3k + 1`` of
# them, fp32) take room the estimate counts a quarter of (the unrolled
# forward and the backward still take 95.4, and fp32 at block 256,
# 89.6).  So the estimate follows what Mosaic allocates, and the budget
# (the figure the on-demand backward already plans with) is the largest
# reading known to compile plus a tenth.
_PYR_LOOKUP_BUDGET = _FUSED_BWD_BUDGET


def pyramid_lookup_vmem_bytes(h8: int, w8: int, levels: int, radius: int,
                              block_q: int, storage_bytes: int) -> int:
    """Per-instance VMEM residency of the fused pyramid lookup at a
    ``(H/8, W/8)`` map: every level's ``(hl, wl, block_q)`` block,
    double-buffered by the pipeline (level 0 dominates: 55x128 bf16 is
    2 x 1.8 MB, 136x240 2 x 8.4 MB), the ``(k*wl, block_q)`` fp32 tap
    accumulators (the rolled forward holds ``3k + 1`` window rows of up
    to 24 columns more in their place: 22.6 MiB where this counts 6.0 at
    136x240 and block 384, which the 100 MiB limit still takes above the
    budget's 78), and the tap and coordinate blocks.  ``wl`` counts as
    the sublane tile it pads to.  The backward's level-0 call writes one
    block of the same shape where the forward reads one, so one figure
    covers both directions."""
    k = 2 * radius + 1
    sub = 8 * max(4 // storage_bytes, 1)     # sublane tile of the storage
    total = 2 * (levels * k * k + 2) * block_q * 4
    for lvl in range(levels):
        hl, wl = h8 >> lvl, w8 >> lvl
        if not hl or not wl:
            break
        total += 2 * hl * (-(-wl // sub) * sub) * block_q * storage_bytes
        total += k * (-(-wl // 8) * 8) * block_q * 4
    return total


def pyramid_lookup_path(platform: str, h8: int, w8: int, *, levels: int,
                        radius: int, block_q: int, storage_bytes: int,
                        rows_split: bool = False) -> str:
    """Which lookup samples a MATERIALIZED pyramid: ``'mosaic'`` (the
    fused kernel below, query-minor pyramid) or ``'xla'``
    (``ops.corr.corr_lookup``'s batched einsums, query-major pyramid).

    The one place this is decided, from what the code can observe when
    it traces: the platform, the map's shape and whether image rows are
    split over devices.  Mosaic where it can run -- on a TPU, with the
    kernel's per-block residency inside its VMEM budget, whole images on
    each device -- because where both were measured it is the faster
    one (55x128 rows, radius 4 and 3: 0.21-0.27 / 0.21 ms an iteration
    since PR 33, from the 0.20 its pipeline takes to fetch the pyramid
    up with the spread of a block's windows, 0.35 / 0.24 before, where
    XLA's eight batched M=9 mat-muls and their relayouts take 1.5 / 1.1;
    the train step has run it at 46x62 since before the benchmark:
    PERF.md sections 5 and 6, PR 27 and PR 33); XLA everywhere else: off
    TPU the kernel only runs in the interpreter, a block over the budget
    does not compile, and GSPMD cannot partition a Mosaic call over
    rows."""
    if platform != "tpu" or rows_split:
        return "xla"
    fits = pyramid_lookup_vmem_bytes(
        h8, w8, levels, radius, block_q, storage_bytes) <= _PYR_LOOKUP_BUDGET
    return "mosaic" if fits else "xla"


def _window_span(cy, hl: int, r: int, axis=None):
    """Which rows the windows of a set of queries reach at one level.

    ``cy``: window centres in the level's own rows, the block's queries
    along ``axis`` (the whole array when ``None``: the kernel hands its
    ``(1, BQ)`` block).  A window is rows ``y0 - r .. y0 + r + 1`` with
    ``y0 = floor(cy)`` and touches the map iff ``-r-1 <= y0 <= hl+r-1``;
    a query whose window does not (padded queries sit at -1e6) is not
    ``live`` and is left out of the bounds.  Returns ``(y0, live, b,
    top)``: the least and the greatest ``y0`` of the live queries, as
    floats; with none, ``top < b``."""
    y0 = jnp.floor(cy)
    live = jnp.logical_and(y0 >= -(r + 1.0), y0 <= hl + (r - 1.0))
    b = jnp.min(jnp.where(live, y0, hl + float(r)), axis=axis)
    top = jnp.max(jnp.where(live, y0, -(r + 2.0)), axis=axis)
    return y0, live, b, top


def lookup_reach(coords, shape, levels: int, radius: int,
                 block_q: int = 128):
    """What the rolled forward does for a coordinate field, a block and a
    level: the counter the kernel cannot return (its one result is the
    tap block).  Plain ``jax.numpy``, off any timed path.

    ``coords``: ``(B, H1, W1, 2)`` level-0 centres as the lookup gets
    them; ``shape``: the ``(H/8, W/8)`` of level 0.  Returns one dict a
    level, each entry ``(B, blocks)`` int32 over the raster-ordered
    blocks of ``block_q`` queries: ``first`` and ``rows`` (the rows
    ``[first, first + rows)`` of the level that the block's taps are
    made from, the union of its windows cut to the map; a slab the
    kernel cuts at the map's edge also loads up to ``k`` rows beyond
    them, into window rows nothing reads), ``passes`` (the distinct
    window starts the y stage makes a pass for, spread + 1; 0 where no
    window touches the map) and ``held`` (the level's rows, all of which
    the pipeline still fetches)."""
    B = coords.shape[0]
    c = coords.reshape(B, -1, 2).astype(jnp.float32)
    blocks = -(-c.shape[1] // block_q)
    cy = _pad_coords_oor(c, blocks * block_q)[..., 1].reshape(
        B, blocks, block_q)
    out = []
    for lvl in range(levels):
        hl, wl = shape[0] >> lvl, shape[1] >> lvl
        _, _, b, top = _window_span(cy / (2.0 ** lvl), hl, radius, axis=-1)
        passes = jnp.maximum(top - b + 1.0, 0.0)
        first = jnp.clip(b - radius, 0, hl)
        last = jnp.clip(top + (radius + 2.0), 0, hl)
        if not (hl and wl):      # an over-pooled level: no kernel work
            passes, first, last = (jnp.zeros_like(b),) * 3
        out.append({
            "first": first.astype(jnp.int32),
            "rows": jnp.maximum(last - first, 0.0).astype(jnp.int32),
            "passes": passes.astype(jnp.int32),
            "held": jnp.full(b.shape, hl, jnp.int32)})
    return out


def _pyr_fwd_level_body(corr_ref, c_ref, out_ref, acc_ref, lvl, out_off,
                        hl, wl, k):
    """One level's forward sampling inside the fused kernel (QUERY-MINOR:
    queries live in lanes, x in sublanes): write ``(k*k, BQ)`` taps at
    sublane offset ``out_off`` of ``out_ref``.

    Tap accumulation lives in a ``(k*wl, BQ)`` VMEM scratch ref (not
    loop-carried registers) so each row tile can be SKIPPED outright
    when no query window reaches its rows — queries are raster-ordered,
    so one block's ``cy`` spans ~2 image rows plus flow, and at bounded
    flow most of the image contributes nothing to a block's taps (round
    4: same bound as the blocked backward's ``_tile_overlaps``).

    corr_ref: (1, hl, wl, BQ); c_ref: (1, 2, BQ); out: (1, L*k*k, BQ)."""
    bq = c_ref.shape[2]
    r = (k - 1) // 2
    lvl_div = 1.0 / (2.0 ** lvl)
    cx = c_ref[0, 0:1, :] * lvl_div      # (1, BQ)
    cy = c_ref[0, 1:2, :] * lvl_div
    posx = jax.lax.broadcasted_iota(jnp.int32, (wl, bq), 0) \
        .astype(jnp.float32)
    wx = [_tap_weight(cx, float(i - r), posx) for i in range(k)]  # (wl,BQ)

    T = min(_ROW_TILE, hl)
    nt = hl // T
    # Window bounds hoisted out of the tile loop: one min/max pair per
    # level instead of per tile.  Padded queries sit at -1e6: they relax
    # the lower bound but never extend the upper one.
    ymax = jnp.max(cy) + (r + 1.0)
    ymin = jnp.min(cy) - (r + 1.0)

    acc_ref[...] = jnp.zeros((k * wl, bq), jnp.float32)

    def tile_body(t, _):
        y0 = (t * T).astype(jnp.float32)

        @pl.when(jnp.logical_and(ymax >= y0, ymin <= y0 + (T - 1.0)))
        def _():
            blk = corr_ref[0, pl.ds(t * T, T), :, :]     # (T, wl, BQ)
            for yi in range(T):
                # fp32 accumulation regardless of the stored pyramid
                # dtype (corr_dtype='bfloat16' halves the HBM read
                # traffic; the convert rides the VMEM load).
                row = blk[yi, :, :].astype(jnp.float32)
                for j in range(k):
                    acc_ref[j * wl:(j + 1) * wl, :] += _tap_weight(
                        cy, float(j - r - yi), y0) * row
        return 0

    jax.lax.fori_loop(0, nt, tile_body, 0)
    if hl % T:
        rem = nt * T
        blk = corr_ref[0, rem:, :, :]
        for yi in range(hl - rem):
            row = blk[yi, :, :].astype(jnp.float32)
            for j in range(k):
                acc_ref[j * wl:(j + 1) * wl, :] += _tap_weight(
                    cy, float(j - r - yi), float(rem)) * row

    for i in range(k):
        for j in range(k):
            out_ref[0, out_off + i * k + j:out_off + i * k + j + 1, :] = \
                jnp.sum(wx[i] * acc_ref[j * wl:(j + 1) * wl, :], axis=0,
                        keepdims=True).astype(out_ref.dtype)


def _window_rows(wl: int, k: int):
    """Rows of the rolled forward's window scratch at a level ``wl``
    wide: ``(wl8, nx)``.  ``wl8`` is ``wl`` padded to the fp32 sublane
    tile; ``nx`` the whole 8-row tiles of x a run of ``k + 1`` window
    positions can straddle, kept behind the ``wl8`` rows, or 0 where the
    level is no wider than that and the x stage contracts all of it."""
    wl8 = -(-wl // 8) * 8
    nx = 8 * (-(-(k + 8) // 8))
    return wl8, (nx if wl8 > nx else 0)


def _pyr_fwd_level_rolled(corr_ref, c_ref, tap_ref, win_ref, lvl, hl, wl,
                          k):
    """One level of the forward an undifferentiated call runs: the taps
    of :func:`_pyr_fwd_level_body` (to fp32 rounding), from work that
    follows what the block's windows reach and not the map, in a kernel
    an eighth as long to trace as that one.  Both stages are a gather
    followed by arithmetic on what was gathered.

    **The y stage gathers rows.**  A lane whose window starts at
    ``y0 = floor(cy)`` needs the ``k + 1`` rows ``C[y0 - r + m]``,
    ``m = 0..k``, each used by two taps.  Lanes of a block differ in
    ``y0`` by ``o = y0 - b`` (``b`` the block's least ``y0``), so window
    row ``m`` of a lane is ``C[b - r + o + m]``: one pass a value of
    ``o`` present in the block (a loop of spread + 1 trips: 1-3 at
    smooth flow) copies the slab of rows ``b - r + d + (0..k)`` into the
    window rows of the lanes with ``o == d``.  Rows outside ``[0, hl)``
    and lanes whose window lies wholly outside the map (padded queries
    sit at -1e6) select nothing and stay zero: zeros padding, never a
    clamped read; such lanes are left out of ``b`` and the spread, so a
    padded block costs what its real lanes reach.  The kernel this
    replaced (PR 27) updated all ``k`` accumulators of ``(wl, BQ)`` once
    a reached row, ``~(k + 1 + spread) * k`` tile read-modify-writes
    where this makes ``(k + 1) * (spread + 1)`` selects;
    :func:`lookup_reach` counts rows and passes.

    **The x stage gathers tiles, contracts them, then mixes.**  A lane's
    ``k + 1`` window columns start at ``floor(cx) - r`` and so lie in
    ``nx / 8`` consecutive 8-row tiles of x (3 at radius 4, 2 at radius
    3) out of ``wl / 8``: each lane picks its tiles (a select over the
    level's tiles, all ``k + 1`` rows at once) into the ``nx`` rows kept
    behind the window rows.  For each x offset ``i`` those rows are
    contracted with the offset's bilinear weights over ``nx`` positions
    (not ``wl``), and the two y weights ``(1 - f, f)`` are applied to
    the contracted ``(k + 1, 1, BQ)`` sums: the ``k`` y-interpolated
    rows are never built.  A level no wider than ``nx`` is contracted
    whole.  The ``k`` taps of the offset go to entry ``lvl*k + i`` of
    ``tap_ref``, an fp32 ``(L*k, k, 1, BQ)`` scratch; the kernel casts
    the whole scratch to the output block once.

    Storage may be fp32, bf16 or int8/fp8 codes (the quantized lookup
    calls this body too): rows are converted as they are read and all
    arithmetic is fp32.  Measured on a v5e at 55x128 (PERF.md section 6,
    PR 33; ms an iteration at 1 / 3 / 5 / 9 window starts a block):
    0.207 / 0.215 / 0.230 / 0.271 at radius 4 where the PR 27 body took
    0.358 / 0.373 / 0.394 / 0.425, 0.205 against 0.251 at radius 3, and
    a body that only lets the pipeline bring the 132 MB pyramid in takes
    0.202: at smooth flow the kernel waits for its fetch.  The loop over
    passes stays rolled (its trip count is the block's own) and a pass
    is one expression over its slab; the loops over x tiles and x
    offsets are short and static, which read 0.015 and 0.02 ms faster
    than their rolled forms for ~0.1 s more of lowering a program (jax
    traces and lowers a kernel again in every process, for every program
    that holds it: kernel length is set-up time, PERF.md section 6,
    PR 27)."""
    bq = c_ref.shape[2]
    r = (k - 1) // 2
    lvl_div = 1.0 / (2.0 ** lvl)
    cx = c_ref[0, 0:1, :] * lvl_div      # (1, BQ)
    cy = c_ref[0, 1:2, :] * lvl_div
    wl8, nx = _window_rows(wl, k)

    y0, live, b, top = _window_span(cy, hl, r)  # no live lane: top < b
    o = jnp.where(live, y0 - b, -1.0)                    # (1, BQ)
    fy = cy - y0
    w_lo = jnp.where(live, 1.0 - fy, 0.0)[None]          # (1, 1, BQ)
    w_hi = jnp.where(live, fy, 0.0)[None]
    base = b.astype(jnp.int32) - r

    # Window row m is row k + m of win_ref: a pass reads its rows as one
    # slab cut to the map and stores it shifted by what was cut, so the
    # k rows on either side take what falls outside and are never read.
    n = min(k + 1, hl)
    win = win_ref.at[k:2 * k + 1]
    win[:, 0:wl8, :] = jnp.zeros((k + 1, wl8, bq), jnp.float32)

    def pass_body(d, _):
        at = jnp.clip(base + d, 0, hl - n)
        # fp32 whatever the stored pyramid dtype
        slab = corr_ref[0, pl.ds(at, n), :, :].astype(jnp.float32)
        to = pl.ds(k + at - (base + d), n)
        pick = (o == d.astype(jnp.float32))[None]         # (1, 1, BQ)
        win_ref[to, 0:wl, :] = jnp.where(pick, slab, win_ref[to, 0:wl, :])
        return 0

    jax.lax.fori_loop(0, (top - b).astype(jnp.int32) + 1, pass_body, 0)

    # what the x stage contracts: the picked tiles, or the level whole
    cols = win.at[:, wl8:wl8 + nx] if nx else win.at[:, 0:wl8]
    posx = jax.lax.broadcasted_iota(jnp.int32, (cols.shape[1], bq), 0) \
        .astype(jnp.float32)
    if nx:
        # tile t0 + j of the level becomes tile j of the nx rows, for
        # the lanes whose window starts in tile t0 (cut to the map: a
        # window that leaves it finds zero weights, not other columns)
        t0 = jnp.clip(jnp.floor((jnp.floor(cx) - r) * 0.125), 0.0,
                      (wl8 - nx) // 8)[None]              # (1, 1, BQ)
        tiles = [jnp.zeros((k + 1, 8, bq), jnp.float32)] * (nx // 8)
        for t in range(wl8 // 8):
            tile = win[:, 8 * t:8 * t + 8, :]
            tiles = [jnp.where(t0 == float(t - j), tile, got)
                     for j, got in enumerate(tiles)]
        for j, got in enumerate(tiles):
            cols[:, 8 * j:8 * j + 8, :] = got
        posx = posx + 8.0 * t0[0]

    def tap_body(i, _):
        wx = _tap_weight(cx, (i - r).astype(jnp.float32), posx)
        s = jnp.sum(wx[None] * cols[...], axis=1,
                    keepdims=True)                        # (k + 1, 1, BQ)
        tap_ref[lvl * k + i] = w_lo * s[:k] + w_hi * s[1:]
        return 0

    jax.lax.fori_loop(0, k, tap_body, 0, unroll=True)


def _pyr_bwd_level_body(c_ref, g_ref, dcorr_ref, lvl, g_off, hl, wl, k):
    """One level's transpose inside the fused kernel (QUERY-MINOR):
    scatter the taps at sublane offset ``g_off`` of ``g_ref`` into this
    level's ``dcorr`` (1, hl, wl, BQ)."""
    bq = c_ref.shape[2]
    r = (k - 1) // 2
    lvl_div = 1.0 / (2.0 ** lvl)
    cx = c_ref[0, 0:1, :] * lvl_div
    cy = c_ref[0, 1:2, :] * lvl_div
    posx = jax.lax.broadcasted_iota(jnp.int32, (wl, bq), 0) \
        .astype(jnp.float32)

    # b_j(x, q) = sum_i wx_i(x, q) g(i*k+j, q)
    b = [sum(_tap_weight(cx, float(i - r), posx)
             * g_ref[0, g_off + i * k + j:g_off + i * k + j + 1, :]
             for i in range(k)) for j in range(k)]

    T = min(_ROW_TILE, hl)
    nt = hl // T
    # Same per-tile window bound as the forward: tiles no query window
    # reaches get a plain zero store instead of the 9-FMA-per-row
    # construction (the write itself cannot be skipped — dcorr is dense).
    ymax = jnp.max(cy) + (r + 1.0)
    ymin = jnp.min(cy) - (r + 1.0)

    def _rows(y0f, yis):
        return jnp.stack([
            sum(_tap_weight(cy, float(j - r - yi), y0f) * b[j]
                for j in range(k)) for yi in yis
        ], axis=0)                                   # (T, wl, BQ)

    def tile_body(t, _):
        y0 = (t * T).astype(jnp.float32)
        hit = jnp.logical_and(ymax >= y0, ymin <= y0 + (T - 1.0))

        @pl.when(hit)
        def _():
            dcorr_ref[0, pl.ds(t * T, T), :, :] = _rows(
                y0, range(T)).astype(dcorr_ref.dtype)

        @pl.when(jnp.logical_not(hit))
        def _():
            dcorr_ref[0, pl.ds(t * T, T), :, :] = jnp.zeros(
                (T, wl, bq), dcorr_ref.dtype)
        return 0

    jax.lax.fori_loop(0, nt, tile_body, 0)
    if hl % T:
        rem = nt * T
        dcorr_ref[0, rem:, :, :] = _rows(
            float(rem), range(hl - rem)).astype(dcorr_ref.dtype)


def _pyr_multi_fwd_kernel(*refs, levels, k, kk_total):
    """Fused forward over every non-empty level: round-2 profiling showed
    the per-call overhead of one pallas_call per level per direction
    (~200 calls/step at unroll 6) costing as much as the level-0 math —
    the small levels were pure overhead.  ``levels``: static list of
    ``(lvl, out_off, hl, wl)``; refs = [corr_0..corr_{n-1}, c, out,
    acc_0..acc_{n-1}]."""
    nl = len(levels)
    c_ref, out_ref = refs[nl], refs[nl + 1]
    acc_refs = refs[nl + 2:]
    bq = c_ref.shape[2]
    covered = 0
    for (lvl, off, hl, wl), corr_ref, acc_ref in zip(levels, refs[:nl],
                                                     acc_refs):
        _pyr_fwd_level_body(corr_ref, c_ref, out_ref, acc_ref, lvl, off,
                            hl, wl, k)
        covered += k * k
    if covered < kk_total:  # empty (over-pooled) trailing levels -> zeros
        out_ref[0, covered:, :] = jnp.zeros((kk_total - covered, bq),
                                            out_ref.dtype)


def _pyr_multi_fwd_rolled_body(*refs, levels, k, kk_total):
    """:func:`_pyr_multi_fwd_kernel` over the rolled level body; refs as
    there, with each level's window scratch (``3k + 1`` rows of
    :func:`_window_rows` columns) where its accumulator stood, plus the
    fp32 ``(kk_total // k, k, 1, BQ)`` tap scratch last."""
    nl = len(levels)
    c_ref, out_ref, tap_ref = refs[nl], refs[nl + 1], refs[-1]
    for (lvl, _, hl, wl), corr_ref, win_ref in zip(levels, refs[:nl],
                                                   refs[nl + 2:-1]):
        _pyr_fwd_level_rolled(corr_ref, c_ref, tap_ref, win_ref, lvl, hl,
                              wl, k)
    if nl * k * k < kk_total:  # empty (over-pooled) trailing levels
        tap_ref[nl * k:] = jnp.zeros(
            (kk_total // k - nl * k, k, 1, c_ref.shape[2]), jnp.float32)
    out_ref[0] = tap_ref[...].reshape(kk_total, c_ref.shape[2]).astype(
        out_ref.dtype)


@functools.lru_cache(maxsize=None)
def _rolled_kernel(levels, k, kk_total):
    """The rolled kernel as ONE jitted callable a signature: pallas_call
    traces its kernel anew at every call site, and a process that serves
    holds one an ``iter`` program (four batch sizes a bucket); through
    ``jax.jit`` the later ones find the first one's jaxpr."""
    @jax.jit
    def _pyr_multi_fwd_rolled_kernel(*refs):
        _pyr_multi_fwd_rolled_body(*refs, levels=levels, k=k,
                                   kk_total=kk_total)

    return _pyr_multi_fwd_rolled_kernel


def _pyr_multi_bwd_kernel(*refs, levels, k):
    """Fused transpose over every non-empty level; refs =
    [c, g, dcorr_0..dcorr_{n-1}]."""
    c_ref, g_ref = refs[0], refs[1]
    for (lvl, off, hl, wl), dcorr_ref in zip(levels, refs[2:]):
        _pyr_bwd_level_body(c_ref, g_ref, dcorr_ref, lvl, off, hl, wl, k)


def _pyr_levels_fwd(pyramid, coords_p, radius, block_q, interpret,
                    out_dtype=jnp.float32, rolled=False):
    """All levels in ONE pallas_call -> (B, L*k*k, Npad) taps.

    Query-minor layout throughout: ``pyramid`` levels are
    ``(B, hl, wl, Npad)`` and ``coords_p`` is ``(B, 2, Npad)`` — queries
    in lanes, so every VMEM/HBM tile is dense (Npad is a multiple of
    128) and the per-tap contraction is a sublane reduction.

    ``rolled``: the kernel of :func:`_pyr_fwd_level_rolled` (what a call
    that is not differentiated runs) in place of the unrolled one."""
    B = pyramid[0].shape[0]
    Npad = pyramid[0].shape[3]
    k = 2 * radius + 1
    L = len(pyramid)
    nonempty, levels = _odm_levels(pyramid, k)
    if rolled:
        kern = _rolled_kernel(tuple(levels), k, L * k * k)
    else:
        kern = functools.partial(_pyr_multi_fwd_kernel, levels=levels, k=k,
                                 kk_total=L * k * k)
    in_specs = [
        pl.BlockSpec((1, c.shape[1], c.shape[2], block_q),
                     lambda b, i: (b, 0, 0, i),
                     memory_space=pltpu.VMEM)
        for _, c in nonempty
    ] + [pl.BlockSpec((1, 2, block_q), lambda b, i: (b, 0, i),
                      memory_space=pltpu.VMEM)]
    return tpu_pallas_call(
        kern,
        grid=(B, Npad // block_q),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, L * k * k, block_q),
                               lambda b, i: (b, 0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, L * k * k, Npad), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((3 * k + 1, sum(_window_rows(c.shape[2], k)),
                        block_q)
                       if rolled
                       else (k * c.shape[2], block_q), jnp.float32)
            for _, c in nonempty
        ] + ([pltpu.VMEM((L * k, k, 1, block_q), jnp.float32)]
             if rolled else []),
        interpret=interpret,
    )(*[c for _, c in nonempty], coords_p)


def _pyr_levels_bwd(coords_p, g, shapes, radius, block_q, interpret):
    """Grouped transpose calls; ``g``: (B, L*k*k, Npad), levels
    query-minor (B, hl, wl, Npad).  Unlike the forward, level 0 stays
    its OWN pallas_call: one call producing all four dcorr outputs pins
    the whole group (537+134+33+8 MB fp32 at chairs batch 16) live per
    unrolled iteration and OOMs — keeping the big level separate lets
    XLA's scheduler interleave its accumulation and retire the temp
    early.  The SMALL levels (1..) are fused into one call: profiled at
    ~0.35 ms/call with near-zero math, they were pure per-call overhead
    (48 bwd calls/step at unroll 12), and their combined liveness is
    <15% of level 0's."""
    B, _, Npad = coords_p.shape
    k = 2 * radius + 1
    nonempty = [(lvl, s, dt) for lvl, (s, dt) in enumerate(shapes)
                if s[1] and s[2]]
    # [[level0], [level1..]] — singleton groups when only one level;
    # no groups at all when every level is empty (degenerate over-pooled
    # pyramid) so the all-zeros fallback below covers it instead of a
    # zero-output pallas_call.
    groups = [g for g in (nonempty[:1], nonempty[1:]) if g]
    by_level = {}
    for grp in groups:
        kern = functools.partial(
            _pyr_multi_bwd_kernel,
            levels=[(lvl, lvl * k * k, s[1], s[2]) for lvl, s, _ in grp],
            k=k)
        outs = tpu_pallas_call(
            kern,
            grid=(B, Npad // block_q),
            in_specs=[
                pl.BlockSpec((1, 2, block_q), lambda b, i: (b, 0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, k * k * len(shapes), block_q),
                             lambda b, i: (b, 0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, s[1], s[2], block_q),
                             lambda b, i: (b, 0, 0, i),
                             memory_space=pltpu.VMEM)
                for _, s, _ in grp
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, s[1], s[2], Npad), dt)
                for _, s, dt in grp
            ],
            interpret=interpret,
        )(coords_p, g)
        for (lvl, _, _), out in zip(grp, outs):
            by_level[lvl] = out
    return [by_level.get(lvl, jnp.zeros(s, dt))
            for lvl, (s, dt) in enumerate(shapes)]


def pallas_pyramid_lookup(pyramid, coords, radius: int = 4,
                          block_q: int = 128, interpret=None,
                          out_dtype=jnp.float32):
    """Fused window sampling of a MATERIALIZED correlation pyramid.

    Drop-in replacement for :func:`raft_tpu.ops.corr.corr_lookup` (the
    reference ``CorrBlock.__call__``, corr.py:29-50) — same tap-order
    contract, same zeros-padding bilinear semantics.

    Args:
      pyramid: list of ``(B, Hl, Wl, Npad)`` QUERY-MINOR levels (fp32 or
        bf16 storage — see ``RAFTConfig.corr_dtype``; taps always
        accumulate fp32 in-kernel and cotangents match each level's
        stored dtype)
        (from :func:`raft_tpu.ops.corr.build_corr_pyramid_flat`) whose
        query dim is already padded to a multiple of ``block_q`` (zero
        fmap1 rows correlate to zero).
      coords: ``(B, H1, W1, 2)`` level-0 centroids (N = H1*W1 real
        queries), last axis ``(x, y)``.
      out_dtype: tap output dtype.  Pass bf16 when the consumer casts
        immediately anyway (the refinement step does) — halves the tap
        write/read traffic; accumulation stays fp32 in-kernel either
        way (hashable static arg: use ``jnp.bfloat16``, not a dtype
        instance).

    Returns:
      ``(B, H1, W1, L * (2r+1)^2)`` ``out_dtype`` lookup features.
    """
    def lookup(p, c):
        return _pyramid_lookup(p, c, radius, block_q, interpret,
                               out_dtype)

    return per_data_shard(lookup, (BATCH, BATCH))(pyramid, coords)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _pyramid_lookup(pyramid, coords, radius, block_q, interpret,
                    out_dtype):
    # the primal: no gradient is asked of this call (inference), so it
    # runs the forward kernel that is short to trace
    out, _ = _pyr_fwd(pyramid, coords, radius, block_q, interpret,
                      out_dtype, rolled=True)
    return out


def _pyr_fwd(pyramid, coords, radius, block_q, interpret,
             out_dtype=jnp.float32, rolled=False):
    if interpret is None:
        interpret = _auto_interpret()
    B, H1, W1, _ = coords.shape
    N = H1 * W1
    Npad = pyramid[0].shape[3]
    if Npad % block_q:
        raise ValueError(
            f"pyramid query dim {Npad} is not a multiple of block_q "
            f"{block_q}; build the pyramid with "
            f"build_corr_pyramid_flat(..., pad_q={block_q}) — a mismatch "
            "would silently skip trailing query lanes in the Pallas grid")
    k = 2 * radius + 1
    c = _pad_coords_oor(coords.reshape(B, N, 2).astype(jnp.float32),
                        Npad).transpose(0, 2, 1)
    out = _pyr_levels_fwd(list(pyramid), c, radius, block_q, interpret,
                          out_dtype, rolled)
    out = out[:, :, :N].reshape(B, len(pyramid) * k * k, H1, W1)
    # The bwd needs each level's shape AND stored dtype (cotangents must
    # match the primal dtypes, which may differ per level); dtypes aren't
    # valid residual leaves, so carry a zero-size prototype per level.
    return (out.transpose(0, 2, 3, 1),
            (tuple(x.shape for x in pyramid),
             tuple(jnp.zeros((0,), x.dtype) for x in pyramid), coords))


def _pyr_bwd(radius, block_q, interpret, out_dtype, residuals, g):
    shapes, protos, coords = residuals
    if interpret is None:
        interpret = _auto_interpret()
    B, H1, W1, _ = coords.shape
    N = H1 * W1
    Npad = shapes[0][3]
    if Npad % block_q:
        raise ValueError(
            f"pyramid query dim {Npad} is not a multiple of block_q "
            f"{block_q}; build the pyramid with "
            f"build_corr_pyramid_flat(..., pad_q={block_q})")
    c = _pad_coords_oor(coords.reshape(B, N, 2).astype(jnp.float32),
                        Npad).transpose(0, 2, 1)
    g = g.reshape(B, N, -1).transpose(0, 2, 1).astype(jnp.float32)
    if Npad != N:
        g = jnp.pad(g, ((0, 0), (0, 0), (0, Npad - N)))
    # container must match the primal's (build_corr_pyramid_flat returns a
    # list)
    dpyr = _pyr_levels_bwd(c, g,
                           [(s, p.dtype) for s, p in zip(shapes, protos)],
                           radius, block_q, interpret)
    return dpyr, jnp.zeros_like(coords)


_pyramid_lookup.defvjp(_pyr_fwd, _pyr_bwd)


# ---------------------------------------------------------------------------
# The differentiated lookup that takes the FEATURES' gradients
#
# A level of the volume is linear in each feature map,
# ``C[q, y, x] = <f1[q], f2[y, x]> / sqrt(C)``, so the gradients a lookup
# sends back are sums over the windows it read:
#
#     df1[q]    = sum_{y,x} D[q, y, x] f2[y, x] / sqrt(C)
#     df2[y, x] = sum_q     D[q, y, x] f1[q]    / sqrt(C)
#
# where ``D[q]`` is the tap cotangent of query ``q`` spread bilinearly over
# the (k+1)^2 positions of its window.  ``_pyr_bwd`` writes ``D`` as a
# dense volume-sized array, which XLA then sums over the iterations and
# multiplies back into the features.  Here ``D`` lives in VMEM one query
# block at a time, over the rows the block's windows reach, and goes
# straight into two MXU products with the features.
# ---------------------------------------------------------------------------

# Volume rows a product of the window backward takes at once (f2 rows are
# a leading dim, so any count tiles), and its query block: swept on a v5e
# over rows 4, 8, 16 and blocks 128, 256 (scripts/bench_lookup_backward.py).
# At 96x128 x 1 level (batch 8, C 128, bfloat16), where the window path
# runs, 4 and 256 were the fastest, 3.09 ms a call (3.15 at 8 and 256,
# 3.40 at 8 and 128); at 46x62 x 4 levels (batch 16, C 256, float32),
# where the dense backward is the cheaper, 4.34 (4.24 at 8 and 256).
_WIN_ROWS = 4
_WIN_BLOCK_Q = 256


def _win_width(wl: int, k: int):
    """``(wp, nx)`` of a level ``wl`` wide: ``wp`` the width padded to the
    bfloat16 sublane tile (the features' and ``D``'s rows), ``nx`` the
    columns of the local frame a window's ``k + 1`` columns fall in (whole
    8-column tiles), or 0 where the level is no wider than that."""
    wp = -(-wl // 16) * 16
    nx = 8 * (-(-(k + 8) // 8))
    return wp, (nx if wp > nx else 0)


def _win_bwd_kernel(*refs, lvl, hl, k, rows, nparts, d_halves, inv_scale):
    """``df1`` of one query block and its share of ``df2`` for one level.

    refs = [c, g, f1 parts.., f2 parts.., df1, df2, h, d(, loc)]: ``c``
    ``(1, 2, BQ)`` level-0 centres, ``g`` ``(1, k*k, BQ)`` the level's tap
    cotangent, ``f1`` parts ``(1, BQ, C)`` and ``f2`` parts ``(1, Hp, wp,
    C)`` bfloat16 (the features, or their high and low halves), ``df1``
    ``(1, BQ, C)``, ``df2`` ``(1, Hp, wp, C)`` float32, resident across the
    query blocks of an image; scratch ``h`` ``(k + 1, wp, BQ)``: window row
    ``m`` of every lane at full width, ``d`` ``(Hp + 2k, wp, BQ)``: ``D`` by
    volume row, offset by ``k`` so rows a window has outside the map land
    in a margin nothing reads; ``loc`` ``(k + 1, nx, BQ)``: the window rows
    in the lane's local frame.

    **x stage.**  A lane's window columns ``floor(cx) - r .. + k`` lie in
    ``nx / 8`` consecutive 8-column tiles from ``t0``; the tap cotangent
    is spread over those ``nx`` columns only (``L_j``, the y tap ``j``'s
    bilinear x mix), then the two y weights give window row ``m``,
    ``(1 - fy) L_m + fy L_{m-1}``, which a select over the level's tiles
    puts at full width.  **y stage**, as the rolled forward's: one pass a
    distinct ``y0 = floor(cy)`` of the block writes the ``k + 1`` window
    rows of the lanes that start there into ``d``; rows outside the
    windows stay zero.  **Products**: over the row tiles the windows
    reach, ``D`` against the feature parts, float32 accumulation, ``D``
    as a high and a low bfloat16 half where ``d_halves`` (a float32
    volume, whose dense cotangent is float32), else rounded to bfloat16
    as a bfloat16 volume's dense cotangent is: ``df1 += D^T f2`` for the block,
    ``df2[rows] += D f1``; ``D`` carries the volume's ``1/sqrt(C)``."""
    c_ref, g_ref = refs[:2]
    f1_refs = refs[2:2 + nparts]
    f2_refs = refs[2 + nparts:2 + 2 * nparts]
    df1_ref, df2_ref, h_ref, d_ref = refs[2 + 2 * nparts:6 + 2 * nparts]
    loc_ref = refs[6 + 2 * nparts] if len(refs) > 6 + 2 * nparts else None
    bq = c_ref.shape[2]
    wp = h_ref.shape[1]
    C = df1_ref.shape[2]
    r = (k - 1) // 2
    lvl_div = 1.0 / (2.0 ** lvl)
    cx = c_ref[0, 0:1, :] * lvl_div                      # (1, BQ)
    cy = c_ref[0, 1:2, :] * lvl_div

    @pl.when(pl.program_id(1) == 0)
    def _():
        df2_ref[0] = jnp.zeros(df2_ref.shape[1:], jnp.float32)

    y0, live, b, top = _window_span(cy, hl, r)
    npass = (top - b).astype(jnp.int32) + 1              # <= 0: no live lane
    df1_ref[0] = jnp.zeros((bq, C), jnp.float32)

    @pl.when(npass > 0)
    def _():
        # x stage: each y tap's cotangent over the lane's local columns
        nx = loc_ref.shape[1] if loc_ref is not None else 0
        if nx:
            t0 = jnp.clip(jnp.floor((jnp.floor(cx) - r) * 0.125), 0.0,
                          float((wp - nx) // 8))          # (1, BQ)
            posx = jax.lax.broadcasted_iota(jnp.int32, (nx, bq), 0) \
                .astype(jnp.float32) + 8.0 * t0
        else:
            posx = jax.lax.broadcasted_iota(jnp.int32, (wp, bq), 0) \
                .astype(jnp.float32)
        wx = [_tap_weight(cx, float(i - r), posx) for i in range(k)]
        lj = [sum(wx[i] * g_ref[0, i * k + j:i * k + j + 1, :]
                  for i in range(k)) for j in range(k)]
        fy = cy - y0
        w_lo = jnp.where(live, (1.0 - fy) * inv_scale, 0.0)
        w_hi = jnp.where(live, fy * inv_scale, 0.0)
        rows_m = [(w_lo * lj[m] if m < k else 0.0)
                  + (w_hi * lj[m - 1] if m else 0.0) for m in range(k + 1)]
        if nx:
            for m, row in enumerate(rows_m):
                loc_ref[m] = row

            def spread(m, _):
                loc = loc_ref[m]                          # (nx, BQ)
                for t in range(wp // 8):
                    got = jnp.zeros((8, bq), jnp.float32)
                    for j in range(nx // 8):
                        if 0 <= t - j <= (wp - nx) // 8:
                            got = jnp.where(t0 == float(t - j),
                                            loc[8 * j:8 * j + 8], got)
                    h_ref[m, 8 * t:8 * t + 8, :] = got
                return 0

            jax.lax.fori_loop(0, k + 1, spread, 0)
        else:
            for m, row in enumerate(rows_m):
                h_ref[m] = row

        # the row tiles the windows reach inside the map, and what to zero
        lo = jnp.maximum(b - r, 0.0).astype(jnp.int32)
        hi = jnp.minimum(top + (r + 1.0), hl - 1.0).astype(jnp.int32)
        t_lo = jax.lax.div(lo, jnp.int32(rows))
        t_hi = jax.lax.div(hi, jnp.int32(rows))
        base = b.astype(jnp.int32) - r + k               # d row of b - r
        z_lo = jnp.minimum(base, k + t_lo * rows)
        z_hi = jnp.maximum(base + npass + k, k + (t_hi + 1) * rows)

        def zero(y, _):
            d_ref[pl.ds(y, 1)] = jnp.zeros((1, wp, bq), jnp.float32)
            return 0

        jax.lax.fori_loop(z_lo, z_hi, zero, 0)

        # y stage: the lanes whose window starts at b + p
        o = jnp.where(live, y0 - b, -1.0)

        def pass_body(p, _):
            pick = (o == p.astype(jnp.float32))[None]     # (1, 1, BQ)
            for m in range(k + 1):
                at = pl.ds(base + p + m, 1)
                d_ref[at] = jnp.where(pick, h_ref[pl.ds(m, 1)], d_ref[at])
            return 0

        jax.lax.fori_loop(0, npass, pass_body, 0)

        f1s = [ref[0] for ref in f1_refs]                 # (BQ, C)

        def product(t, acc):
            y = t * rows
            dm = d_ref[pl.ds(k + y, rows)].reshape(rows * wp, bq)
            d_hi = dm.astype(jnp.bfloat16)
            f2s = [ref[0, pl.ds(y, rows)].reshape(rows * wp, C)
                   for ref in f2_refs]
            pairs = [(d_hi, i) for i in range(nparts)]
            if d_halves:
                pairs.append(((dm - d_hi.astype(jnp.float32))
                              .astype(jnp.bfloat16), 0))
            for dp, i in pairs:
                acc = acc + jax.lax.dot_general(
                    dp, f2s[i], (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # (BQ, C)
            part = sum(jax.lax.dot_general(
                dp, f1s[i], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) for dp, i in pairs)
            df2_ref[0, pl.ds(y, rows)] += part.reshape(rows, wp, C)
            return acc

        df1_ref[0] = jax.lax.fori_loop(t_lo, t_hi + 1, product,
                                       jnp.zeros((bq, C), jnp.float32))


def _win_vmem_bytes(hl: int, wl: int, radius: int, channels: int,
                    block_q: int, feature_parts: int) -> int:
    """Per-instance VMEM of the window backward at one level: the image's
    target map (``feature_parts`` bfloat16 arrays) and its float32
    ``df2``, both resident and double-buffered, ``D`` by volume row, the
    full-width window rows and the query blocks."""
    k = 2 * radius + 1
    rows = min(_WIN_ROWS, hl)
    hp = -(-hl // rows) * rows
    wp, nx = _win_width(wl, k)
    maps = 2 * hp * wp * channels * (2 * feature_parts + 4)
    scratch = ((hp + 2 * k + k + 1) * wp + (k + 1) * nx) * block_q * 4
    blocks = 2 * block_q * (channels * (2 * feature_parts + 4)
                            + 4 * (k * k + 2))
    return maps + scratch + blocks


# What the two backwards cost, for lookup_backward_path.  The dense one
# moves the volume-sized cotangent three times: written by the kernel,
# read by XLA's sum over the iterations, read by the volume's product
# (v5e HBM, 819 GB/s; a floor: on a v5e it read 9.4 ms a call against
# 8.9 at 96x128 x 1 level, 2.8 against 1.3 at 46x62 x 4 levels, where
# the pooled levels' backward adds its own passes).  The window kernels'
# time grows with the queries of a level (their x stage and y passes) and
# with the padded columns of the rows a query's window reaches,
# (k + 1 + _WIN_ROWS) of them: a part per column and a part per column
# and MXU channel pass (4 flops at the v5e MXU's 197 TFLOP/s).  The two
# other constants fit the window kernels' v5e readings at those two
# geometries, 3.09 and 4.34 ms a call.
_DENSE_S_PER_BYTE = 3 / 819e9
_WIN_S_PER_QUERY = 1.56e-8
_WIN_S_PER_COL = 6.2e-12
_WIN_S_PER_COL_CH = 2.0e-14


def lookup_backward_path(platform: str, shapes, radius: int, channels: int,
                         storage_bytes: int, feature_bytes: int) -> str:
    """How a differentiated Mosaic lookup into a materialized pyramid
    returns its gradient: ``'windows'`` (the features' gradients over the
    windows read, :func:`pallas_pyramid_lookup_windows`) or ``'volume'``
    (the dense cotangent of every level, :func:`_pyr_bwd`).

    From what a program observes while it traces: ``shapes`` the
    query-minor levels ``(B, hl, wl, Npad)``, the window radius, the
    features' channels and bytes (bfloat16 features enter whole, wider
    ones as two bfloat16 halves), the volume's storage bytes.
    ``D`` takes one pass against each feature part, and a second (its
    low half) for a float32 volume.
    The dense cotangent's traffic grows as the square of the map, the
    window kernels' work as the map; the cheaper by the estimate above,
    where the kernels' blocks fit the VMEM budget, on a TPU, for a
    volume stored in floats (a quantized one sends no gradient back)."""
    if platform != "tpu" or storage_bytes not in (2, 4):
        return "volume"
    k = 2 * radius + 1
    parts = 1 if feature_bytes == 2 else 2
    passes = parts + (storage_bytes > 2)
    dense = windows = 0.0
    for B, hl, wl, npad in shapes:
        if not (hl and wl):
            continue
        if _win_vmem_bytes(hl, wl, radius, channels, _WIN_BLOCK_Q,
                           parts) > _PYR_LOOKUP_BUDGET:
            return "volume"
        dense += B * hl * wl * npad * storage_bytes * _DENSE_S_PER_BYTE
        cols = B * npad * (k + 1 + _WIN_ROWS) * _win_width(wl, k)[0]
        windows += B * npad * _WIN_S_PER_QUERY + cols * (
            _WIN_S_PER_COL + _WIN_S_PER_COL_CH * channels * passes)
    return "windows" if windows < dense else "volume"


def _bf16_parts(x, split: bool):
    """``x`` as bfloat16 arrays that sum to it: its high and low halves
    where ``split``, else itself rounded (exact for a bfloat16 ``x``)."""
    x32 = x.astype(jnp.float32)
    hi = x32.astype(jnp.bfloat16)
    if not split:
        return [hi]
    return [hi, (x32 - hi.astype(jnp.float32)).astype(jnp.bfloat16)]


def _win_bwd_level(lvl, f2, f1_parts, cpt, gl, k, block_q, d_halves,
                   interpret):
    """``(df1, df2)`` of one level, float32: ``f1_parts``
    ``(B, Npad, C)`` bfloat16, ``cpt`` ``(B, 2, Npad)``, ``gl`` ``(B,
    k*k, Npad)``, padded to ``block_q``; ``f2`` ``(B, hl, wl, C)``, split
    as ``f1`` is; ``d_halves`` as :func:`_win_bwd_kernel` takes it."""
    B, hl, wl, C = f2.shape
    Npad = cpt.shape[2]
    nparts = len(f1_parts)
    rows = min(_WIN_ROWS, hl)
    hp = -(-hl // rows) * rows
    wp, nx = _win_width(wl, k)
    f2_parts = [jnp.pad(p, ((0, 0), (0, hp - hl), (0, wp - wl), (0, 0)))
                for p in _bf16_parts(f2, nparts > 1)]
    kern = functools.partial(_win_bwd_kernel, lvl=lvl, hl=hl, k=k,
                             rows=rows, nparts=nparts, d_halves=d_halves,
                             inv_scale=1.0 / float(C) ** 0.5)
    qspec = pl.BlockSpec((1, block_q, C), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM)
    mspec = pl.BlockSpec((1, hp, wp, C), lambda b, i: (b, 0, 0, 0),
                         memory_space=pltpu.VMEM)
    df1, df2 = tpu_pallas_call(
        kern,
        grid=(B, Npad // block_q),
        in_specs=[pl.BlockSpec((1, 2, block_q), lambda b, i: (b, 0, i),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, k * k, block_q), lambda b, i: (b, 0, i),
                               memory_space=pltpu.VMEM)]
        + [qspec] * nparts + [mspec] * nparts,
        out_specs=[qspec, mspec],
        out_shape=[jax.ShapeDtypeStruct((B, Npad, C), jnp.float32),
                   jax.ShapeDtypeStruct((B, hp, wp, C), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((k + 1, wp, block_q), jnp.float32),
                        pltpu.VMEM((hp + 2 * k, wp, block_q), jnp.float32)]
        + ([pltpu.VMEM((k + 1, nx, block_q), jnp.float32)] if nx else []),
        interpret=interpret,
    )(cpt, gl, *f1_parts, *f2_parts)
    return df1, df2[:, :hl, :wl]


def window_grads(fmap1, fmap2_levels, coords, g, radius: int,
                 interpret=None, d_halves: bool = True,
                 bf16_values: bool = False):
    """The features' gradients of a lookup whose taps got cotangent ``g``
    ``(B, H1, W1, L*k*k)``: ``(df1, [df2 a level])`` in the features'
    dtypes, by the window kernels above (``fmap1`` ``(B, H1, W1, C)``,
    ``fmap2_levels`` the pooled target maps ``(B, hl, wl, C)``, ``coords``
    ``(B, H1, W1, 2)`` level-0 centres).  ``d_halves``: ``D`` enters the
    products as two bfloat16 halves (the precision of a float32 volume's
    dense cotangent) or rounded once (a bfloat16 volume's).
    ``bf16_values``: the maps, whatever their dtype, hold bfloat16
    values and enter the products whole."""
    if interpret is None:
        interpret = _auto_interpret()
    block_q = _WIN_BLOCK_Q
    B, H1, W1, C = fmap1.shape
    N = H1 * W1
    k = 2 * radius + 1
    Npad = -(-N // block_q) * block_q
    cpt = _pad_coords_oor(coords.reshape(B, N, 2).astype(jnp.float32),
                          Npad).transpose(0, 2, 1)
    gt = g.reshape(B, N, -1).transpose(0, 2, 1).astype(jnp.float32)
    gt = jnp.pad(gt, ((0, 0), (0, 0), (0, Npad - N)))
    # bfloat16 features enter whole, wider ones as two bfloat16 halves
    split = not bf16_values and any(
        x.dtype != jnp.bfloat16 for x in [fmap1, *fmap2_levels])
    f1_parts = [jnp.pad(p.reshape(B, N, C), ((0, 0), (0, Npad - N), (0, 0)))
                for p in _bf16_parts(fmap1, split)]
    df1 = jnp.zeros((B, N, C), jnp.float32)
    df2s = []
    for lvl, f2 in enumerate(fmap2_levels):
        if not (f2.shape[1] and f2.shape[2]):
            df2s.append(jnp.zeros_like(f2))
            continue
        d1, d2 = _win_bwd_level(lvl, f2, f1_parts, cpt,
                                gt[:, lvl * k * k:(lvl + 1) * k * k],
                                k, block_q, d_halves, interpret)
        df1 = df1 + d1[:, :N]
        df2s.append(d2.astype(f2.dtype))
    return df1.reshape(fmap1.shape).astype(fmap1.dtype), df2s


def pallas_pyramid_lookup_windows(pyramid, fmap1, fmap2_levels, coords,
                                  radius: int = 4, block_q: int = 128,
                                  interpret=None, out_dtype=jnp.float32,
                                  bf16_values: bool = False):
    """:func:`pallas_pyramid_lookup` of a pyramid built from ``fmap1`` and
    ``fmap2_levels`` (``fmap2`` pooled as :func:`raft_tpu.ops.corr.
    pool_fmap_pyramid` pools it: pooling the volume's target dims is
    correlating with the pooled map), whose backward returns the
    features' gradients over the windows the taps read
    (:func:`window_grads`) and none to the pyramid, which the caller
    builds from stopped features.  The forward is the same kernel's; the
    coordinates' cotangent is zero, as there.  ``bf16_values``: as
    :func:`window_grads` takes it."""
    d_halves = pyramid[0].dtype.itemsize > 2

    def lookup(p, f1, f2s, c):
        return _pyramid_lookup_windows(p, f1, tuple(f2s), c, radius,
                                       block_q, interpret, out_dtype,
                                       d_halves, bf16_values)

    return per_data_shard(lookup, (BATCH, BATCH, BATCH, BATCH))(
        pyramid, fmap1, list(fmap2_levels), coords)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _pyramid_lookup_windows(pyramid, fmap1, fmap2_levels, coords, radius,
                            block_q, interpret, out_dtype, d_halves,
                            bf16_values):
    return _pyramid_lookup(pyramid, coords, radius, block_q, interpret,
                           out_dtype)


def _pyr_win_fwd(pyramid, fmap1, fmap2_levels, coords, radius, block_q,
                 interpret, out_dtype, d_halves, bf16_values):
    out, _ = _pyr_fwd(pyramid, coords, radius, block_q, interpret,
                      out_dtype)
    return out, (fmap1, fmap2_levels, coords)


def _pyr_win_bwd(radius, block_q, interpret, out_dtype, d_halves,
                 bf16_values, residuals, g):
    fmap1, fmap2_levels, coords = residuals
    df1, df2s = window_grads(fmap1, fmap2_levels, coords, g, radius,
                             interpret=interpret, d_halves=d_halves,
                             bf16_values=bf16_values)
    return None, df1, tuple(df2s), jnp.zeros_like(coords)


_pyramid_lookup_windows.defvjp(_pyr_win_fwd, _pyr_win_bwd)


def pallas_pyramid_lookup_quantized(pyramid, coords, radius: int = 4,
                                    block_q: int = 128, interpret=None,
                                    out_dtype=jnp.float32):
    """Fused window sampling of a QUANTIZED materialized pyramid.

    ``pyramid``: list of :class:`raft_tpu.ops.corr.QuantizedLevel` in
    query-minor layout (``values (B, Hl, Wl, Npad)`` int8/fp8 +
    ``scale (B, 1, 1, 1)`` fp32, from
    :func:`raft_tpu.ops.corr.build_corr_pyramid_flat` with a quantized
    ``out_dtype``).  The kernel is the SAME one the fp32/bf16 path runs
    — the per-tile ``astype(jnp.float32)`` that already rides the VMEM
    load converts the codes, taps accumulate fp32 in VMEM — and because
    sampling is linear in the stored values the dequant is one
    per-level multiply on the (small) tap output, fused by XLA into the
    kernel epilogue.  An fp8 pyramid is a dtype swap upstream, not a
    different lookup.

    No ``custom_vjp``: the quantize boundary is stop_gradient'd
    upstream (codes are integers — no tangent space) and ``coords`` is
    detached per iteration by the refinement step, so autodiff treats
    the whole lookup as primal-only — the reference's unwired
    alt_cuda_corr backward, made explicit.  HBM cost of the resident
    pyramid drops 4x vs fp32 (2x vs bf16), plus the halved lookup read
    traffic.

    Returns ``(B, H1, W1, L * (2r+1)^2)`` ``out_dtype`` features.
    """
    def lookup(p, c):
        return _pyramid_lookup_quantized(p, c, radius, block_q,
                                         interpret, out_dtype)

    return per_data_shard(lookup, (BATCH, BATCH))(pyramid, coords)


def _pyramid_lookup_quantized(pyramid, coords, radius, block_q, interpret,
                              out_dtype):
    if interpret is None:
        interpret = _auto_interpret()
    values = [lv.values for lv in pyramid]
    scales = [lv.scale for lv in pyramid]
    B, H1, W1, _ = coords.shape
    N = H1 * W1
    Npad = values[0].shape[3]
    if Npad % block_q:
        raise ValueError(
            f"pyramid query dim {Npad} is not a multiple of block_q "
            f"{block_q}; build the pyramid with "
            f"build_corr_pyramid_flat(..., pad_q={block_q})")
    k = 2 * radius + 1
    L = len(values)
    c = _pad_coords_oor(
        jax.lax.stop_gradient(coords).reshape(B, N, 2).astype(jnp.float32),
        Npad).transpose(0, 2, 1)
    # Accumulate + emit fp32 from the kernel; the per-level dequant
    # multiply below needs full precision before the consumer cast.
    out = _pyr_levels_fwd(values, c, radius, block_q, interpret,
                          jnp.float32, rolled=True)    # (B, L*k*k, Npad)
    scale = jnp.concatenate(
        [s.reshape(B, 1) for s in scales], axis=1)     # (B, L)
    out = out.reshape(B, L, k * k, Npad) * scale[:, :, None, None]
    out = out.reshape(B, L * k * k, Npad)[:, :, :N]
    out = out.reshape(B, L * k * k, H1, W1).transpose(0, 2, 3, 1)
    return out.astype(out_dtype)


def pallas_corr_lookup(fmap1, fmap2_pyramid, coords, radius: int = 4,
                       block_q: int = 128, interpret=None):
    """Fused on-demand pyramid correlation lookup.

    Args:
      fmap1: ``(B, H1, W1, C)`` query features.
      fmap2_pyramid: sequence of pooled target features
        ``(B, Hl, Wl, C)`` (from :func:`raft_tpu.ops.corr.pool_fmap_pyramid`).
      coords: ``(B, H1, W1, 2)`` level-0 centroids, last axis ``(x, y)``.
      radius: window radius r.
      block_q: query pixels per kernel instance (MXU-aligned).
      interpret: force pallas interpreter (default: auto — on for non-TPU
        backends so tests run on CPU).

    Returns:
      ``(B, H1, W1, L * (2r+1)^2)`` fp32 lookup features.
    """
    def lookup(f1, f2p, c):
        return _corr_lookup(f1, f2p, c, radius, block_q, interpret)

    return per_data_shard(lookup, (BATCH, BATCH, BATCH))(
        fmap1, fmap2_pyramid, coords)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _corr_lookup(fmap1, fmap2_pyramid, coords, radius, block_q, interpret):
    out, _ = _corr_fwd(fmap1, fmap2_pyramid, coords, radius, block_q,
                       interpret)
    return out


def _odm_levels(fmap2_pyramid, k):
    nonempty = [(lvl, f2) for lvl, f2 in enumerate(fmap2_pyramid)
                if f2.shape[1] > 0 and f2.shape[2] > 0]
    levels = [(lvl, lvl * k * k, f2.shape[1], f2.shape[2])
              for lvl, f2 in nonempty]
    return nonempty, levels


def _odm_f2_dtype(nonempty, block_q):
    """fp32 f2 blocks whenever they fit the VMEM budget; bf16 beyond.

    At beyond-HBM shapes (the path's whole purpose — e.g. 1440x2560,
    where fp32 f2 + correlation scratch is ~118 MB against the 100 MB
    budget) bf16 f2 (fp32 accumulation via preferred_element_type)
    halves the resident footprint.  The threshold models the actual
    per-instance residency — f2 levels + per-level scratch + the query
    blocks — against the declared 100 MB limit with headroom for
    double-buffered block DMA, so fp32 is kept as long as it genuinely
    fits (1080p-class included)."""
    if not nonempty:
        return jnp.float32
    C = nonempty[0][1].shape[-1]
    rows = sum(f2.shape[1] * f2.shape[2] for _, f2 in nonempty)
    f2_bytes = rows * C * 4
    scratch_bytes = rows * block_q * 4
    blocks_bytes = 4 * block_q * (2 * C + 2 + 81 * len(nonempty)) * 2
    if f2_bytes + scratch_bytes + blocks_bytes > 88 * 1024 * 1024:
        return jnp.bfloat16
    return jnp.float32


def _corr_fwd(fmap1, fmap2_pyramid, coords, radius, block_q, interpret):
    if interpret is None:
        interpret = _auto_interpret()
    B, H1, W1, C = fmap1.shape
    N = H1 * W1
    k = 2 * radius + 1
    L = len(fmap2_pyramid)
    f1 = fmap1.reshape(B, N, C).astype(jnp.float32)
    c = coords.reshape(B, N, 2).astype(jnp.float32)
    f1p, cp, _ = _pad_queries(f1, c, block_q)
    Npad = f1p.shape[1]

    nonempty, levels = _odm_levels(fmap2_pyramid, k)
    f2dt = _odm_f2_dtype(nonempty, block_q)
    kern = functools.partial(_odm_fwd_kernel, levels=levels, k=k,
                             kk_total=L * k * k,
                             inv_scale=1.0 / float(C) ** 0.5)
    in_specs = [
        pl.BlockSpec((1, f2.shape[1], f2.shape[2], C),
                     lambda b, i: (b, 0, 0, 0), memory_space=pltpu.VMEM)
        for _, f2 in nonempty
    ] + [
        pl.BlockSpec((1, block_q, C), lambda b, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 2, block_q), lambda b, i: (b, 0, i),
                     memory_space=pltpu.VMEM),
    ]
    out = tpu_pallas_call(
        kern,
        grid=(B, Npad // block_q),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, L * k * k, block_q),
                               lambda b, i: (b, 0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, L * k * k, Npad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((f2.shape[1] * f2.shape[2], block_q), jnp.float32)
            for _, f2 in nonempty
        ],
        interpret=interpret,
    )(*[f2.astype(f2dt) for _, f2 in nonempty], f1p,
      cp.transpose(0, 2, 1))
    out = out[:, :, :N].reshape(B, L * k * k, H1, W1).transpose(0, 2, 3, 1)
    return out, (fmap1, tuple(fmap2_pyramid), coords)


def _corr_bwd(radius, block_q, interpret, residuals, g):
    fmap1, fmap2_pyramid, coords = residuals
    if interpret is None:
        interpret = _auto_interpret()
    B, H1, W1, C = fmap1.shape
    N = H1 * W1
    k = 2 * radius + 1
    L = len(fmap2_pyramid)
    inv_scale = 1.0 / float(C) ** 0.5
    f1 = fmap1.reshape(B, N, C).astype(jnp.float32)
    c = coords.reshape(B, N, 2).astype(jnp.float32)
    g_base = g.reshape(B, N, -1).transpose(0, 2, 1).astype(jnp.float32)

    nonempty, _ = _odm_levels(fmap2_pyramid, k)
    blocked, fused = _partition_bwd_levels(nonempty, block_q, k)

    df1_acc = jnp.zeros((B, N, C), jnp.float32)
    df2_by_level = {}

    if fused:
        f1p, cp, _ = _pad_queries(f1, c, block_q)
        Npad = f1p.shape[1]
        gp = g_base
        if Npad != N:
            gp = jnp.pad(gp, ((0, 0), (0, 0), (0, Npad - N)))
        levels = [(lvl, lvl * k * k, f2.shape[1], f2.shape[2])
                  for lvl, f2 in fused]
        f2dt = _odm_f2_dtype(fused, block_q)
        kern = functools.partial(_odm_bwd_kernel, levels=levels, k=k,
                                 inv_scale=inv_scale)
        in_specs = [
            pl.BlockSpec((1, f2.shape[1], f2.shape[2], C),
                         lambda b, i: (b, 0, 0, 0),
                         memory_space=pltpu.VMEM)
            for _, f2 in fused
        ] + [
            pl.BlockSpec((1, block_q, C), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, block_q), lambda b, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, L * k * k, block_q), lambda b, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ]
        out_specs = (pl.BlockSpec((1, block_q, C), lambda b, i: (b, i, 0),
                                  memory_space=pltpu.VMEM),) + tuple(
            pl.BlockSpec((1, f2.shape[1], f2.shape[2], C),
                         lambda b, i: (b, 0, 0, 0),
                         memory_space=pltpu.VMEM)
            for _, f2 in fused)
        out_shape = (jax.ShapeDtypeStruct((B, Npad, C),
                                          jnp.float32),) + tuple(
            jax.ShapeDtypeStruct((B, f2.shape[1], f2.shape[2], C),
                                 jnp.float32)
            for _, f2 in fused)
        outs = tpu_pallas_call(
            kern,
            grid=(B, Npad // block_q),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((f2.shape[1] * f2.shape[2], block_q),
                           jnp.float32)
                for _, f2 in fused
            ],
            interpret=interpret,
        )(*[f2.astype(f2dt) for _, f2 in fused], f1p,
          cp.transpose(0, 2, 1), gp)
        df1_acc = df1_acc + outs[0][:, :N]
        for (lvl, _), out in zip(fused, outs[1:]):
            df2_by_level[lvl] = out

    if blocked:
        # Captured at TRACE time: this read happens inside the custom-vjp
        # backward while jit traces it, and the jit cache keys on shapes/
        # dtypes only — changing RAFT_ODM_BWD_BLOCK_Q later in the same
        # process silently returns the old program (an in-process sweep
        # would record identical timings for different nominal values).
        # Sweep with a fresh process per value, or plumb it through
        # RAFTConfig like lookup_block_q.
        bq2 = int(os.environ.get("RAFT_ODM_BWD_BLOCK_Q", _BWD_BLOCK_Q))
        f1p2, cp2, _ = _pad_queries(f1, c, bq2)
        Npad2 = f1p2.shape[1]
        gp2 = g_base
        if Npad2 != N:
            gp2 = jnp.pad(gp2, ((0, 0), (0, 0), (0, Npad2 - N)))
        cpt2 = cp2.transpose(0, 2, 1)
        # Stream f2 in the dtype the FUSED path would store for the whole
        # pyramid (bf16 at beyond-HBM shapes, fp32 at small ones) — the
        # df1 kernel re-reads the level once per query block, so the
        # dtype is the dominant DMA knob.
        f2dt_blocked = _odm_f2_dtype(nonempty, block_q)
        for lvl, f2 in blocked:
            df1_l, df2_l = _odm_bwd_blocked_level(
                lvl, f2, f1p2, cpt2, gp2, k, inv_scale, bq2, interpret,
                f2_dtype=f2dt_blocked)
            df1_acc = df1_acc + df1_l[:, :N]
            df2_by_level[lvl] = df2_l

    df1 = df1_acc.reshape(fmap1.shape).astype(fmap1.dtype)
    df2s = []
    for lvl, f2 in enumerate(fmap2_pyramid):
        if lvl in df2_by_level:
            df2s.append(df2_by_level[lvl].astype(f2.dtype))
        else:
            df2s.append(jnp.zeros_like(f2))
    # coords gradient is structurally zero (reference detaches coords each
    # iteration, raft.py:123; CUDA kernel never fills coords_grad).
    return df1, tuple(df2s), jnp.zeros_like(coords)


_corr_lookup.defvjp(_corr_fwd, _corr_bwd)


# ---------------------------------------------------------------------------
# Fused lookup -> motion-encoder convc1 (fused_lookup_encoder)
#
# The pyramid lookup's ONLY consumer is the motion encoder's first conv
# (models/update.py convc1) — a 1x1 conv over the (2r+1)^2*levels tap
# channels.  Unfused, the (B, H/8, W/8, 324) tap tensor round-trips HBM
# between the lookup kernel and the conv.  Here the tap block stays in
# the VMEM scratch the lookup already accumulates into and feeds one MXU
# contraction (taps^T @ W) + bias + relu in the same kernel instance:
# the tap tensor never materializes.
#
# Quantized pyramids ride for free: sampling is linear in the stored
# codes and the conv is linear in the taps, so the per-(batch, level)
# dequant scale FOLDS INTO THE CONV WEIGHTS (w_l <- scale_bl * w_l) —
# the kernel contracts raw-code taps against pre-scaled weights, fp32
# accumulation end to end, and dequant-on-tap semantics are preserved
# exactly.  Backward is a recomputing custom_vjp: relu-masked cotangent
# -> dW/db by re-running the (unfused) lookup, pyramid/coords
# cotangents via the unfused lookup's own vjp (real dcorr for fp32/bf16
# pyramids, structural zeros for quantized ones — the same stop-gradient
# boundary, so fnet still gets zero grad through a quantized volume).
# ---------------------------------------------------------------------------


def _pyr_encode_kernel(*refs, levels, k, kk_pad):
    """Fused taps -> 1x1 conv (+bias+relu) kernel body.

    refs = [corr_0..corr_{n-1}, c, w, bias, out, taps, acc_0..acc_{n-1}];
    ``w`` is (1, kk_pad, Fpad) fp32 with any dequant scale pre-folded,
    ``taps`` a (1, kk_pad, BQ) fp32 VMEM scratch standing in for the
    unfused kernel's HBM tap output.  The mat-mul runs once per grid
    instance, OUTSIDE the row-tile loops (in-loop mat-muls regress to
    scalar code — see the Mosaic lessons at the top of this file).
    """
    nl = len(levels)
    c_ref = refs[nl]
    w_ref = refs[nl + 1]
    b_ref = refs[nl + 2]
    out_ref = refs[nl + 3]
    taps_ref = refs[nl + 4]
    acc_refs = refs[nl + 5:]
    bq = c_ref.shape[2]
    covered = 0
    for (lvl, off, hl, wl), corr_ref, acc_ref in zip(levels, refs[:nl],
                                                     acc_refs):
        _pyr_fwd_level_body(corr_ref, c_ref, taps_ref, acc_ref, lvl, off,
                            hl, wl, k)
        covered += k * k
    if covered < kk_pad:  # empty trailing levels + sublane-pad rows
        taps_ref[0, covered:, :] = jnp.zeros((kk_pad - covered, bq),
                                             taps_ref.dtype)
    taps = taps_ref[0]                                 # (kk_pad, BQ)
    out = jax.lax.dot_general(
        taps, w_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (BQ, Fpad)
    out = jnp.maximum(out + b_ref[...], 0.0)
    out_ref[0, :, :] = out.astype(out_ref.dtype)


def _pyr_levels_fwd_encode(values, coords_p, w_scaled, bias, radius,
                           block_q, interpret):
    """One pallas_call: lookup + convc1 -> (B, Npad, Fpad) fp32."""
    B = values[0].shape[0]
    Npad = values[0].shape[3]
    k = 2 * radius + 1
    nonempty, levels = _odm_levels(values, k)
    kk_pad, fpad = w_scaled.shape[1], w_scaled.shape[2]
    kern = functools.partial(_pyr_encode_kernel, levels=levels, k=k,
                             kk_pad=kk_pad)
    in_specs = [
        pl.BlockSpec((1, c.shape[1], c.shape[2], block_q),
                     lambda b, i: (b, 0, 0, i),
                     memory_space=pltpu.VMEM)
        for _, c in nonempty
    ] + [
        pl.BlockSpec((1, 2, block_q), lambda b, i: (b, 0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, kk_pad, fpad), lambda b, i: (b, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, fpad), lambda b, i: (0, 0),
                     memory_space=pltpu.VMEM),
    ]
    return tpu_pallas_call(
        kern,
        grid=(B, Npad // block_q),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, fpad),
                               lambda b, i: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, Npad, fpad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, kk_pad, block_q), jnp.float32)] + [
            pltpu.VMEM((k * c.shape[2], block_q), jnp.float32)
            for _, c in nonempty
        ],
        interpret=interpret,
    )(*[c for _, c in nonempty], coords_p, w_scaled, bias)


def _is_quantized_pyramid(pyramid) -> bool:
    # Duck-typed (QuantizedLevel carries .values/.scale) so this module
    # needs no import from ops.corr.
    return hasattr(pyramid[0], "values")


def pallas_pyramid_lookup_encode(pyramid, coords, weight, bias,
                                 radius: int = 4, block_q: int = 128,
                                 interpret=None, out_dtype=jnp.float32):
    """Fused pyramid lookup + motion-encoder convc1 (+bias+relu).

    Equivalent to::

        corr = pallas_pyramid_lookup[_quantized](pyramid, coords, ...)
        out  = relu(corr @ weight + bias)       # the 1x1 convc1

    but the ``(B, H1, W1, L*(2r+1)^2)`` tap tensor never reaches HBM:
    taps accumulate in the lookup's VMEM scratch and feed the conv
    contraction in the same kernel instance (fp32 accumulation both
    stages).  Accepts plain (fp32/bf16) OR quantized
    (:class:`~raft_tpu.ops.corr.QuantizedLevel`) pyramids — for the
    latter the per-(batch, level) dequant scale is folded into
    ``weight`` before the kernel, preserving dequant-on-tap semantics
    exactly.

    Args:
      pyramid: query-minor levels from ``build_corr_pyramid_flat``
        (arrays or QuantizedLevel; Npad a multiple of ``block_q``).
      coords: ``(B, H1, W1, 2)`` level-0 centroids (detached inside —
        coords cotangent is structurally zero, matching the unfused
        refinement-step contract).
      weight: ``(L*(2r+1)^2, F)`` convc1 kernel (the HWIO ``(1,1,KK,F)``
        conv param reshaped).
      bias: ``(F,)`` convc1 bias.

    Returns ``(B, H1, W1, F)`` ``out_dtype`` activations.

    Gradients: ``weight``/``bias`` always; pyramid cotangents are real
    for fp32/bf16 storage (delegated to the unfused lookup's vjp) and
    structural zeros for quantized storage (the stop-gradient boundary
    — fnet gets zero grad through a quantized volume, unchanged).
    """
    def encode(p, c, w, b):
        return _pyramid_lookup_encode(p, c, w, b, radius, block_q,
                                      interpret, out_dtype)

    return per_data_shard(encode, (BATCH, BATCH, WHOLE, WHOLE))(
        pyramid, coords, weight, bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _pyramid_lookup_encode(pyramid, coords, weight, bias, radius, block_q,
                           interpret, out_dtype):
    out, _ = _pyr_enc_fwd(pyramid, coords, weight, bias, radius, block_q,
                          interpret, out_dtype)
    return out


def _pyr_enc_fwd(pyramid, coords, weight, bias, radius, block_q,
                 interpret, out_dtype):
    if interpret is None:
        interpret = _auto_interpret()
    quantized = _is_quantized_pyramid(pyramid)
    values = [lv.values if quantized else lv for lv in pyramid]
    B, H1, W1, _ = coords.shape
    N = H1 * W1
    Npad = values[0].shape[3]
    if Npad % block_q:
        raise ValueError(
            f"pyramid query dim {Npad} is not a multiple of block_q "
            f"{block_q}; build the pyramid with "
            f"build_corr_pyramid_flat(..., pad_q={block_q})")
    k = 2 * radius + 1
    L = len(values)
    kk = L * k * k
    if weight.shape != (kk, weight.shape[1]) or weight.shape[0] != kk:
        raise ValueError(
            f"weight shape {weight.shape} does not match the tap count "
            f"levels*(2r+1)^2 = {kk}")
    F = weight.shape[1]
    kk_pad = -(-kk // 8) * 8          # sublane-tile align the contraction
    fpad = -(-F // 128) * 128         # lane-tile align the conv features
    c = _pad_coords_oor(
        jax.lax.stop_gradient(coords).reshape(B, N, 2).astype(jnp.float32),
        Npad).transpose(0, 2, 1)
    w32 = weight.astype(jnp.float32)
    if quantized:
        scale = jnp.concatenate(
            [lv.scale.reshape(B, 1) for lv in pyramid], axis=1)  # (B, L)
        wb = w32.reshape(L, k * k, F)[None] * scale[:, :, None, None]
        wb = wb.reshape(B, kk, F)
    else:
        wb = jnp.broadcast_to(w32[None], (B, kk, F))
    wb = jnp.pad(wb, ((0, 0), (0, kk_pad - kk), (0, fpad - F)))
    b2 = jnp.pad(bias.astype(jnp.float32).reshape(1, F),
                 ((0, 0), (0, fpad - F)))
    out = _pyr_levels_fwd_encode(values, c, wb, b2, radius, block_q,
                                 interpret)
    out = out[:, :N, :F].reshape(B, H1, W1, F).astype(out_dtype)
    return out, (pyramid, coords, weight, bias, out)


def _pyr_enc_bwd(radius, block_q, interpret, out_dtype, residuals, g):
    pyramid, coords, weight, bias, out = residuals
    if interpret is None:
        interpret = _auto_interpret()
    quantized = _is_quantized_pyramid(pyramid)
    # relu mask from the saved activations; fp32 math below.
    gm = (g * (out > 0)).astype(jnp.float32)           # (B, H1, W1, F)
    if quantized:
        # Recompute the DEQUANTIZED taps (dW is w.r.t. the scaled
        # contraction the forward ran); codes/scales get structural
        # zeros — int codes have no tangent space (float0), and the
        # scale sits behind the same stop-gradient as the codes.
        corr = _pyramid_lookup_quantized(
            pyramid, coords, radius, block_q, interpret, jnp.float32)

        def _zero_ct(x):
            if jnp.issubdtype(x.dtype, jnp.inexact):
                return jnp.zeros_like(x)
            import numpy as np

            return np.zeros(x.shape, jax.dtypes.float0)

        dpyr = jax.tree_util.tree_map(_zero_ct, pyramid)
        dcoords = jnp.zeros_like(coords)
    else:
        # Delegate to the unfused lookup's own vjp: identical recompute
        # + transpose kernels, so the fused path inherits the exact
        # unfused gradient semantics (real per-level dcorr, zero
        # dcoords).
        def lookup(p, c):
            return _pyramid_lookup(p, c, radius, block_q, interpret,
                                   jnp.float32)

        corr, pullback = jax.vjp(lookup, pyramid, coords)
        g_corr = jnp.einsum("bhwf,kf->bhwk", gm,
                            weight.astype(jnp.float32))
        dpyr, dcoords = pullback(g_corr)
    dw = jnp.einsum("bhwk,bhwf->kf", corr.astype(jnp.float32), gm)
    db = jnp.sum(gm, axis=(0, 1, 2))
    return dpyr, dcoords, dw.astype(weight.dtype), db.astype(bias.dtype)


_pyramid_lookup_encode.defvjp(_pyr_enc_fwd, _pyr_enc_bwd)
