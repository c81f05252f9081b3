"""Single-head attention inside the K x K windows of a feature map, as two
Mosaic kernels (forward, backward): GMFlow's window attention
(``models/gmflow.py window_attention``) with a window's scores, their
softmax and ``dP`` / ``dS`` never leaving VMEM.

What XLA makes of the ``jnp`` body writes a float32 ``(windows, n, n)``
score array to HBM and reads it back, twice forward and twice backward
(PERF.md section 5: 49.5 ms of a 169.5 ms train step for products that
need 7.1).  A window's whole score matrix is small (768 x 768 float32 =
2.4 MB at the chairs crop), so one grid step holds it: a block of the
window's query rows against ALL of the window's keys and values, which are
resident -- no online softmax, a row sees every key it can attend to.

Addressing.  The maps come as ``(B, h, w, C)`` and are viewed, for free,
as ``(B, K, h/K, K, w/K, C)``; a window (or a block of its rows) is the
block ``(., ., rows, ., w/K, C)`` of that view, so no ``split_windows`` /
``merge_windows`` relayout stands in front of or behind the kernel, and in
VMEM ``(rows, w/K, C) -> (rows * w/K, C)`` keeps the layout when ``w/K``
is a multiple of the dtype's sublane tile (:func:`window_attention_path`
asks for that).

Orientation.  Scores are held KEY-major, ``(n, bq)``: keys along sublanes,
the block's queries along lanes.  Every per-query statistic (maximum, sum,
log-sum-exp, ``rowsum(dO * O)``) is then a ``(1, bq)`` row, reductions run
over sublanes (plain vector maxima and adds, no cross-lane work), the
log-sum-exp residual is a dense ``(windows, 1, n)`` float32 array, and of
the seven products two contract over the keys' axis of a score-shaped
operand: ``dq = dS^T k``, which Mosaic takes as it is, and ``O``, which is
made as ``(C, bq)`` so that the row ``1 / l`` normalises it, and transposed
once, small.  On a v5e at the chairs crop (128 windows of 768 tokens,
bfloat16; PERF.md section 6, PR 35) that is 0.30 ms forward and 0.57
backward; the query-major twin (lane reductions, two transposed products
and no log-sum-exp) 0.28 and 0.63, half windows a grid step 0.49 and 0.68.

Precision is the ``jnp`` body's: ``q``, ``k``, ``v`` in the compute dtype,
every product accumulated in float32, scores times ``1/sqrt(C)`` and the
additive region mask (0 within a region, ``mask_value`` across) in
float32, a float32 softmax, ``P`` rounded to the compute dtype for ``P v``
(normalised after the product, in float32).  Backward: ``dv = P^T dO``,
``dP = dO v^T``, ``dS = P * (dP - rowsum(dO * O))`` in float32, ``dq = dS
k / sqrt(C)``, ``dk = dS^T q / sqrt(C)``; ``P`` is rebuilt from ``q``,
``k`` and the saved log-sum-exp.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops.pallas_util import (BATCH, WHOLE, per_data_shard,
                                      tpu_pallas_call)

_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b

# VMEM a grid step may plan on, by the estimate below, under the 100 MiB
# ``vmem_limit_bytes`` every kernel here declares.  The estimate follows
# what Mosaic allocates for a v5e (the least limit it compiles the backward
# under, ``tests/test_chip_compile.py``): 9.4 MiB estimated where it takes
# 9 (a whole 24 x 32 window, bfloat16), 22.3 for 21 (half of a 28 x 64
# one), 39.4 for 34 (all of it), 27.6 for 26 (half, float32).
_ATTN_BUDGET = 48 * 1024 * 1024


def window_attention_vmem_bytes(rows: int, hk: int, wk: int, channels: int,
                                itemsize: int) -> int:
    """VMEM one grid step of the BACKWARD kernel holds (the forward holds
    about half) for a block of ``rows`` rows of an ``(hk, wk)`` window: of
    its ``(n, bq)`` temporaries (scores, ``P``, ``dP``, ``dS`` in float32,
    ``P`` and ``dS`` rounded for their products) Mosaic keeps about two
    float32 ones and a rounded one alive at a time, 9 bytes an entry by
    its own accounting whatever the compute dtype, counted here as 10; the
    window's ``k``, ``v``, ``dk``, ``dv`` and the block's ``q``, ``O``,
    ``dO``, ``dq`` double-buffered by the pipeline; and the two float32
    accumulators."""
    n, bq = hk * wk, rows * wk
    blocks = 2 * (4 * n + 4 * bq) * channels * itemsize
    return 10 * n * bq + blocks + 2 * n * channels * 4


def window_block_rows(hk: int, wk: int, channels: int,
                      itemsize: int) -> Optional[int]:
    """Rows of a window a grid step takes: the most that divide ``hk``, give
    whole lane tiles of queries (``rows * wk`` a multiple of 128, or the
    whole window) and fit the budget; ``None`` when no block does, or when
    ``wk`` is not a multiple of the dtype's sublane tile (the in-VMEM
    reshape would not keep the layout) or ``channels`` of a lane tile."""
    if wk % (8 * max(4 // itemsize, 1)) or channels % 128:
        return None
    for rows in range(hk, 0, -1):
        if hk % rows or (rows != hk and (rows * wk) % 128):
            continue
        if window_attention_vmem_bytes(rows, hk, wk, channels,
                                       itemsize) <= _ATTN_BUDGET:
            return rows
    return None


def window_attention_path(platform: str, hk: int, wk: int, channels: int,
                          itemsize: int, rows_split: bool = False) -> str:
    """Which window attention runs for windows of ``(hk, wk)`` tokens:
    ``'mosaic'`` (the kernels below) or ``'xla'`` (the ``jnp`` body of
    ``models/gmflow.py window_attention``).

    The one place this is decided, from what the code can observe when it
    traces, as :func:`raft_tpu.ops.pallas_corr.pyramid_lookup_path` does
    for the lookup: Mosaic on a TPU, with whole images on each device and
    a block of the window that is aligned and inside the VMEM budget
    (:func:`window_block_rows`); XLA everywhere else -- off TPU the kernel
    only runs in the interpreter, an unaligned window would be relaid in
    VMEM, a block over the budget does not compile, and GSPMD cannot
    partition a Mosaic call over rows."""
    if platform != "tpu" or rows_split:
        return "xla"
    fits = window_block_rows(hk, wk, channels, itemsize) is not None
    return "mosaic" if fits else "xla"


def _scale(channels: int) -> float:
    return 1.0 / float(channels) ** 0.5


def _scores(q, k, rq_ref, rk_ref, scale, mask_value):
    """``(n, bq)`` float32: ``k q^T / sqrt(C)``, plus the region mask where
    the window has one (``rq_ref`` ``(1, bq)``, ``rk_ref`` ``(n, 1)``)."""
    s = jax.lax.dot_general(k, q, _NT,
                            preferred_element_type=jnp.float32) * scale
    if rq_ref is not None:
        s = s + jnp.where(rk_ref[...] == rq_ref[...], 0.0, mask_value)
    return s


def _fwd_kernel(*refs, scale, mask_value, masked):
    q_ref, k_ref, v_ref = refs[:3]
    rq_ref, rk_ref = refs[3:5] if masked else (None, None)
    o_ref, lse_ref = refs[-2:]
    rows, wk, C = q_ref.shape
    dt = q_ref.dtype
    q = q_ref[...].reshape(rows * wk, C)
    k = k_ref[...].reshape(-1, C)
    v = v_ref[...].reshape(-1, C)
    s = _scores(q, k, rq_ref, rk_ref, scale, mask_value)
    m = jnp.max(s, axis=0, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=0, keepdims=True)
    o = jax.lax.dot_general(v, p.astype(dt), _TN,
                            preferred_element_type=jnp.float32)   # (C, bq)
    o_ref[...] = (o * (1.0 / l)).T.astype(dt).reshape(rows, wk, C)
    lse_ref[...] = m + jnp.log(l)


def _bwd_kernel(*refs, scale, mask_value, masked):
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[:6]
    rq_ref, rk_ref = refs[6:8] if masked else (None, None)
    dq_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs[-5:]
    rows, wk, C = q_ref.shape
    dt = q_ref.dtype
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[...].reshape(rows * wk, C)
    k = k_ref[...].reshape(-1, C)
    v = v_ref[...].reshape(-1, C)
    do = do_ref[...].reshape(rows * wk, C)
    o = o_ref[...].reshape(rows * wk, C)
    p = jnp.exp(_scores(q, k, rq_ref, rk_ref, scale, mask_value)
                - lse_ref[...])
    dv_acc[...] += jnp.dot(p.astype(dt), do,
                           preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    # rowsum(dO * O) as a (1, bq) row: a ones-row product, in float32
    delta = jax.lax.dot_general(
        jnp.ones((8, C), jnp.float32),
        do.astype(jnp.float32) * o.astype(jnp.float32), _NT,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[0:1]
    ds = (p * (dp - delta)).astype(dt)
    dk_acc[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
    dq = jax.lax.dot_general(ds, k, _TN, preferred_element_type=jnp.float32)
    dq_ref[...] = (dq * scale).astype(dt).reshape(rows, wk, C)

    @pl.when(r == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dt).reshape(dk_ref.shape)
        dv_ref[...] = dv_acc[...].astype(dt).reshape(dv_ref.shape)


def _specs(splits, hk, wk, C, rows, regions):
    """Grid ``(K, K, B, row blocks)``, the batch inside the window so that a
    window's region ids are fetched once: the specs of a block of rows and
    of a whole window of the ``(B, K, hk, K, wk, C)`` view, of the rows'
    log-sum-exp ``(B, K, K, 1, n)``, and the region ids as operands with
    their specs (``(K, K, 1, n)`` by block, ``(K, K, n, 1)`` whole)."""
    n, bq = hk * wk, rows * wk
    blk = pl.BlockSpec((None, None, rows, None, wk, C),
                       lambda i, j, b, r: (b, i, r, j, 0, 0))
    whole = pl.BlockSpec((None, None, hk, None, wk, C),
                         lambda i, j, b, r: (b, i, 0, j, 0, 0))
    lse = pl.BlockSpec((None, None, None, 1, bq),
                       lambda i, j, b, r: (b, i, j, 0, r))
    if regions is None:
        return blk, whole, lse, [], []
    rid = jnp.asarray(regions, jnp.int32)
    return blk, whole, lse, [
        rid.reshape(splits, splits, 1, n), rid.reshape(splits, splits, n, 1)
    ], [pl.BlockSpec((None, None, 1, bq), lambda i, j, b, r: (i, j, 0, r)),
        pl.BlockSpec((None, None, n, 1), lambda i, j, b, r: (i, j, 0, 0))]


def _forward(q, k, v, regions, splits, mask_value, rows, interpret):
    B, h, w, C = q.shape
    hk, wk = h // splits, w // splits
    view = (B, splits, hk, splits, wk, C)
    blk, whole, lse, rid, rid_specs = _specs(splits, hk, wk, C, rows,
                                             regions)
    out, stats = tpu_pallas_call(
        functools.partial(_fwd_kernel, scale=_scale(C),
                          mask_value=mask_value, masked=bool(rid)),
        grid=(splits, splits, B, hk // rows),
        in_specs=[blk, whole, whole] + rid_specs, out_specs=(blk, lse),
        out_shape=(jax.ShapeDtypeStruct(view, q.dtype),
                   jax.ShapeDtypeStruct((B, splits, splits, 1, hk * wk),
                                        jnp.float32)),
        interpret=interpret, name="window_attention_fwd",
    )(q.reshape(view), k.reshape(view), v.reshape(view), *rid)
    return out.reshape(B, h, w, C), stats


def _backward(q, k, v, o, do, stats, regions, splits, mask_value, rows,
              interpret):
    B, h, w, C = q.shape
    hk, wk = h // splits, w // splits
    view = (B, splits, hk, splits, wk, C)
    blk, whole, lse, rid, rid_specs = _specs(splits, hk, wk, C, rows,
                                             regions)
    shape = jax.ShapeDtypeStruct(view, q.dtype)
    grads = tpu_pallas_call(
        functools.partial(_bwd_kernel, scale=_scale(C),
                          mask_value=mask_value, masked=bool(rid)),
        grid=(splits, splits, B, hk // rows),
        in_specs=[blk, whole, whole, blk, blk, lse] + rid_specs,
        out_specs=(blk, whole, whole), out_shape=(shape, shape, shape),
        scratch_shapes=[pltpu.VMEM((hk * wk, C), jnp.float32),
                        pltpu.VMEM((hk * wk, C), jnp.float32)],
        interpret=interpret, name="window_attention_bwd",
    )(*(x.reshape(view) for x in (q, k, v, o, do)), stats, *rid)
    return tuple(g.reshape(B, h, w, C) for g in grads)


def _rolled(x, shift, back=False):
    if shift is None:
        return x
    return jnp.roll(x, shift if back else (-shift[0], -shift[1]),
                    axis=(1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _attention(q, k, v, regions, splits, shift, mask_value, rows, interpret):
    return _attention_fwd(q, k, v, regions, splits, shift, mask_value, rows,
                          interpret)[0]


def _attention_fwd(q, k, v, regions, splits, shift, mask_value, rows,
                   interpret):
    q, k, v = (_rolled(x, shift) for x in (q, k, v))
    out, stats = _forward(q, k, v, regions, splits, mask_value, rows,
                          interpret)
    out = _rolled(out, shift, back=True)
    # the result is kept as the caller has it (the projection behind it
    # keeps that array anyway) and rolled once more in the backward pass
    return out, (q, k, v, out, stats, regions)


def _attention_bwd(splits, shift, mask_value, rows, interpret, res, do):
    q, k, v, out, stats, regions = res
    grads = _backward(q, k, v, _rolled(out, shift), _rolled(do, shift),
                      stats, regions, splits, mask_value, rows, interpret)
    return tuple(_rolled(g, shift, back=True) for g in grads) + (None,)


_attention.defvjp(_attention_fwd, _attention_bwd)


def window_attention(q, k, v, splits: int, shift=None, regions=None,
                     mask_value: float = 0.0,
                     block_rows: Optional[int] = None, interpret=None):
    """``softmax(q k^T / sqrt(C) + mask) v`` inside each of the ``splits x
    splits`` windows of the ``(B, h, w, C)`` maps ``q``, ``k``, ``v``;
    returns the ``(B, h, w, C)`` map of messages, in their dtype.

    ``shift``: ``None``, or ``(rows, columns)`` by which the maps are rolled
    up and left before the windows are cut, and the messages back after
    (``jnp.roll``: the one relayout left around the kernels).
    ``regions``: ``None``, or ``(splits * splits, n)`` int32, a region id a
    token of each window of the rolled maps (windows and their tokens
    row-major): tokens of different regions get ``mask_value`` added to
    their score.  ``block_rows``: rows of a window a grid step takes
    (default :func:`window_block_rows`; a test hands a smaller one).
    Differentiable in ``q``, ``k``, ``v``; under a data-parallel mesh the
    kernels run per batch shard
    (:func:`raft_tpu.ops.pallas_util.per_data_shard`)."""
    B, h, w, C = q.shape
    if block_rows is None:
        block_rows = window_block_rows(h // splits, w // splits, C,
                                       q.dtype.itemsize)
        if block_rows is None:
            raise ValueError(
                f"no block of a {h // splits}x{w // splits} window of "
                f"{q.dtype.name} fits the kernel: ask "
                "window_attention_path first")
    static = (splits, shift and tuple(shift), mask_value, block_rows,
              interpret)
    ids = () if regions is None else (jnp.asarray(regions, jnp.int32),)

    def attend(q, k, v, *ids):
        return _attention(q, k, v, ids[0] if ids else None, *static)

    return per_data_shard(attend, (BATCH,) * 3 + (WHOLE,) * len(ids))(
        q, k, v, *ids)
