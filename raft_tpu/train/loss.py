"""Sequence loss and flow metrics (reference ``train.py:42-76``).

The reference computes the loss over a Python list of per-iteration
predictions (train.py:47-60); here predictions arrive as one stacked
``(iters, B, H, W, 2)`` array (the `lax.scan` output) and the weighted sum
is a single vectorized contraction — XLA fuses it into the backward pass.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def flow_metrics(flow_pred: jnp.ndarray, flow_gt: jnp.ndarray,
                 valid: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """End-point-error stats over valid pixels (reference train.py:62-70).

    ``flow_pred``/``flow_gt``: (B, H, W, 2); ``valid``: (B, H, W) in {0,1}.
    """
    epe = jnp.sqrt(jnp.sum((flow_pred - flow_gt) ** 2, axis=-1))
    mask = valid > 0.5
    n = jnp.maximum(jnp.sum(mask), 1)

    def vmean(x):
        return jnp.sum(jnp.where(mask, x, 0.0)) / n

    return {
        "epe": vmean(epe),
        "1px": vmean((epe < 1.0).astype(jnp.float32)),
        "3px": vmean((epe < 3.0).astype(jnp.float32)),
        "5px": vmean((epe < 5.0).astype(jnp.float32)),
    }


def combined_valid(flow_gt: jnp.ndarray, valid: jnp.ndarray,
                   max_flow: float) -> jnp.ndarray:
    """Loss/metric mask: valid ∧ |flow_gt| < max_flow, as float {0,1}
    (reference train.py:51-52)."""
    mag = jnp.sqrt(jnp.sum(flow_gt ** 2, axis=-1))
    return ((valid > 0.5) & (mag < max_flow)).astype(jnp.float32)


# SEA-RAFT's ``var_max`` (``var_min`` is 0): the first component's
# log-scale is clipped to [0, VAR_MAX], the second's to [0, 0].
VAR_MAX = 10.0
_LOG2 = 0.6931471805599453


def mixture_nll(abs_err, a1, a2, b1):
    """Negative log-likelihood of ``abs_err = |gt - flow|`` (one flow
    channel) under SEA-RAFT's mixture of two Laplace distributions, per
    element: logits ``a1, a2``; log-scale ``clip(b1, 0, VAR_MAX)`` for the
    first component and 0 for the second, which is therefore a plain L1
    term (the head's fourth ``info`` channel is never read):

        logsumexp_k(a_k) - logsumexp_k(a_k - log 2 - log b_k
                                       - abs_err * exp(-log b_k))

    Float32 in, float32 out (callers cast first).  With ``a1 == a2`` and
    ``b1 <= 0`` it is ``abs_err + log 2``."""
    with jax.named_scope("mol_loss"):
        lb1 = jnp.clip(b1, 0.0, VAR_MAX)
        t1 = a1 - _LOG2 - lb1 - abs_err * jnp.exp(-lb1)
        t2 = a2 - _LOG2 - abs_err
        return jnp.logaddexp(a1, a2) - jnp.logaddexp(t1, t2)


def mixture_sequence_loss(flow_preds: jnp.ndarray, info_preds: jnp.ndarray,
                          flow_gt: jnp.ndarray, valid: jnp.ndarray,
                          gamma: float = 0.85, max_flow: float = 400.0
                          ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """SEA-RAFT's ``sequence_loss`` (train.py, ``use_var``) over stacked
    predictions: ``flow_preds`` (n, B, H, W, 2) and ``info_preds``
    (n, B, H, W, 4) = two logits, two raw log-scales.  Prediction ``i``
    weighs ``gamma**(n - i - 1)``; its term is the mean of
    :func:`mixture_nll` over both flow channels of the pixels that are
    valid, under ``max_flow`` and finite.  Metrics as
    :func:`sequence_loss`'s."""
    n = flow_preds.shape[0]
    valid = combined_valid(flow_gt, valid, max_flow)
    err = jnp.abs(flow_preds.astype(jnp.float32) - flow_gt[None])
    info = info_preds.astype(jnp.float32)
    nll = mixture_nll(err, info[..., 0:1], info[..., 1:2], info[..., 2:3])
    keep = jnp.isfinite(jax.lax.stop_gradient(nll)) & (
        valid[None, ..., None] > 0.5)
    per_iter = (jnp.sum(jnp.where(keep, nll, 0.0), axis=(1, 2, 3, 4))
                / jnp.maximum(jnp.sum(keep, axis=(1, 2, 3, 4)), 1))
    weights = gamma ** (n - jnp.arange(n, dtype=jnp.float32) - 1.0)
    diff = jax.lax.stop_gradient(flow_preds - flow_gt[None])
    epe_all = jnp.sqrt(jnp.sum(diff ** 2, axis=-1))
    n_valid = jnp.maximum(jnp.sum(valid), 1.0)
    epe_iter = jnp.sum(valid[None] * epe_all, axis=(1, 2, 3)) / n_valid
    metrics = dict(flow_metrics(flow_preds[-1], flow_gt, valid),
                   loss_iter=per_iter, epe_iter=epe_iter)
    return jnp.sum(weights * per_iter), metrics


def sequence_loss(flow_preds: jnp.ndarray, flow_gt: jnp.ndarray,
                  valid: jnp.ndarray, gamma: float = 0.8,
                  max_flow: float = 400.0
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Exponentially-weighted L1 over the prediction sequence
    (reference ``sequence_loss``, train.py:47-72).

    - ``flow_preds``: (iters, B, H, W, 2) stacked per-iteration flows.
    - weight of prediction i is ``gamma**(iters - i - 1)`` (train.py:55).
    - pixels with ``|flow_gt| >= max_flow`` or invalid are excluded
      (train.py:51-52); like the reference, the per-iteration term is the
      mean over *all* pixels with invalid ones zeroed (train.py:58-59),
      not the mean over valid pixels.

    Besides the reference's final-iteration metrics, the metrics dict
    carries the refinement-convergence curve: ``loss_iter`` (the
    unweighted per-iteration L1 terms, (iters,)) and ``epe_iter`` (the
    per-iteration masked-mean EPE, (iters,)) — a healthy RAFT shows a
    monotonically falling ``epe_iter``; a flat tail says the extra GRU
    iterations buy nothing (docs/OBSERVABILITY.md).
    """
    n_predictions = flow_preds.shape[0]
    valid = combined_valid(flow_gt, valid, max_flow)
    vmask = valid[None, ..., None].astype(flow_preds.dtype)

    i = jnp.arange(n_predictions, dtype=flow_preds.dtype)
    weights = gamma ** (n_predictions - i - 1.0)

    abs_err = jnp.abs(flow_preds - flow_gt[None])
    per_iter = jnp.mean(vmask * abs_err, axis=(1, 2, 3, 4))
    flow_loss = jnp.sum(weights * per_iter)

    # Metrics need no gradient; stop_gradient keeps the sqrt's inf
    # derivative at exactly-zero error out of any rematerialized
    # backward (same reasoning as UpsampleLossStep, models/raft.py).
    diff = jax.lax.stop_gradient(flow_preds - flow_gt[None])
    epe_all = jnp.sqrt(jnp.sum(diff ** 2, axis=-1))       # (iters, B, H, W)
    n_valid = jnp.maximum(jnp.sum(valid), 1.0)
    epe_iter = jnp.sum(valid[None] * epe_all, axis=(1, 2, 3)) / n_valid

    metrics = flow_metrics(flow_preds[-1], flow_gt,
                           valid.astype(jnp.float32))
    metrics = dict(metrics, loss_iter=per_iter, epe_iter=epe_iter)
    return flow_loss, metrics
