"""The SPMD training step.

TPU-first replacement for the reference's training iteration
(train.py:161-181): one jitted function computes forward + backward +
update for the whole mesh.  Parameters/optimizer state are replicated; the
batch is sharded over the ``data`` mesh axis — XLA inserts the gradient
all-reduce (psum over ICI) from the sharding annotations.  There is no
GradScaler: bf16 keeps fp32 range, and the global-norm clip lives inside
the optax chain.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.models.raft import RAFT, corr_impl_at
from raft_tpu.obs.health import tree_all_finite, tree_select
from raft_tpu.parallel.mesh import (batch_sharding, data_parallel_kernels,
                                    replicated_sharding,
                                    spatial_batch_sharding)
from raft_tpu.train.loss import mixture_sequence_loss, sequence_loss
from raft_tpu.train.state import TrainState


def init_state(model: RAFT, tx: optax.GradientTransformation,
               rng: jax.Array, image_shape: Tuple[int, int],
               batch_size: int = 1, iters: int = 2) -> TrainState:
    """Initialize parameters + optimizer state on tiny inputs (shapes don't
    affect conv params; iters doesn't affect the scanned weights).  The
    shape is rounded up to the model's ``pad_multiple`` (a model that
    splits its maps into windows takes no other)."""
    m = model.config.pad_multiple
    H, W = (-(-d // m) * m for d in image_shape)
    dummy = jnp.zeros((batch_size, H, W, 3), jnp.float32)
    variables = model.init({"params": rng, "dropout": rng},
                           dummy, dummy, iters=iters, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      batch_stats=batch_stats, opt_state=tx.init(params),
                      nonfinite_steps=jnp.zeros((), jnp.int32))


def make_loss_fn(model: RAFT, cfg: TrainConfig) -> Callable:
    """Build ``loss_fn(params, batch_stats, batch, rng) ->
    (loss, (metrics, new_batch_stats))`` — the differentiated core of
    :func:`make_train_step`, exposed so ``scripts/replay_step.py`` can
    re-run a forensic bundle's exact step computation offline."""

    def loss_fn(params, batch_stats, batch, rng):
        variables = {"params": params}
        mutable = False
        if batch_stats:
            variables["batch_stats"] = batch_stats
            if not cfg.freeze_bn:
                mutable = ["batch_stats"]
        kwargs = dict(iters=cfg.iters, train=True, freeze_bn=cfg.freeze_bn,
                      rngs={"dropout": rng}, mutable=mutable)
        if cfg.fused_loss:
            # Sequence loss fused into the scan: per-iteration scalars
            # instead of stacked full-res flows (identical numerics at
            # fp32; bf16-rounding-level difference when
            # resolved_upsample_dtype is bfloat16).
            kwargs["loss_targets"] = (batch["flow"], batch["valid"],
                                      cfg.max_flow)
        out = model.apply(variables, batch["image1"], batch["image2"],
                          **kwargs)
        out, new_vars = out if mutable else (out, {})
        if cfg.fused_loss:
            # One term a prediction, the architecture's own (L1, or arch
            # 'searaft''s mixture likelihood), and as many as the model
            # made: ``iters``, or ``iters + 1`` where the loop starts
            # from a regressed flow.
            per_iter, metrics = out
            n = per_iter.shape[0]
            i = jnp.arange(n, dtype=per_iter.dtype)
            weights = cfg.gamma ** (n - i - 1.0)
            loss = jnp.sum(weights * per_iter)
            metrics = dict(metrics, loss_iter=per_iter)
        elif model.config.mixture_head:
            loss, metrics = mixture_sequence_loss(
                out["flow"], out["info"], batch["flow"], batch["valid"],
                gamma=cfg.gamma, max_flow=cfg.max_flow)
        else:
            loss, metrics = sequence_loss(
                out, batch["flow"], batch["valid"],
                gamma=cfg.gamma, max_flow=cfg.max_flow)
        return loss, (metrics, new_vars.get("batch_stats"))

    return loss_fn


def make_train_step(model: RAFT, tx: optax.GradientTransformation,
                    cfg: TrainConfig, mesh: Optional[Mesh] = None,
                    donate: bool = True,
                    shard_spatial: bool = False) -> Callable:
    """Build ``step_fn(state, batch, rng) -> (state, metrics)``.

    ``batch``: dict of ``image1/image2 (B,H,W,3)``, ``flow (B,H,W,2)``,
    ``valid (B,H,W)`` — globally batch-sharded when a mesh is given.
    ``shard_spatial`` additionally splits image height over the mesh's
    ``spatial`` axis (activation/corr-volume sharding for large inputs —
    GSPMD inserts the halo exchanges and gathers).
    ``freeze_bn`` is static per-stage (reference train.py:147-148).

    Pallas kernels under a mesh: GSPMD cannot partition a Mosaic kernel,
    so while the step traces, every Pallas entry point runs per ``data``
    shard under ``shard_map`` (``ops/pallas_util.py per_data_shard``) —
    the kernels only; BatchNorm statistics and the gradient all-reduce
    stay over the global batch.  ``shard_spatial`` with a Pallas path is
    refused: the kernels take whole images, and splitting image rows
    across devices would need halo logic inside them that does not
    exist — pick ``corr_impl='allpairs'`` or ``'chunked'`` there.

    ``cfg.accum_steps > 1`` enables gradient-accumulation microbatching:
    the batch is reshaped to ``(accum, B/accum, ...)`` and a ``lax.scan``
    runs forward+backward per microbatch, accumulating gradients in fp32;
    the single optax update then sees the mean gradient — equal to the
    full-batch gradient at equal effective batch (the sequence loss is a
    mean over batch elements), within fp32 reduction-order tolerance.
    Peak activation/temp memory scales with the microbatch, which is what
    keeps the paper's effective batch 10 on HBM-bound configs.  Notes:
    dropout draws a distinct RNG per microbatch (identical at the default
    dropout=0); BatchNorm running stats chain through the scan (each
    microbatch updates them in sequence — the same as training with
    smaller batches, not bit-identical to one full-batch update, and the
    batch-stat *normalization* couples only within a microbatch, so use
    ``freeze_bn`` stages — every stage but chairs — for exact-parity
    needs); logged metrics are the mean of per-microbatch metrics.

    Training health (``cfg.nonfinite_guard``, default on): an in-graph
    ``isfinite`` reduction over loss+grads gates the update — a poisoned
    step leaves params/opt_state/batch_stats bit-identical, bumps the
    ``nonfinite_steps`` counter carried in ``TrainState``, and sets the
    ``nonfinite`` metric flag the host observes at Logger cadence
    (forensics: raft_tpu/obs/health.py).  The step also emits
    ``param_norm`` / ``update_ratio`` (the optax-update tap) and the
    per-iteration ``loss_iter``/``epe_iter`` curves — all riding the
    existing metrics dict, zero added device syncs.
    """

    if shard_spatial:
        mc = model.config
        # what the row-split trace below will be told, asked here first:
        # a materialized pyramid keeps the XLA lookup under either of its
        # names, the on-demand kernel has nothing to fall back to
        with data_parallel_kernels(mesh, rows_split=True):
            impl = corr_impl_at(mc, cfg.image_size[0] // 8,
                                cfg.image_size[1] // 8)
        pallas = [name for name, on in (
            (f"corr_impl={impl!r}", impl in ("allpairs_pallas", "pallas")),
            ("upsample_loss_kernel='pallas'",
             mc.resolved_upsample_loss_kernel == "pallas"),
            ("fused_gru=True", mc.resolved_fused_gru)) if on]
        if pallas:
            raise ValueError(
                f"shard_spatial=True cannot run the Pallas path "
                f"({', '.join(pallas)}): a Mosaic kernel cannot be "
                "partitioned over image rows and this repo does not "
                "replicate it silently.  Use corr_impl='allpairs' or "
                "'chunked' (XLA, partitioned by GSPMD) with spatial "
                "sharding, or shard over the data axis only")

    loss_fn = make_loss_fn(model, cfg)
    accum = max(int(getattr(cfg, "accum_steps", 1)), 1)
    guard = bool(getattr(cfg, "nonfinite_guard", True))

    def step_fn(state: TrainState, batch: Dict, rng: jax.Array):
        rng = jax.random.fold_in(rng, state.step)
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        if accum == 1:
            (loss, (metrics, new_bs)), grads = grad_fn(
                state.params, state.batch_stats, batch, rng)
        else:
            B = batch["image1"].shape[0]
            if B % accum:
                raise ValueError(
                    f"accum_steps={accum} must divide the batch size "
                    f"{B} evenly (remainder {B % accum}); pick a batch "
                    f"size that is a multiple of accum_steps")
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape((accum, B // accum) + x.shape[1:]),
                batch)

            def body(carry, xs):
                acc, bs = carry
                mb, i = xs
                (loss_i, (metrics_i, new_bs)), grads_i = grad_fn(
                    state.params, bs, mb, jax.random.fold_in(rng, i))
                # fp32 accumulation regardless of the grad dtype, so
                # summing `accum` near-equal terms doesn't lose low bits
                # before the mean.
                acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), acc, grads_i)
                # None at trace time when batch_stats is absent/frozen —
                # the carry then just threads the input stats through.
                bs = bs if new_bs is None else new_bs
                return (acc, bs), (loss_i, metrics_i)

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (acc, new_bs), (losses, metrics_seq) = jax.lax.scan(
                body, (zeros, state.batch_stats),
                (micro, jnp.arange(accum)))
            # Mean of per-microbatch gradients == full-batch gradient
            # (the loss is a mean over batch elements, equal sizes).
            grads = jax.tree_util.tree_map(
                lambda a, p: (a / accum).astype(p.dtype), acc,
                state.params)
            loss = jnp.mean(losses)
            # Mean over the accum axis ONLY: scalar metrics stay scalars
            # and the per-iteration curves (loss_iter/epe_iter) keep
            # their (iters,) shape.
            metrics = jax.tree_util.tree_map(
                lambda x: jnp.mean(x, axis=0), metrics_seq)
        new_state, norms = state.apply_gradients(
            grads, tx, new_batch_stats=new_bs, return_norms=True)
        metrics = dict(metrics, loss=loss,
                       grad_norm=optax.global_norm(grads), **norms)
        if guard:
            ok = tree_all_finite((loss, grads))
            cnt = state.nonfinite_steps
            if cnt is None:  # legacy state without the counter
                cnt = jnp.zeros((), jnp.int32)
            # Gate the whole update: the skipped branch re-emits the
            # input params/opt_state/batch_stats bit-identically (the
            # step index still advances — the schedule and the data
            # stream move on past the poisoned batch).
            good = new_state.replace(nonfinite_steps=cnt)
            bad = state.replace(step=state.step + 1,
                                nonfinite_steps=cnt + 1)
            new_state = tree_select(ok, good, bad)
            metrics["nonfinite"] = 1.0 - ok.astype(jnp.float32)
        return new_state, metrics

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,) if donate else ())

    def mesh_step_fn(state, batch, rng):
        with data_parallel_kernels(mesh, rows_split=shard_spatial):
            return step_fn(state, batch, rng)

    repl = replicated_sharding(mesh)
    data = spatial_batch_sharding(mesh) if shard_spatial \
        else batch_sharding(mesh)
    return jax.jit(
        mesh_step_fn,
        in_shardings=(repl, data, repl),
        out_shardings=(repl, repl),
        donate_argnums=(0,) if donate else (),
    )


def step_cost(compiled, batch_size: int, num_devices: int):
    """:class:`~raft_tpu.obs.cost.ProgramCost` of a compiled train step.

    The compiled module is the PER-DEVICE program under SPMD, so its
    flops advance ``batch / num_devices`` pairs — that is what makes
    ``flops_per_pair`` mesh-shape-invariant (the figure the
    ``--max-flops-per-pair-growth`` gate compares across runs).
    Host-side metadata only; the compile site owns calling this
    (train/loop.py first-dispatch block).
    """
    from raft_tpu.obs import cost as cost_mod

    return cost_mod.program_cost(
        compiled, program="train_step",
        pairs_per_call=float(batch_size) / max(int(num_devices), 1))


# The jitted test-mode forward lives in raft_tpu.evaluate.make_eval_fn.
