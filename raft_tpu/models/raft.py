"""The RAFT model: encoders + correlation + scanned refinement (NHWC).

TPU-first re-design of the reference's ``core/raft.py``:

- The per-iteration Python loop (raft.py:122-139) becomes a single
  ``flax.linen.scan`` over a shared-weight refinement step — traced once,
  compiled once, with optional rematerialization of the step body
  (``config.remat``) for the backward pass.
- The per-step ``coords1.detach()`` (raft.py:123) becomes
  ``jax.lax.stop_gradient``.
- Frames are encoded with shared weights by stacking them on the batch axis
  (the reference's list-input trick, extractor.py:171-174).
- Mixed precision: encoders and the update block run in
  ``config.compute_dtype`` (bf16 on TPU — replaces the reference's
  torch.cuda.amp autocast + GradScaler, no loss scaling needed for bf16);
  correlation volumes and the coordinate state stay fp32
  (raft.py:102-103, corr.py:50).
- The convex-upsample stage (mask head + 8x upsample, raft.py:127-137) is
  **hoisted out of the refinement scan**: the mask depends only on the
  GRU state, so training runs it as a second lightweight scan over the
  stacked per-iteration ``(net, flow)`` pairs, two iterations per step
  (outside the remat'd heavy body), and inference applies it to the final
  iteration only — the reference pays the mask head + upsample every
  test-mode iteration (raft.py:122-139) for outputs it throws away
  (+30% measured on 32-iter Sintel-shape eval).

API:
  ``model.apply(variables, image1, image2, iters=12)`` ->
      ``(iters, B, H, W, 2)`` stacked per-iteration upsampled flows (train
      mode list at raft.py:144).
  ``test_mode=True`` -> ``(flow_low, flow_up)`` (raft.py:141-142).

Images are NHWC float in [0, 255]; flow is ``(..., 2)`` with ``(x, y)``
channel order matching the reference.
"""

from __future__ import annotations

from typing import Optional, Sequence

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from raft_tpu.config import RAFTConfig, _warn_pallas_fallback
from raft_tpu.models.extractor import (BasicEncoder, ResNetEncoder,
                                       SmallEncoder)
from raft_tpu.models.layers import conv
from raft_tpu.models.update import (Attention, BasicUpdateBlock, FlowHead,
                                    FusedCorrLookup, GMAUpdateBlock,
                                    MaskHead, SEARAFTUpdateBlock,
                                    SmallUpdateBlock)
from raft_tpu.ops.corr import (
    QuantizedLevel,
    build_corr_pyramid,
    build_corr_pyramid_flat,
    chunked_corr_lookup,
    corr_lookup,
    pool_fmap_pyramid,
)
from raft_tpu.ops.sampler import coords_grid, upflow8
from raft_tpu.ops.upsample import (convex_combine_flat, convex_upsample,
                                   convex_upsample_data,
                                   convex_upsample_flat,
                                   space_to_depth_flow)
from raft_tpu.parallel.mesh import image_rows_split


def corr_impl_at(cfg: RAFTConfig, h8: int, w8: int) -> str:
    """The correlation implementation ``cfg`` runs at a ``(H/8, W/8)``
    feature map: ``cfg.resolved_corr_impl``, with the lookup of a
    materialized pyramid ('allpairs' / 'allpairs_pallas', one choice
    under two names) picked by ``ops.pallas_corr.pyramid_lookup_path``
    from what this process can observe -- 'allpairs_pallas' where the
    Mosaic kernel runs (a TPU, its block inside the VMEM budget, whole
    images a device), 'allpairs' (XLA) otherwise.

    Everything that builds or samples the pyramid, or has to know which
    of the two a program will hold, asks here while it traces, where
    shapes are static: :func:`_build_corr_state` and
    :func:`lookup_corr` (so the two layouts cannot disagree),
    ``make_train_step``'s row-split check, the serve engine's AOT key,
    ``cli/train.py``'s banner."""
    impl = cfg.resolved_corr_impl
    if cfg.corr_impl not in ("allpairs", "allpairs_pallas"):
        return impl
    from raft_tpu.ops.pallas_corr import pyramid_lookup_path

    # pallas_offtpu='interpret' stands in for the chip where a test
    # asked for the kernel by name; nobody else gets the interpreter.
    on_chip = jax.default_backend() == "tpu" or impl == "allpairs_pallas"
    path = pyramid_lookup_path(
        "tpu" if on_chip else jax.default_backend(), h8, w8,
        levels=cfg.corr_levels, radius=cfg.corr_radius,
        block_q=cfg.lookup_block_q,
        storage_bytes={"float32": 4, "bfloat16": 2}.get(
            cfg.resolved_corr_dtype, 1),
        rows_split=image_rows_split())
    return "allpairs_pallas" if path == "mosaic" else "allpairs"


@flax.struct.dataclass
class WindowCorrState:
    """A materialized pyramid built from stopped features, with the
    features it was built from: what :func:`lookup_corr` hands
    ``pallas_pyramid_lookup_windows``, whose backward returns the
    features' gradients over the windows read instead of the pyramid's
    dense cotangent.  ``fmap2_levels``: ``fmap2`` pooled as the pyramid's
    target dims are.  The maps are carried in float32, so that the
    gradients of the refinement's lookups sum in float32 and are rounded
    once to the maps' own dtype; ``bf16_values``: they hold bfloat16
    values, which the kernels take whole."""

    pyramid: Sequence[jax.Array]
    fmap1: jax.Array
    fmap2_levels: Sequence[jax.Array]
    bf16_values: bool = flax.struct.field(pytree_node=False, default=False)


def _lookup_backward(cfg: RAFTConfig, batch: int, h: int, w: int,
                     channels: int, feature_bytes: int) -> Optional[str]:
    """How the differentiated lookup into the volume of two ``(batch, h,
    w, channels)`` maps returns its gradient: ``'windows'`` (the features'
    gradients over the windows the taps read: the on-demand impls always,
    the Mosaic lookup where ``ops.pallas_corr.lookup_backward_path``
    finds it the cheaper), ``'volume'`` (the pyramid's dense cotangent),
    or None where no gradient reaches the features through it (a
    quantized pyramid).  The fused lookup-encoder samples the pyramid
    itself and keeps the dense backward."""
    impl = corr_impl_at(cfg, h, w)
    if impl in ("chunked", "pallas"):
        return "windows"
    if cfg.corr_dtype_is_quantized:
        return None
    if impl != "allpairs_pallas" or cfg.fused_lookup_encoder:
        return "volume"
    from raft_tpu.ops.pallas_corr import lookup_backward_path

    npad = -(-h * w // cfg.lookup_block_q) * cfg.lookup_block_q
    return lookup_backward_path(
        jax.default_backend(), [(batch, h >> lvl, w >> lvl, npad)
                                for lvl in range(cfg.corr_levels)],
        cfg.corr_radius, channels,
        {"float32": 4, "bfloat16": 2}.get(cfg.resolved_corr_dtype, 1),
        feature_bytes)


def lookup_backward_at(cfg: RAFTConfig, batch: int, h8: int,
                       w8: int) -> Optional[str]:
    """What the train loop's records quote as ``lookup_backward`` for a
    step over ``batch`` pairs at an ``(H/8, W/8)`` map: what
    :func:`_build_corr_state` decides for the volume the refinement
    reads (RAFT's at 1/8 from the float32 feature maps, arch 'unimatch''s
    at 1/4 from the backbone's maps in the compute type), or None where
    the model has no lookup (arch 'gmflow')."""
    if cfg.global_matching:
        if not cfg.refines:
            return None
        from raft_tpu.models import gmflow

        return _lookup_backward(cfg, batch, 2 * h8, 2 * w8, gmflow.CHANNELS,
                                cfg.dtype.itemsize)
    return _lookup_backward(cfg, batch, h8, w8, 128 if cfg.small else 256,
                            4)


def attention_bytes(cfg: RAFTConfig, pairs: int, h8: int, w8: int) -> int:
    """Bytes of attention matrix a step over ``pairs`` pairs at an
    ``(H/8, W/8)`` map builds and holds.  Arch 'gma': one ``(N, N)``
    matrix a pair, held through the refinement loop (training: saved for
    the backward pass; serving: in the slot state).  Archs 'gmflow' and
    'unimatch': the float32 softmaxes of the matching and of the
    propagation at 1/8 (two ``(N, N)`` a pair), kept from the forward pass
    to the backward one; the window attentions' ``(n, n)`` scores are not
    among them: the Mosaic kernels never hold one outside a grid step, and
    under the default ``RAFTConfig.remat`` the ``jnp`` body rebuilds each
    in the backward pass (``models/gmflow.py window_attention``), and the
    local match and propagation at 1/4 hold 81 and 9 scores a position.
    0 for the other architectures."""
    if cfg.global_motion:
        return pairs * (h8 * w8) ** 2 * cfg.dtype.itemsize
    if cfg.global_matching:
        return 2 * pairs * (h8 * w8) ** 2 * 4
    return 0


def window_attention_at(cfg: RAFTConfig, h8: int, w8: int):
    """Which body the window attentions of archs 'gmflow' and 'unimatch'
    run in a program traced now, for an ``(H/8, W/8)`` map: ``'mosaic'``
    or ``'xla'`` (``models/gmflow.py window_attention_path``: asked inside
    ``parallel.mesh.data_parallel_kernels`` it sees a row split), one a
    scale -- a list where the model matches at more than one scale;
    ``'none'`` for the architectures that have none."""
    if not cfg.global_matching:
        return "none"
    from raft_tpu.models import gmflow

    paths = [gmflow.window_attention_path(h8 << s, w8 << s, gmflow.CHANNELS,
                                          cfg.dtype, k)
             for s, k in enumerate(cfg.attn_splits)]
    return paths[0] if len(paths) == 1 else paths


def refine_counters(cfg: RAFTConfig, iters: int, h8: int, w8: int) -> dict:
    """What the train loop's records quote of a refinement that follows a
    matching (arch 'unimatch'): ``lookup`` (the path, the level count and
    the map of the one-level 1/4 volume the refinements read) and
    ``refine_iters``; ``{}`` for the other architectures."""
    if not (cfg.global_matching and cfg.refines):
        return {}
    h4, w4 = 2 * h8, 2 * w8
    path = corr_impl_at(cfg, h4, w4)
    return {"lookup": {"path": "mosaic" if path == "allpairs_pallas"
                       else "xla", "levels": cfg.corr_levels,
                       "map": [h4, w4]},
            "refine_iters": iters}


def predictions(cfg: RAFTConfig, iters: int) -> int:
    """Flow predictions a pair a training step makes: one a refinement
    iteration, and one more where the loop starts from a regressed first
    flow (arch 'searaft'); two a scale where the flow is matched (the
    matched flow and the propagated one; arch 'gmflow' has no loop and
    makes two whatever ``iters``, arch 'unimatch' four and then one a
    refinement)."""
    if cfg.global_matching:
        return 2 * cfg.num_scales + (iters if cfg.refines else 0)
    return iters + 1 if cfg.regressed_first_flow else iters


def batch_norm_calls(cfg: RAFTConfig) -> int:
    """Encoder calls a forward pass that normalise with batch statistics
    in training: the context encoder's one for 'full' and 'gma', none for
    'small', 'gmflow' and 'unimatch', and three for 'searaft' (the pair's
    context, and the feature encoder once an image, each over its own
    batch)."""
    return {"small": 0, "gmflow": 0, "unimatch": 0,
            "searaft": 3}.get(cfg.arch, 1)


def refuse_loop_state(cfg: RAFTConfig, what: str) -> None:
    """``what`` (``flow_init``, slot batching, a streaming session, early
    exit) reads or writes the state a refinement loop carries between
    iterations.  Where the model has none a server can hold (a matching
    model: no loop, or a refinement inside its one program) it is refused
    by name, not ignored."""
    if cfg.global_matching:
        loop = ("no refinement loop" if not cfg.refines else
                "its refinements inside one program")
        raise ValueError(
            f"arch {cfg.arch!r} computes a flow in one pass, with {loop} "
            f"and no state carried between iterations: "
            f"{what} is not available for --arch {cfg.arch}")


def _flow_head(cfg: RAFTConfig, name=None) -> FlowHead:
    """Arch 'searaft': the 6-channel head (flow update, 2 mixture logits,
    2 log-scales).  It is applied to the first hidden state before the
    loop and to every iteration's in it, with one set of weights: the
    module is built (named) where a program's top level is, and the loop
    body applies an unnamed copy to the parameters handed in."""
    return FlowHead(2 * cfg.hidden_dim, cfg.dtype, out_channels=6,
                    name=name)


def _remat_wrap(target, cfg):
    """Apply ``cfg.remat`` / ``cfg.remat_policy`` to a scan body (module
    class or function form) — one dispatch shared by both training scan
    shapes so a policy change can't silently diverge them."""
    if not cfg.remat:
        return target
    if cfg.remat_policy == "dots":
        return nn.remat(target,
                        policy=jax.checkpoint_policies.dots_saveable)
    if cfg.remat_policy == "save_corr":
        # arch 'gma': the attention matrix is built before the scan and
        # enters the body as a broadcast input, so it is outside every
        # remat policy here: SAVED once a step for the backward pass (one
        # bf16 (B, N, N) array, 260 MB at the chairs crop and batch 16,
        # which every iteration's dv = A^T dg and the softmax's backward
        # read), never saved per iteration and never rebuilt.  Rebuilding
        # it would buy those 260 MB for one more q k^T and softmax; the
        # step fits the chip without (PERF.md section 4).  Inside the
        # body the policy is unchanged: of the aggregate only 'motion' is
        # kept, A v is recomputed with the rest of the update block.
        return nn.remat(
            target,
            policy=jax.checkpoint_policies.save_only_these_names(
                "corr", "motion"))
    if cfg.remat_policy == "save_corr_upsample":
        # For the single-scan fused path (``fuse_upsample_in_scan``):
        # additionally save the mask-head logits, so the backward does
        # not re-run the 128->256->576 mask convs per iteration — the
        # recompute that made fused+save_corr 13% SLOWER than two scans
        # at the things crop in round 3 (~40-47 MB bf16 per iteration of
        # saves at stage crops; the softmax/FMA upsample chain itself
        # still recomputes).
        return nn.remat(
            target,
            policy=jax.checkpoint_policies.save_only_these_names(
                "corr", "motion", "mask"))
    if cfg.remat_policy == "full":
        return nn.remat(target)
    raise ValueError(f"unknown remat_policy: {cfg.remat_policy!r} "
                     "(expected 'full', 'dots', 'save_corr' or "
                     "'save_corr_upsample')")


class RefinementStep(nn.Module):
    """One GRU refinement iteration (the body of the reference's hot loop,
    raft.py:122-131; the upsample half of that loop lives in
    :class:`UpsampleStep`)."""

    config: RAFTConfig

    @nn.compact
    def __call__(self, carry, inputs):
        cfg = self.config
        dt = cfg.dtype
        net, coords1 = carry
        # attn: the (B, N, N) attention of arch 'gma', a loop invariant
        # like the pyramid; None for the other architectures.  head: the
        # flow head's parameters (arch 'searaft', see _flow_head), else
        # None.
        inp, coords0, corr_state, attn, head = inputs

        coords1 = jax.lax.stop_gradient(coords1)

        # The same question _build_corr_state asked of the same shape:
        # the lookup has to match the layout the pyramid was built in.
        corr_impl = corr_impl_at(cfg, coords1.shape[1], coords1.shape[2])
        if cfg.fused_lookup_encoder and corr_impl != "allpairs_pallas":
            _warn_pallas_fallback(
                "fused_lookup_encoder=True (samples the Mosaic lookup's "
                f"pyramid; this map runs corr_impl={corr_impl!r})",
                "unfused lookup+conv")
        if cfg.fused_lookup_encoder and corr_impl == "allpairs_pallas":
            # Defer the lookup INTO the motion encoder: the fused Pallas
            # kernel (ops/pallas_corr.pallas_pyramid_lookup_encode)
            # samples the pyramid and applies convc1 in one VMEM pass —
            # the (B, H/8, W/8, corr_planes) tap tensor never reaches
            # HBM.  coords1 is already detached above, and the fused
            # kernel's vjp preserves the unfused gradient semantics
            # (real dcorr for fp32/bf16 pyramids, the stop-gradient
            # zeros for quantized ones).  The 'corr' remat tag moves to
            # the fused conv output inside the encoder.
            corr = FusedCorrLookup(
                pyramid=corr_state, coords=coords1,
                channels=cfg.corr_planes, radius=cfg.corr_radius,
                block_q=cfg.lookup_block_q)
        else:
            corr = lookup_corr(cfg, corr_state, coords1, dt)

        # Tag the sampled window features so remat_policy='save_corr' can
        # keep them (and only them) for the backward pass: the window
        # sampling is ~half the forward iteration, and its taps are small
        # (B, H/8, W/8, levels*(2r+1)^2).  (On the fused-lookup path the
        # taps never materialize; the encoder tags the fused conv output
        # instead.)
        if not isinstance(corr, FusedCorrLookup):
            corr = checkpoint_name(corr.astype(dt), "corr")

        flow = coords1 - coords0
        if cfg.arch == "searaft":
            net = SEARAFTUpdateBlock(cfg.hidden_dim, dt,
                                     name="update_block")(
                net, inp, corr, flow.astype(dt))
            delta = _flow_head(cfg).apply({"params": head}, net)
            coords1 = coords1 + delta[..., :2].astype(jnp.float32)
            return (net, coords1), (net, coords1 - coords0, delta[..., 2:])
        fused_gru = cfg.resolved_fused_gru
        block_cls, extra = {
            "small": (SmallUpdateBlock, ()),
            "full": (BasicUpdateBlock, ()),
            "gma": (GMAUpdateBlock, (attn,)),
        }[cfg.arch]
        block = block_cls(cfg.hidden_dim, dt, fused_gru=fused_gru,
                          name="update_block")
        net, delta_flow = block(net, inp, corr, flow.astype(dt), *extra)

        coords1 = coords1 + delta_flow.astype(jnp.float32)
        new_flow = coords1 - coords0
        return (net, coords1), (net, new_flow)


class UpsampleStep(nn.Module):
    """Mask head + convex upsample (full model; the second half of the
    reference's loop body, raft.py:127-137).

    Scanned over the stacked ``(net, flow)`` pairs in iteration groups for
    training; called once on the final pair for inference.  The carry is
    unused (scan plumbing only).
    """

    config: RAFTConfig

    @nn.compact
    def __call__(self, carry, net, flow, info=None):
        """``info``: arch 'searaft' only, the 4 channels beside the flow,
        upsampled with the flow's weights and returned beside it."""
        cfg = self.config
        mask = MaskHead(cfg.hidden_dim, cfg.dtype, name="mask_head")(net)
        flow_up = convex_upsample(flow, mask.astype(jnp.float32))
        if info is not None:
            return carry, (flow_up, convex_upsample_data(
                info.astype(jnp.float32), mask.astype(jnp.float32)))
        return carry, flow_up


def _fsum(x):
    """Sums always accumulate fp32 (5.8M terms at training shapes — bf16
    accumulation would lose the loss signal entirely)."""
    return jnp.sum(x, axis=(1, 2, 3, 4), dtype=jnp.float32)


def _epe_sums(vm, dx, dy):
    """``[epe_sum, 1px_sum, 3px_sum, 5px_sum]`` a folded iteration over the
    masked elements.  Metrics need no gradient; without stop_gradient the
    sqrt's derivative at exactly-zero dx²+dy² injects inf·0 = NaN into the
    remat'd backward even though the metric cotangents are zero."""
    dx = jax.lax.stop_gradient(dx)
    dy = jax.lax.stop_gradient(dy)
    epe = jnp.sqrt(dx * dx + dy * dy)
    return [_fsum(vm * epe), _fsum(vm * (epe < 1.0)),
            _fsum(vm * (epe < 3.0)), _fsum(vm * (epe < 5.0))]


class UpsampleLossStep(nn.Module):
    """Mask head + FLAT convex upsample + masked L1/EPE partial sums.

    The training-path replacement for :class:`UpsampleStep`: per-iteration
    upsampled flows are produced in space-to-depth ``(c, p, q)`` channel
    layout (:func:`convex_upsample_flat`) and compared against the
    space-to-depth ground truth *inside the scan*, so the only per-
    iteration outputs are five scalars — the full-resolution
    ``(B, 8H, 8W, 2)`` tensors (280 MB/step stacked, plus their pathological
    6-D layouts) never reach HBM.  Shares the ``mask_head`` parameter
    scope with :class:`UpsampleStep` (same tree, checkpoint-compatible).

    Inputs per scan step: ``net, flow`` with ``g`` iterations folded into
    batch; broadcast: ``gt128 (B, H, W, 128)``, ``vmask64 (B, H, W, 64)``.
    Emits ``(g, 5)``: ``[l1_sum, epe_sum, 1px_sum, 3px_sum, 5px_sum]``
    per folded iteration (sums over masked elements; the caller
    normalizes — reference loss semantics train.py:47-72).
    """

    config: RAFTConfig

    @nn.compact
    def __call__(self, carry, net, flow, gt128, vmask64, info=None):
        """``info`` (arch 'searaft'; ``(gB, H/8, W/8, 4)``: two mixture
        logits, two raw log-scales): the flow and ``info`` are upsampled
        with one set of weights and the term is the mixture-of-Laplace
        likelihood (``train/loss.py mixture_nll``) in place of L1, float32
        at full resolution, still in space-to-depth layout and still
        reduced here.  Emits ``(g, 6)``: the five sums above with the
        likelihood's sum first, and the count of elements it ran over."""
        cfg = self.config
        udt = jnp.dtype(cfg.resolved_upsample_dtype)
        B = gt128.shape[0]
        g = net.shape[0] // B
        mask = MaskHead(cfg.hidden_dim, cfg.dtype, name="mask_head")(net)
        # Tagged so remat_policy='save_corr_upsample' can pin the logits
        # (no-op under the other policies / outside remat).
        mask = checkpoint_name(mask, "mask")
        if info is not None:
            return carry, self._mixture_sums(flow, info, mask, gt128,
                                             vmask64, g, udt)
        if cfg.resolved_upsample_loss_kernel == "pallas":
            from raft_tpu.ops.pallas_upsample import \
                pallas_upsample_loss_sums

            sums = pallas_upsample_loss_sums(flow, mask, gt128, vmask64)
            return carry, jnp.sum(sums.reshape(g, B, 5), axis=1)
        if cfg.resolved_upsample_loss_kernel != "xla":
            raise ValueError(
                f"unknown upsample_loss_kernel: "
                f"{cfg.upsample_loss_kernel!r} (expected 'xla' or "
                "'pallas')")
        out = convex_upsample_flat(flow, mask,
                                   compute_dtype=udt)  # (gB, H, W, 128)
        # The ground-truth COMPARE always runs fp32: with both sides in
        # bf16, |out - gt| under ~0.2% of the flow magnitude rounds both
        # operands to the same value (bf16 ulp at a 400-px KITTI flow is
        # 2 px), dx becomes exactly 0 and those pixels stop producing L1
        # gradient — sub-pixel convergence would stall exactly where
        # RAFT's precision matters.  With gt full-precision, out's own
        # rounding only SHIFTS dx (sign noise ~1 ulp, no dead zone).
        # The expensive part (the 9-tap softmax/FMA chain) still runs in
        # ``udt``.
        out = out.astype(jnp.float32).reshape((g, B) + out.shape[1:])
        dx = out[..., :64] - gt128[None, ..., :64]
        dy = out[..., 64:] - gt128[None, ..., 64:]
        vm = vmask64[None]
        l1 = _fsum(vm * (jnp.abs(dx) + jnp.abs(dy)))
        sums = jnp.stack([l1, *_epe_sums(vm, dx, dy)], axis=-1)   # (g, 5)
        return carry, sums

    def _mixture_sums(self, flow, info, mask, gt128, vmask64, g, udt):
        from raft_tpu.train.loss import mixture_nll

        cfg = self.config
        if cfg.resolved_upsample_loss_kernel != "xla":
            raise ValueError(
                "upsample_loss_kernel='pallas' computes the L1 term; arch "
                f"{cfg.arch!r} trains on a mixture likelihood: use 'xla'")
        B = gt128.shape[0]
        out = convex_combine_flat(
            jnp.concatenate([8.0 * flow.astype(udt), info.astype(udt)],
                            axis=-1), mask, compute_dtype=udt)
        # float32 from here on, as the L1 compare above and for the same
        # reason; (g, B, H, W, 6 * 64): flow x, y, logits, log-scales
        out = out.astype(jnp.float32).reshape((g, B) + out.shape[1:])
        ch = [out[..., k * 64:(k + 1) * 64] for k in range(5)]
        dx = ch[0] - gt128[None, ..., :64]
        dy = ch[1] - gt128[None, ..., 64:]
        # Each flow channel under the pixel's one mixture, as two terms of
        # the 64-lane layout: stacking them into one (2, g, B, ...) array
        # read 1.7 % slower a step on the chip (62.59 against 63.68
        # pairs/s/chip, PERF.md section 6 PR 32).
        nll = [mixture_nll(jnp.abs(d), ch[2], ch[3], ch[4]) for d in (dx, dy)]
        vm = vmask64[None]
        keep = [jnp.isfinite(jax.lax.stop_gradient(t)) & (vm > 0.5)
                for t in nll]
        sums = [_fsum(jnp.where(keep[0], nll[0], 0.0)
                      + jnp.where(keep[1], nll[1], 0.0)),
                *_epe_sums(vm, dx, dy),
                _fsum(keep[0].astype(jnp.float32)
                      + keep[1].astype(jnp.float32))]
        return jnp.stack(sums, axis=-1)               # (g, 6)


def _make_encoders(cfg: RAFTConfig):
    """Construct the two shared-weight encoders with their canonical
    scope names (``fnet``/``cnet``), and for arch 'gma' the attention
    (``att``) beside them.  Called from inside a compact method; used by
    both :class:`RAFT` and the slot-serving :class:`RAFTEncode` so the
    param tree cannot drift between them."""
    dt = cfg.dtype
    hdim, cdim = cfg.hidden_dim, cfg.context_dim
    if cfg.arch == "searaft":
        # SEA-RAFT (M): two ResNet-34 trunks of 256 channels out, then
        # init_conv to [net, context] and the shared flow head
        return (ResNetEncoder(256, dt, cfg.remat, name="fnet"),
                ResNetEncoder(256, dt, cfg.remat, name="cnet"), None,
                (conv(hdim + cdim, 3, 1, dt, name="init_conv",
                      torch_default_init=True, in_features=256),
                 _flow_head(cfg, name="flow_head")))
    if cfg.small:
        fnet = SmallEncoder(128, "instance", cfg.dropout, dt, name="fnet")
        cnet = SmallEncoder(hdim + cdim, "none", cfg.dropout, dt,
                            name="cnet")
    else:
        fnet = BasicEncoder(256, "instance", cfg.dropout, dt, name="fnet")
        cnet = BasicEncoder(hdim + cdim, "batch", cfg.dropout, dt,
                            name="cnet")
    if cfg.global_motion:
        # one head as wide as the context (GMA core/network.py)
        return fnet, cnet, Attention(cdim, dt, name="att"), None
    return fnet, cnet, None, None


def _build_corr_state(cfg: RAFTConfig, fmap1, fmap2, train: bool = False):
    """Correlation state from a pair of fp32 feature maps, dispatched on
    ``corr_impl``.  Shared by the cold encode (:func:`_encode_state`)
    and the streaming warm encode (:class:`RAFTEncodeWarm`, which feeds
    a *carried* ``fmap1`` from the previous frame), so the corr-state
    pytree structure cannot drift between the two admit programs.

    A materialized pyramid comes query-minor for the Mosaic lookup or
    query-major for XLA's, as :func:`corr_impl_at` picks for this map's
    shape; :func:`lookup_corr` asks it the same.  A one-level bfloat16
    volume (arch 'unimatch' at 1/4) is written in bfloat16 by the product
    itself (``ops/corr.py _bf16_level``).

    In a training forward (``train``: the program the step differentiates)
    the Mosaic lookup's pyramid comes as a :class:`WindowCorrState` where
    :func:`_lookup_backward` says "windows": built from stopped maps, the
    maps beside it.  An inference program has no backward to choose and
    keeps the pyramid alone."""
    corr_impl = corr_impl_at(cfg, fmap1.shape[1], fmap1.shape[2])
    if corr_impl == "allpairs":
        # corr_dtype (storage) applies here too: the XLA lookup
        # re-accumulates fp32 in _sample_windows regardless.
        return build_corr_pyramid(
            fmap1, fmap2, cfg.corr_levels, cfg.resolved_corr_precision,
            out_dtype=jnp.dtype(cfg.resolved_corr_dtype))
    if corr_impl == "allpairs_pallas":
        B, h, w, C = fmap1.shape
        if train and _lookup_backward(cfg, B, h, w, C,
                                      fmap1.dtype.itemsize) == "windows":
            # the volume is forward only: its lookup sends the gradient
            # to the features, over the windows it read
            stop = jax.lax.stop_gradient
            f32 = jnp.float32
            return WindowCorrState(
                build_corr_pyramid_flat(
                    stop(fmap1), stop(fmap2), cfg.corr_levels,
                    cfg.resolved_corr_precision, pad_q=cfg.lookup_block_q,
                    out_dtype=jnp.dtype(cfg.resolved_corr_dtype)),
                fmap1.astype(f32),
                tuple(lvl.astype(f32) for lvl in
                      pool_fmap_pyramid(fmap2, cfg.corr_levels)),
                bf16_values=fmap1.dtype == jnp.bfloat16)
        return build_corr_pyramid_flat(
            fmap1, fmap2, cfg.corr_levels, cfg.resolved_corr_precision,
            pad_q=cfg.lookup_block_q,
            out_dtype=jnp.dtype(cfg.resolved_corr_dtype))
    if corr_impl in ("chunked", "pallas"):
        if cfg.corr_dtype_is_quantized:
            raise ValueError(
                f"corr_dtype={cfg.resolved_corr_dtype!r} requires a "
                "materialized pyramid (corr_impl 'allpairs' or "
                "'allpairs_pallas'); the on-demand "
                f"{corr_impl!r} path never stores the volume, so "
                "there is nothing to quantize")
        return (fmap1, pool_fmap_pyramid(fmap2, cfg.corr_levels))
    raise ValueError(f"unknown corr_impl: {cfg.corr_impl!r}")


def lookup_corr(cfg: RAFTConfig, corr_state, coords, dt):
    """The ``levels * (2r+1)^2`` window taps of ``corr_state`` (as
    :func:`_build_corr_state` built it) around ``coords``, x-major,
    dispatched on the lookup :func:`corr_impl_at` picks for the map: the
    same question the builder asked of the same shape, so the lookup reads
    the layout the pyramid was built in.  Shared by
    :class:`RefinementStep` and ``models/gmflow.py RefineStep``."""
    corr_impl = corr_impl_at(cfg, coords.shape[1], coords.shape[2])
    if corr_impl == "allpairs":
        return corr_lookup(corr_state, coords, cfg.corr_radius,
                           cfg.resolved_corr_precision)
    if corr_impl == "chunked":
        fmap1, f2_pyramid = corr_state
        return chunked_corr_lookup(fmap1, f2_pyramid, coords,
                                   cfg.corr_radius,
                                   block_size=cfg.corr_block_size,
                                   precision=cfg.resolved_corr_precision)
    if corr_impl == "allpairs_pallas":
        if isinstance(corr_state, WindowCorrState):
            from raft_tpu.ops.pallas_corr import \
                pallas_pyramid_lookup_windows

            return pallas_pyramid_lookup_windows(
                corr_state.pyramid, corr_state.fmap1,
                corr_state.fmap2_levels, coords, cfg.corr_radius,
                cfg.lookup_block_q, None, dt, corr_state.bf16_values)
        if isinstance(corr_state[0], QuantizedLevel):
            from raft_tpu.ops.pallas_corr import \
                pallas_pyramid_lookup_quantized

            # Same kernel, int8/fp8 codes on the load path, dequant
            # fused onto the tap output (linear sampling); no
            # custom_vjp — the quantize boundary upstream is
            # stop_gradient'd, so the lookup is primal-only.
            return pallas_pyramid_lookup_quantized(
                corr_state, coords, cfg.corr_radius,
                cfg.lookup_block_q, None, dt)
        from raft_tpu.ops.pallas_corr import pallas_pyramid_lookup

        # Taps are consumed in cfg.dtype — emit them in that dtype from
        # the kernel and skip the fp32 round-trip through HBM (np.dtype
        # is hashable, so it works as a custom_vjp static arg).
        return pallas_pyramid_lookup(corr_state, coords, cfg.corr_radius,
                                     cfg.lookup_block_q, None, dt)
    if corr_impl == "pallas":
        from raft_tpu.ops.pallas_corr import pallas_corr_lookup

        fmap1, f2_pyramid = corr_state
        return pallas_corr_lookup(fmap1, tuple(f2_pyramid), coords,
                                  cfg.corr_radius,
                                  min(cfg.corr_block_size, 128))
    raise ValueError(f"unknown corr_impl: {cfg.corr_impl!r}")


def _encode_state(cfg: RAFTConfig, fnet, cnet, att, start, image1, image2,
                  train, freeze_bn, flow_init=None):
    """The pre-scan half of the forward pass: normalize → shared-weight
    two-frame encode → correlation state → context split (→ attention,
    arch 'gma') → initial coordinate grids.  One body shared by
    :meth:`RAFT.__call__` and the iteration-granular serving split
    (:class:`RAFTEncode`), so the slot-mode parity pin (bit-identical to
    request mode) is structural rather than a copy that has to be kept in
    sync.

    ``start`` (arch 'searaft', else None): ``(init_conv, flow head)``.
    There the feature encoder is called once an image (batch norm: a
    call's statistics are its own), the context encoder reads both
    images, ``net`` and ``inp`` come from ``init_conv`` with no ``tanh``
    or ReLU, and ``coords1`` starts at the grid plus the flow the head
    regresses from ``net``.  Returns a seventh value: None, or that
    first prediction's ``info`` (B, H/8, W/8, 4)."""
    dt = cfg.dtype
    hdim = cfg.hidden_dim

    image1 = 2.0 * (image1.astype(jnp.float32) / 255.0) - 1.0
    image2 = 2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0
    B = image1.shape[0]

    if start is not None:
        # fnet has batch norm: one call an image, each over its own batch
        fmap1 = fnet(image1.astype(dt), train, freeze_bn).astype(
            jnp.float32)
        fmap2 = fnet(image2.astype(dt), train, freeze_bn).astype(
            jnp.float32)
    else:
        # Shared-weight two-frame encode: stack on batch.
        both = jnp.concatenate([image1, image2], axis=0)
        fmaps = fnet(both.astype(dt), train, freeze_bn)
        fmap1 = fmaps[:B].astype(jnp.float32)
        fmap2 = fmaps[B:].astype(jnp.float32)

    corr_state = _build_corr_state(cfg, fmap1, fmap2, train)

    _, H8, W8, _ = fmap1.shape
    if start is not None:
        if flow_init is not None:
            raise ValueError(
                f"arch {cfg.arch!r} regresses its own first flow from the "
                "pair's context; it takes no flow_init (warm start)")
        init_conv, head = start
        ctx = cnet(jnp.concatenate([image1, image2], axis=-1).astype(dt),
                   train, freeze_bn)
        with jax.named_scope("searaft_init"):
            ctx = init_conv(ctx)
            net, inp = ctx[..., :hdim], ctx[..., hdim:]
            first = head(net)
        coords0 = coords_grid(B, H8, W8)
        coords1 = coords0 + first[..., :2].astype(jnp.float32)
        return net, inp, coords0, coords1, corr_state, None, first[..., 2:]

    ctx = cnet(image1.astype(dt), train, freeze_bn)
    net = jnp.tanh(ctx[..., :hdim])
    inp = nn.relu(ctx[..., hdim:])

    coords0 = coords_grid(B, H8, W8)
    coords1 = coords_grid(B, H8, W8)
    if flow_init is not None:
        coords1 = coords1 + flow_init
    attn = att(inp) if att is not None else None
    return net, inp, coords0, coords1, corr_state, attn, None


class RAFT(nn.Module):
    """Full / small RAFT (reference core/raft.py:24-144)."""

    config: RAFTConfig = RAFTConfig()

    @nn.compact
    def __call__(self, image1, image2, iters: int = 12,
                 flow_init: Optional[jax.Array] = None,
                 test_mode: bool = False, train: bool = False,
                 freeze_bn: bool = False,
                 loss_targets: Optional[tuple] = None):
        """``loss_targets``: optional ``(flow_gt (B,H,W,2), valid (B,H,W),
        max_flow)`` — computes the per-iteration L1 terms in-model (in
        space-to-depth layout; the full-res per-iteration flows never
        reach HBM) and returns ``(per_iter_losses (iters,), metrics
        dict)`` instead of stacked flows (the γ-weighting is applied by
        the caller).

        Arch 'searaft' makes ``iters + 1`` predictions (the regressed
        first flow, then one an iteration), each a flow and 4 channels of
        ``info`` (2 mixture logits, 2 raw log-scales): the per-prediction
        terms are its mixture likelihood and come ``(iters + 1,)``; the
        stacked call returns ``{"final", "flow", "info"}`` as the
        published model does; ``test_mode`` returns ``(flow_low,
        flow_up)`` like every architecture (``info`` is read off the
        stacked call: no served reply carries it, docs/SERVING.md
        "--arch searaft").

        Archs 'gmflow' and 'unimatch' are another body behind this
        signature (``models/gmflow.py``): one pass, so ``flow_init`` is
        refused; 'gmflow' has no loop and does not read ``iters``, two
        predictions in training; 'unimatch' refines ``iters`` times at 1/4,
        ``4 + iters`` predictions."""
        cfg = self.config

        if cfg.global_matching:
            # Two bodies behind one signature rather than a zero-trip
            # scan: nothing of the path below (context, hidden state,
            # pyramid, RefinementStep, the stacked upsample scan) has a
            # counterpart in this model, so threading it through would
            # put a branch at every one of them.
            from raft_tpu.models import gmflow

            if flow_init is not None:
                refuse_loop_state(cfg, "flow_init (warm start)")
            return gmflow.forward(cfg, image1, image2, test_mode, train,
                                  freeze_bn, loss_targets, iters)

        fnet, cnet, att, start = _make_encoders(cfg)
        (net, inp, coords0, coords1, corr_state, attn,
         info0) = _encode_state(cfg, fnet, cnet, att, start, image1, image2,
                                train, freeze_bn, flow_init)
        B = image1.shape[0]
        head = None if start is None else start[1].variables["params"]

        if (loss_targets is not None and not cfg.small and not test_mode
                and cfg.fuse_upsample_in_scan):
            if cfg.regressed_first_flow:
                raise ValueError(
                    f"fuse_upsample_in_scan is not built for arch "
                    f"{cfg.arch!r} (its first prediction is made before "
                    "the scan); leave it off")
            return self._fused_inscan_losses(cfg, iters, net, inp, coords0,
                                             coords1, corr_state, attn,
                                             loss_targets)

        step = _remat_wrap(RefinementStep, cfg)
        scan = nn.scan(
            step,
            variable_broadcast="params",
            split_rngs={"params": False, "dropout": True},
            in_axes=nn.broadcast,
            out_axes=0,
            length=iters,
            unroll=cfg.scan_unroll,
        )(cfg, name="refine")

        first = (net, coords1 - coords0) if cfg.regressed_first_flow else ()
        (net, coords1), outs = scan(
            (net, coords1), (inp, coords0, corr_state, attn, head))
        nets, flows = outs[:2]
        infos = outs[2] if cfg.mixture_head else None
        if cfg.regressed_first_flow:
            # prediction 0 stands in front of the loop's
            nets = jnp.concatenate([first[0][None], nets])
            flows = jnp.concatenate([first[1][None], flows])
            infos = jnp.concatenate([info0[None], infos])

        # --- Upsample stage (outside the heavy scan) ---
        if cfg.small:
            # No mask head: bilinear upflow8 (reference raft.py:134-135).
            return self._small_outputs(flows, coords1 - coords0,
                                       test_mode, loss_targets)

        if test_mode:
            # Only the final iteration's flow is returned in test mode
            # (raft.py:141-142) — upsample just that one.
            flow_low = coords1 - coords0
            up = UpsampleStep(cfg, name="upsampler")
            _, flow_up = up(None, net, flow_low)
            return flow_low, flow_up

        # Grouped upsample: fold groups of iterations into the batch axis
        # so the mask-head convs and the convex-combination einsum run at
        # g*B batch while the scan over groups keeps the full-res
        # transients bounded.  Measured on v5e (batch 12, 368x496, bf16):
        # g=1 13.6-13.8, g=2 14.4, g=3 13.9, g=4 14.1, g=6 12.8
        # pairs/s/chip; all-at-once (g=12) needs ~29 GB HBM and OOMs.
        # Rematerialized (cfg.remat_upsample): the backward keeps only the
        # stacked (iters, B, H/8, W/8, hdim) GRU states and recomputes two
        # convs + a softmax per group.
        I = predictions(cfg, iters)
        # Largest divisor of I that is <= upsample_group (clamped to
        # [1, I] so misconfigured knobs degrade instead of raising a
        # bare StopIteration from inside the trace).
        g = next(g for g in range(max(1, min(cfg.upsample_group, I)), 0,
                                  -1)
                 if I % g == 0)
        nets_r = nets.reshape((I // g, g * B) + nets.shape[2:])
        flows_r = flows.reshape((I // g, g * B) + flows.shape[2:])
        # arch 'searaft': info rides beside the flow as one more scanned
        # input of either upsample scan
        more, more_axes = (), ()
        if infos is not None:
            more = (infos.reshape((I // g, g * B) + infos.shape[2:]),)
            more_axes = (0,)

        if loss_targets is not None:
            # Sequence loss fused into the upsample scan: the full-res
            # per-iteration flows never reach HBM (see UpsampleLossStep).
            from raft_tpu.train.loss import combined_valid

            flow_gt, valid, max_flow = loss_targets
            vmask = combined_valid(flow_gt, valid, max_flow)
            gt128 = space_to_depth_flow(flow_gt.astype(jnp.float32))
            vmask64 = space_to_depth_flow(vmask[..., None])
            up_step = UpsampleLossStep
            if cfg.remat_upsample:
                up_step = nn.remat(UpsampleLossStep)
            up_scan = nn.scan(
                up_step,
                variable_broadcast="params",
                split_rngs={"params": False, "dropout": True},
                in_axes=(0, 0, nn.broadcast, nn.broadcast) + more_axes,
                out_axes=0,
                length=I // g,
                unroll=max(1, min(cfg.upsample_unroll, I // g)),
            )(cfg, name="upsampler")
            _, sums = up_scan(None, nets_r, flows_r, gt128, vmask64, *more)
            return self._loss_outputs(sums.reshape(I, sums.shape[-1]),
                                      gt128, vmask64, B)

        up_step = UpsampleStep
        if cfg.remat_upsample:
            up_step = nn.remat(UpsampleStep)
        up_scan = nn.scan(
            up_step,
            variable_broadcast="params",
            split_rngs={"params": False, "dropout": True},
            in_axes=0,
            out_axes=0,
            length=I // g,
            unroll=max(1, min(cfg.upsample_unroll, I // g)),
        )(cfg, name="upsampler")
        _, flow_ups = up_scan(None, nets_r, flows_r, *more)
        if infos is not None:
            flow_ups, info_ups = (x.reshape((I, B) + x.shape[2:])
                                  for x in flow_ups)
            return {"final": flow_ups[-1], "flow": flow_ups,
                    "info": info_ups}
        flow_ups = flow_ups.reshape((I, B) + flow_ups.shape[2:])
        return flow_ups

    def _loss_outputs(self, sums, gt128, vmask64, B):
        """Normalize the per-iteration ``(iters, 5)`` partial sums into
        per-iteration mean losses + final-iteration metrics (reference
        sequence_loss semantics, train.py:47-72).  The per-iteration EPE
        sums the scan already produced become the refinement-convergence
        curve (``epe_iter``, docs/OBSERVABILITY.md) for free — no extra
        compute, it rides the metrics dict to the host at Logger
        cadence."""
        _, H8s, W8s, _ = gt128.shape
        n_all = B * H8s * W8s * 128              # loss mean incl. zeroed
        n_valid = jnp.maximum(jnp.sum(vmask64), 1.0)
        if self.config.mixture_head:
            # the mixture likelihood (``(iters + 1, 6)`` sums): a mean
            # over the elements it ran over, counted in the sixth column
            per_iter = sums[:, 0] / jnp.maximum(sums[:, 5], 1.0)
        else:
            per_iter = sums[:, 0] / n_all
        metrics = {"epe": sums[-1, 1] / n_valid,
                   "1px": sums[-1, 2] / n_valid,
                   "3px": sums[-1, 3] / n_valid,
                   "5px": sums[-1, 4] / n_valid,
                   "epe_iter": sums[:, 1] / n_valid}
        return per_iter, metrics

    def _fused_inscan_losses(self, cfg, iters, net, inp, coords0, coords1,
                             corr_state, attn, loss_targets):
        """Single-scan training path (``cfg.fuse_upsample_in_scan``): the
        refinement step AND the mask head + flat convex upsample + loss
        sums run in ONE scan body, so the per-iteration GRU states are
        consumed in place instead of being stacked to HBM and re-read by
        a second scan (~1.1 GB/step of stacking traffic at chairs batch
        16).  The function-form ``nn.scan`` binds the same ``refine`` /
        ``upsampler`` scopes as the two-scan path, so the param tree —
        and every checkpoint — is identical."""
        from raft_tpu.train.loss import combined_valid

        flow_gt, valid, max_flow = loss_targets
        B = flow_gt.shape[0]
        vmask = combined_valid(flow_gt, valid, max_flow)
        gt128 = space_to_depth_flow(flow_gt.astype(jnp.float32))
        vmask64 = space_to_depth_flow(vmask[..., None])

        def body(mdl, carry, _):
            carry, (net_i, flow_i) = RefinementStep(cfg, name="refine")(
                carry, (inp, coords0, corr_state, attn, None))
            _, sums = UpsampleLossStep(cfg, name="upsampler")(
                None, net_i, flow_i, gt128, vmask64)
            return carry, sums[0]

        body = _remat_wrap(body, cfg)
        scan = nn.scan(
            body,
            variable_broadcast="params",
            split_rngs={"params": False, "dropout": True},
            length=iters,
            unroll=cfg.scan_unroll,
        )
        _, sums = scan(self, (net, coords1), None)
        return self._loss_outputs(sums, gt128, vmask64, B)

    def _small_outputs(self, flows, flow_low, test_mode, loss_targets):
        """Small-model upsampling: parameter-free ``upflow8`` applied to
        the stacked low-res flows (vectorized over iterations)."""
        if test_mode:
            return flow_low, upflow8(flows[-1])
        I, B, H8, W8, _ = flows.shape
        if loss_targets is None:
            up = upflow8(flows.reshape(I * B, H8, W8, 2))
            return up.reshape(I, B, H8 * 8, W8 * 8, 2)

        from raft_tpu.train.loss import combined_valid, flow_metrics

        flow_gt, valid, max_flow = loss_targets
        vmask = combined_valid(flow_gt, valid, max_flow)
        n_valid = jnp.maximum(jnp.sum(vmask), 1.0)

        def body(carry, flow):
            fu = upflow8(flow)
            loss = jnp.mean(vmask[..., None] * jnp.abs(fu - flow_gt))
            diff = jax.lax.stop_gradient(fu - flow_gt)  # metric: no grad
            epe = jnp.sum(vmask * jnp.sqrt(jnp.sum(diff ** 2, -1))
                          ) / n_valid
            return fu, (loss, epe)

        last_flow, (per_iter, epe_iter) = jax.lax.scan(
            body, jnp.zeros(flow_gt.shape, jnp.float32), flows)
        metrics = dict(flow_metrics(last_flow, flow_gt, vmask),
                       epe_iter=epe_iter)
        return per_iter, metrics


# ---------------------------------------------------------------------------
# Iteration-granular serving split (continuous batching)
# ---------------------------------------------------------------------------
#
# The slot-based serve path (serve/slots.py) runs the forward pass as
# two separately-jitted programs instead of one: ``encode`` (everything
# before the refinement scan) and one refinement iteration at a time
# (so requests can join/leave the device batch between iterations, and
# converged samples can exit early).  The modules below bind the
# SAME parameter scopes as :class:`RAFT` — ``fnet``/``cnet``/``refine``/
# ``upsampler`` — so a variables tree from ``RAFT.init`` (or any
# checkpoint) applies unchanged; extra subtrees a given program does not
# touch are simply never read.  The math is the scan body applied once.
#
# BOTH serve batching modes consume these same compiled programs —
# ``batching=request`` drives them in whole-batch lockstep, ``slot``
# continuously — which is what makes the slot-vs-request bitwise parity
# pin (tests/test_serve_slots.py) structural: XLA:CPU specializes
# reduction/fusion order to the surrounding program, so the same math
# compiled into two DIFFERENT programs can differ in the last ulp (the
# encoder's instance-norm and the corr einsum both do, measured ~1e-5
# relative — ``optimization_barrier`` does not pin it).  Sharing one
# executable chain sidesteps the whole class of drift.


class RAFTEncode(nn.Module):
    """Pre-scan half of the forward pass as a standalone program:
    ``(image1, image2) -> (net, inp, coords0, coords1, corr_state,
    attn)``, ``attn`` None unless the arch is 'gma'.

    Per-sample independent in inference mode (instance norm; batch norm
    runs on stored statistics), so lanes of a slot batch can be encoded
    together with ballast and scattered into slots without affecting
    each other."""

    config: RAFTConfig = RAFTConfig()

    @nn.compact
    def __call__(self, image1, image2,
                 flow_init: Optional[jax.Array] = None):
        refuse_loop_state(self.config, "the encode/iterate program pair "
                          "(slot batching)")
        fnet, cnet, att, start = _make_encoders(self.config)
        # the first prediction's info (arch 'searaft') is not served
        return _encode_state(self.config, fnet, cnet, att, start, image1,
                             image2, False, False, flow_init)[:6]


def refuse_frame_cache(cfg: RAFTConfig) -> None:
    """Streaming caches a frame's feature map AND its context for the next
    pair.  Where the context is a function of both frames there is no
    per-frame context to cache: refused by name, not served wrong."""
    refuse_loop_state(cfg, "a streaming session")
    if cfg.context_reads_pair:
        raise ValueError(
            f"arch {cfg.arch!r} computes its context from both frames of "
            "a pair, so a streaming session has no per-frame context to "
            "carry to the next pair: streaming sessions are not "
            f"available for --arch {cfg.arch}; send whole pairs")


class RAFTFrameFeatures(nn.Module):
    """Single-frame feature stash for streaming sessions:
    ``image -> (fmap (fp32), ctx (model dtype))``.

    A streamed pair shares its first frame with the previous pair's
    second frame (consecutive-frame identity), so serving carries that
    frame's feature map AND its raw context-encoder output
    device-resident in the lane.  ``fmap`` feeds the next pair's corr
    build as ``fmap1``; ``ctx`` is split tanh/relu into ``net``/``inp``
    at warm-encode time (the split is cheap, the conv stack is not).
    Binds the same ``fnet``/``cnet`` scopes as :class:`RAFT`, inference
    mode, identical normalization to :func:`_encode_state`."""

    config: RAFTConfig = RAFTConfig()

    @nn.compact
    def __call__(self, image):
        cfg = self.config
        refuse_frame_cache(cfg)
        fnet, cnet, _, _ = _make_encoders(cfg)
        image = 2.0 * (image.astype(jnp.float32) / 255.0) - 1.0
        fmap = fnet(image.astype(cfg.dtype), False, False)
        ctx = cnet(image.astype(cfg.dtype), False, False)
        return fmap.astype(jnp.float32), ctx


class RAFTEncodeWarm(nn.Module):
    """Warm-start encode for streamed frame N+1: only the NEW frame
    runs through the feature encoder — the carried previous-frame
    features stand in for frame 1 of the pair.

    ``(image2, fmap1, ctx1, flow_init) -> (net, inp, coords0, coords1,
    corr_state, attn, fmap2, ctx2)`` where ``fmap1``/``ctx1`` are the carry
    stashed when the previous frame was encoded (its ``fmap2``/its
    :class:`RAFTFrameFeatures` ctx), ``flow_init`` is the previous
    pair's forward-warped flow (added to the ``coords1`` grid exactly
    like :func:`_encode_state`), and the returned ``fmap2``/``ctx2``
    are the NEXT carry.  Per warm frame the encoders run once each
    (fnet + cnet on the new frame) versus three conv-stack passes for a
    cold pair (fnet twice + cnet) — the fnet work per frame is halved,
    which the cost model exposes as ``wenc`` vs ``enc``
    flops-per-pair."""

    config: RAFTConfig = RAFTConfig()

    @nn.compact
    def __call__(self, image2, fmap1, ctx1, flow_init):
        cfg = self.config
        hdim = cfg.hidden_dim
        refuse_frame_cache(cfg)
        fnet, cnet, att, _ = _make_encoders(cfg)
        image2 = 2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0
        fmap2 = fnet(image2.astype(cfg.dtype), False, False)
        ctx2 = cnet(image2.astype(cfg.dtype), False, False)
        fmap2 = fmap2.astype(jnp.float32)

        corr_state = _build_corr_state(cfg, fmap1, fmap2)

        net = jnp.tanh(ctx1[..., :hdim])
        inp = nn.relu(ctx1[..., hdim:])

        B, H8, W8, _ = fmap1.shape
        coords0 = coords_grid(B, H8, W8)
        coords1 = coords_grid(B, H8, W8) + flow_init
        attn = att(inp) if att is not None else None
        return net, inp, coords0, coords1, corr_state, attn, fmap2, ctx2


class RAFTIterStep(nn.Module):
    """One GRU refinement iteration as a standalone program — exactly
    the scanned body (:class:`RefinementStep` under the ``refine``
    scope, which ``variable_broadcast='params'`` leaves un-stacked, so
    single application binds the identical tree).  The step is wrapped
    with the same ``cfg.remat`` policy as the training/inference scan:
    remat changes the compiled graph, and the slot-mode parity pin
    requires the identical program body, not just identical weights."""

    config: RAFTConfig = RAFTConfig()

    @nn.compact
    def __call__(self, net, coords1, inp, coords0, corr_state, attn=None):
        cfg = self.config
        head = None
        if cfg.mixture_head:
            head = _flow_head(cfg, name="flow_head").variables["params"]
        step = _remat_wrap(RefinementStep, cfg)
        (net, coords1), _ = step(cfg, name="refine")(
            (net, coords1), (inp, coords0, corr_state, attn, head))
        return net, coords1


class RAFTUpsample(nn.Module):
    """Final upsample as a standalone program: ``(net, flow_low) ->
    flow_up`` — :class:`UpsampleStep` under the ``upsampler`` scope for
    the full model, parameter-free ``upflow8`` for the small one (same
    dispatch as the test-mode tail of :meth:`RAFT.__call__`)."""

    config: RAFTConfig = RAFTConfig()

    @nn.compact
    def __call__(self, net, flow_low):
        cfg = self.config
        if cfg.small:
            return upflow8(flow_low)
        up = UpsampleStep(cfg, name="upsampler")
        _, flow_up = up(None, net, flow_low)
        return flow_up
