"""Frozen model/training configuration.

The reference threads a mutable argparse ``args`` namespace through the model
(which mutates it in-place: reference ``core/raft.py:29-45`` sets
``corr_levels``/``corr_radius``/``dropout``/``alternate_corr`` and
``core/update.py:65,82`` reads them back).  Here configuration is a frozen
dataclass resolved once at the CLI edge and hashable, so it can be a static
argument under ``jax.jit``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

_warned_fallback = set()

# The full knob vocabulary for the correlation-volume STORAGE dtype and
# the MXU precision of the correlation einsums.  One tuple each, shared
# by the CLI edges (cli/train.py, cli/evaluate.py) and the config
# resolution below, so a typo fails at argument parsing with the
# allowed set in the message instead of minutes later inside
# ``jnp.dtype(...)`` at trace time.
#
# 'int8' (and the fp8 names) are QUANTIZED storage: per-level symmetric
# scale calibrated from the correlation row maxima, fp32 accumulation
# in the lookups, dequant fused into the window sampling
# (raft_tpu/ops/corr.py).  They require a materialized pyramid
# (corr_impl 'allpairs' or 'allpairs_pallas') — the on-demand paths
# never store the volume, so there is nothing to quantize.
CORR_DTYPES = ("auto", "float32", "bfloat16", "int8",
               "float8_e4m3fn", "float8_e5m2")
QUANTIZED_CORR_DTYPES = ("int8", "float8_e4m3fn", "float8_e5m2")
CORR_PRECISIONS = ("auto", "default", "high", "highest")
# The architectures the model code builds (RAFTConfig.arch, the CLIs'
# --arch).
ARCHS = ("full", "small", "gma", "searaft", "gmflow", "unimatch")


def validate_corr_dtype(value: str, flag: str = "corr_dtype") -> str:
    """Validate a corr-storage dtype at the CLI edge.

    Raises ``ValueError`` naming the allowed set — the alternative is an
    opaque trace-time ``jnp.dtype`` failure from deep inside the model.
    """
    if value not in CORR_DTYPES:
        raise ValueError(
            f"invalid {flag}={value!r}; allowed: {', '.join(CORR_DTYPES)}")
    return value


def validate_corr_precision(value: str,
                            flag: str = "corr_precision") -> str:
    """Validate the correlation MXU precision at the CLI edge."""
    if value not in CORR_PRECISIONS:
        raise ValueError(
            f"invalid {flag}={value!r}; allowed: "
            f"{', '.join(CORR_PRECISIONS)}")
    return value


def _warn_pallas_fallback(requested: str, substituted: str) -> None:
    """One warning per (requested, substituted) pair per process: the
    silent alternative is a user discovering the Pallas interpreter's
    ~1000x slowdown by watching a hung process."""
    import warnings

    key = (requested, substituted)
    if key not in _warned_fallback:
        _warned_fallback.add(key)
        warnings.warn(
            f"{requested} requires a TPU backend; dispatching the "
            f"equivalent XLA implementation {substituted!r} instead "
            "(set pallas_offtpu='interpret' to force the Pallas "
            "interpreter)", stacklevel=3)


@dataclasses.dataclass(frozen=True)
class RAFTConfig:
    """Model hyperparameters.

    One preset an architecture, named by ``arch``: the reference's two
    (``core/raft.py:29-39``) -- ``full`` (hidden 128 / context 128 /
    radius 4) and ``small`` (hidden 96 / context 64 / radius 3) -- and
    ``gma`` (Jiang et al., ICCV 2021; PAPERS.md): ``full`` with a
    content attention built once from the context and a global
    aggregate of the motion features fed to a 384-wide GRU input in
    every iteration; and ``searaft`` (SEA-RAFT (M), Wang et al., ECCV
    2024; PAPERS.md): ResNet-34 encoders with batch norm, a context that
    reads both images, a first flow regressed before the loop, two
    ConvNeXt blocks where the GRU stood, a flow head of 6 channels
    (flow, mixture logits, log-scales) outside the update block and a
    mixture-of-Laplace loss over ``iters + 1`` predictions; and
    ``gmflow`` (GMFlow at one scale, Xu et al., CVPR 2022; PAPERS.md):
    no hidden state, pyramid, lookup or loop, but a Transformer of
    shifted-window attention over both feature maps, one softmax over
    the whole correlation volume and a self-attention that propagates
    the flow (``models/gmflow.py``; it reads none of ``hidden_dim``,
    ``context_dim``, ``corr_levels``, ``corr_radius``, which keep
    ``full``'s values); and ``unimatch`` (UniMatch's flow model, GMFlow
    at two scales with six regression refinements, Xu et al., TPAMI 2023;
    PAPERS.md): the same Transformer matched globally at 1/8 and, over
    image 2's features warped by the upsampled flow, locally at 1/4, then
    refined RAFT's way at 1/4 from a one-level volume of the backbone's
    1/4 features (``corr_levels`` 1, ``corr_radius`` 4, ``iters`` the
    refinements).  Build one with :meth:`full`, :meth:`small_model`,
    :meth:`gma`, :meth:`searaft`, :meth:`gmflow`, :meth:`unimatch` or
    :meth:`preset`; the widths below follow from it.
    """

    arch: str = "full"
    hidden_dim: int = 128
    context_dim: int = 128
    corr_levels: int = 4
    corr_radius: int = 4
    dropout: float = 0.0
    # 'allpairs' materializes the pyramid (reference CorrBlock,
    # corr.py:12-60); which lookup samples it -- the fused Mosaic kernel
    # over a query-minor pyramid, or XLA's batched einsums over a
    # query-major one -- is chosen when the model traces, from the
    # platform and the map's shape (``models/raft.py corr_impl_at`` ->
    # ``ops/pallas_corr.pyramid_lookup_path``; PERF.md sections 4-6 hold
    # what each costs in every cell).  'allpairs_pallas' is the same
    # choice, and besides keeps the kernel in the Pallas interpreter off
    # TPU under ``pallas_offtpu='interpret'`` (the CPU tests' way to run
    # the shipped kernel).  'chunked' is the memory-efficient blockwise
    # path (reference AlternateCorrBlock + alt_cuda_corr, corr.py:63-91);
    # 'pallas' is the fused TPU kernel version of 'chunked'.
    corr_impl: str = "allpairs"
    # Pixels per block for the chunked/pallas on-demand correlation path.
    corr_block_size: int = 256
    # Query block (grid tile) for the fused Pallas pyramid lookup
    # (allpairs_pallas); must divide the padded query count.
    lookup_block_q: int = 128
    # Storage dtype for the MATERIALIZED query-minor pyramid
    # (allpairs_pallas AND allpairs): 'bfloat16' halves the HBM traffic
    # of the lookup reads, the dcorr writes and the cross-iteration gradient
    # accumulation (the pyramid is the largest tensor in the step, ~537 MB
    # at chairs batch 16; every PERF.md cell runs bf16 storage, none
    # times float32).  The correlation MATH stays fp32 — the einsum
    # accumulates fp32 (corr_precision) and the Pallas kernels convert
    # tiles to fp32 on load; only the stored values round.
    # 'auto' (default): bfloat16
    # when compute_dtype is bfloat16 — the refinement step already rounds
    # the lookup output to bf16 before the motion encoder consumes it
    # (raft.py corr.astype(dt)), so bf16 storage adds no new precision
    # class to training — and float32 otherwise (the reference's corr
    # dtype, corr.py:50, preserved whenever the model computes fp32).
    # Default validated by the seed-paired storage A/B
    # (AB_CORR_DTYPE.json, scripts/ab_corr_dtype.py, round 5): 150-step
    # toy-chairs stages, arms differing ONLY in corr_dtype at matched
    # seeds, runs bit-deterministic across processes.  Per-seed EPE
    # diffs (bf16 - fp32): +2.52, -2.66, +0.29, -4.74, -1.30, -0.06 —
    # mean -0.99 +/- 1.03 stderr (t = -0.96, n = 6 pairs): no dtype
    # effect resolvable against seed noise, sign favoring bf16 if
    # anything.
    # Real-data full-stage EPE remains the definitive test
    # (docs/REAL_WEIGHTS_RUNBOOK.md); quality-critical runs can still
    # pin 'float32'.
    # 'int8' / 'float8_e4m3fn' / 'float8_e5m2' store the pyramid
    # QUANTIZED with a per-level symmetric scale calibrated from the
    # correlation row maxima; lookups dequantize in the sampling pass
    # and accumulate fp32 (docs/PERFORMANCE.md "Quantized correlation").
    # Inference/serving-focused: the quantize boundary is
    # non-differentiable (stop_gradient, like the reference's unwired
    # alt_cuda_corr backward), so under training the feature encoder
    # receives no gradient through the correlation volume.  Gate any
    # quantized run with the eval EPE-delta mode
    # (``python -m raft_tpu evaluate --epe_delta float32,int8``).
    corr_dtype: str = "auto"
    # MXU precision for the correlation matmul + window-sampling einsums:
    # 'default' (1 bf16 pass), 'high' (bf16x3), 'highest' (fp32), or
    # 'auto' (= 'highest': fp32 correlation, as the reference keeps it,
    # corr.py:50).  Every PERF.md cell runs 'highest'; no cell times
    # the cheaper settings.  Under bf16 compute the fmaps are bf16-exact
    # and 'default' is bitwise identical in VALUE (verified: max abs
    # diff exactly 0.0).
    corr_precision: str = "auto"
    # bf16 compute for encoders + update block (replaces the reference's
    # torch.cuda.amp autocast, raft.py:11-21,99,110,127); correlation
    # stays fp32 at the default corr_precision='highest' (reference
    # corr.py:50 casts .float()) — see corr_precision above to relax it.
    compute_dtype: str = "float32"
    # Rematerialize the scan body in backward (memory/flops trade; the
    # reference has no equivalent — torch retains all activations).
    remat: bool = True
    # Remat policy: 'save_corr' keeps the per-iteration sampled corr
    # windows + motion-encoder outputs (small; skips ~half the backward
    # recompute; what PERF.md's train cells run, 7.4-9.9 GB of 16);
    # 'full' recomputes everything (lowest memory); 'dots' saves all
    # einsum outputs.  No cell times the other two.
    remat_policy: str = "save_corr"
    # Refinement-scan unroll factor (lax.scan unroll): trades compile
    # time/code size for less per-iteration loop overhead.  12 is a
    # full unroll of the training budget and what PERF.md's train cells
    # run; it was picked by sweeps of step bodies that no longer exist
    # (ROADMAP S5/D5) and no cell times another value.
    scan_unroll: int = 12
    # Rematerialize the upsample stage (mask head + convex upsample, which
    # runs in its own scan *after* the GRU refinement scan) in backward.
    # Its residuals are ~1-2 GB at training shapes; recompute is two convs
    # + a softmax, so remat is the safe default.
    remat_upsample: bool = True
    # Compute dtype for the flat convex-upsample + fused-loss chain
    # (training path only; eval always upsamples fp32).  'bfloat16'
    # halves the HBM traffic of the 9-tap softmax/FMA chain (what
    # PERF.md's train cells run) at ~0.4% relative rounding on the
    # upsampled flow (loss 33.5360 vs 33.5361, grad-norm 63.50 vs 63.39
    # on the bench shape).  'auto' (default): bfloat16 when
    # compute_dtype is bfloat16 (the flow predictions entering the
    # upsample already come from bf16 convs), float32 otherwise (the
    # reference upsamples outside autocast, raft.py:72-83).
    # Per-iteration loss sums always accumulate fp32.
    upsample_dtype: str = "auto"
    # Iterations folded into the batch axis per upsample-scan step (the
    # mask-head convs and the flat convex combination run at
    # ``upsample_group * B`` batch).  Must divide ``iters``; values that
    # don't are rounded down to the nearest divisor.  2 is what
    # PERF.md's train cells run; it was picked against a step body that
    # no longer exists (ROADMAP S5/D5) and no cell times another value.
    upsample_group: int = 2
    # Unroll factor for the upsample scan (lax.scan unroll over the
    # iters/upsample_group steps) — the refinement scan's unroll lesson
    # applied to the second scan.
    upsample_unroll: int = 1
    # Training upsample+loss implementation: 'xla' (convex_upsample_flat
    # + compare, scan-stacked) or 'pallas' (ops/pallas_upsample.py — the
    # whole softmax/FMA/compare chain per batch element in VMEM with a
    # recomputing custom_vjp: no softmax intermediate ever reaches HBM).
    # Eval always upsamples via XLA (it returns flows, not losses).
    upsample_loss_kernel: str = "xla"
    # Run the mask head + flat convex upsample + loss INSIDE the
    # refinement scan (training fused-loss path only): the stacked
    # (iters, B, H/8, W/8, hdim) GRU states never reach HBM (~560 MB of
    # dynamic-update-slice writes + re-reads per step at chairs batch
    # 16 — profiled ~10 ms/step of pure stacking traffic).  Param tree
    # is unchanged (the in-scan body binds the same "refine" /
    # "upsampler" scopes).  Eval and the stacked-flows API always use
    # the two-scan form.
    fuse_upsample_in_scan: bool = False
    # Off-TPU handling of the Pallas code paths (corr_impl
    # 'allpairs_pallas'/'pallas', upsample_loss_kernel='pallas').
    # 'fallback' (default): dispatch the equivalent XLA implementation
    # instead — allpairs_pallas -> allpairs (same materialized pyramid,
    # einsum lookup), pallas -> chunked (same O(HW) blockwise on-demand
    # math), pallas upsample kernel -> xla — because off-TPU the Pallas
    # kernels can only run in the interpreter, which is orders of
    # magnitude slower than the XLA paths.  'interpret': keep the Pallas
    # kernels in interpreter mode anyway (the CPU-mesh tests and the
    # driver dryrun use this to exercise the shipped kernel path without
    # a TPU).  Inert on TPU.
    pallas_offtpu: str = "fallback"
    # Fuse the Pallas pyramid lookup with the motion encoder's first
    # 1x1 corr conv (models/update.py convc1): the sampled taps feed
    # the conv accumulator in VMEM and the (B,H/8,W/8,levels*(2r+1)^2)
    # corr-feature tensor never reaches HBM (ops/pallas_corr.py
    # ``pallas_pyramid_lookup_encode``).  fp32 accumulation; int8/fp8
    # dequant folds into the conv weights per (batch, level); the
    # stop-gradient boundary is unchanged (fnet gets zero grad through
    # the volume, conv weights/bias and the rest of the update block
    # still learn).  Requires corr_impl='allpairs_pallas'.  Default
    # off, no chip timing, and no CLI flag sets it (ROADMAP S11).
    fused_lookup_encoder: bool = False
    # Fuse the ConvGRU gate chains (models/update.py ConvGRU/SepConvGRU)
    # with Pallas elementwise kernels (ops/pallas_gru.py): sigmoid(r)*h
    # and the (1-sigmoid(z))*h + sigmoid(z)*tanh(q) blend each become
    # one VMEM pass instead of an XLA elementwise chain with HBM
    # round-trips; the convs stay XLA (convq's input depends on r).
    # Grads via recomputing custom_vjp.  Default off, no chip timing,
    # and no CLI flag sets it (ROADMAP S11).
    fused_gru: bool = False

    def __post_init__(self):
        if self.arch not in ARCHS:
            raise ValueError(f"unknown arch {self.arch!r}; expected one "
                             f"of {', '.join(ARCHS)}")

    @classmethod
    def full(cls, **kw) -> "RAFTConfig":
        base = dict(arch="full", hidden_dim=128, context_dim=128,
                    corr_levels=4, corr_radius=4)
        return cls(**{**base, **kw})

    @classmethod
    def small_model(cls, **kw) -> "RAFTConfig":
        base = dict(arch="small", hidden_dim=96, context_dim=64,
                    corr_levels=4, corr_radius=3)
        return cls(**{**base, **kw})

    @classmethod
    def gma(cls, **kw) -> "RAFTConfig":
        # RAFT-full's widths; one attention head of context_dim channels
        # (GMA core/network.py: dim_head = cdim, --num_heads 1).
        return cls.full(**{"arch": "gma", **kw})

    @classmethod
    def searaft(cls, **kw) -> "RAFTConfig":
        # SEA-RAFT (M): dim 128 for the hidden state and the context,
        # radius 4, 4 levels (config/train/Tartan-C.json); what else the
        # architecture fixes (ResNet-34 stages, 2 ConvNeXt blocks, the
        # 6-channel head) follows from ``arch`` in models/.
        return cls.full(**{"arch": "searaft", **kw})

    @classmethod
    def gmflow(cls, **kw) -> "RAFTConfig":
        # GMFlow, num_scales 1: 128 feature channels, 6 Transformer
        # blocks, one head, 2x2 windows, FFN expansion 4, global matching
        # and propagation: fixed by ``arch`` in models/gmflow.py.
        return cls.full(**{"arch": "gmflow", **kw})

    @classmethod
    def unimatch(cls, **kw) -> "RAFTConfig":
        # UniMatch's flow model (gmflow-scale2-regrefine6): GMFlow's
        # widths at two scales (splits 2 and 8, matching radii -1 / 4,
        # propagation radii -1 / 1) and a refinement that looks up one
        # level of the 1/4 volume at radius 4 through RAFT's motion
        # encoder, GRU (hidden 128) and heads.
        return cls.full(**{"arch": "unimatch", "corr_levels": 1, **kw})

    @classmethod
    def preset(cls, arch: str, **kw) -> "RAFTConfig":
        """The preset the CLIs' ``--arch`` names (an unknown name fails
        in ``__post_init__``, with the allowed set)."""
        makers = {"small": cls.small_model, "gma": cls.gma,
                  "searaft": cls.searaft, "gmflow": cls.gmflow,
                  "unimatch": cls.unimatch}
        return makers.get(arch, cls.full)(**{**kw, "arch": arch})

    @property
    def small(self) -> bool:
        return self.arch == "small"

    @property
    def global_motion(self) -> bool:
        """Whether the update block aggregates motion features through
        an attention matrix carried beside the correlation state."""
        return self.arch == "gma"

    @property
    def regressed_first_flow(self) -> bool:
        """Whether the loop starts from a flow regressed from the context
        (prediction 0 of ``iters + 1``) and not from zero."""
        return self.arch == "searaft"

    @property
    def mixture_head(self) -> bool:
        """Whether the flow head gives 6 channels a prediction (the flow,
        2 mixture logits, 2 log-scales: ``info``), upsampled together,
        and the sequence loss is their mixture-of-Laplace likelihood in
        place of L1."""
        return self.arch == "searaft"

    @property
    def context_reads_pair(self) -> bool:
        """Whether the context is a function of both images: a frame's
        context then cannot be cached for the next pair (streaming)."""
        return self.arch == "searaft"

    @property
    def refines(self) -> bool:
        """Whether the model refines a flow in a loop of ``iters``
        iterations.  Where it does not (arch 'gmflow': one pass, two
        predictions in training) ``iters`` is not read and a CLI refuses a
        value other than its default."""
        return self.arch != "gmflow"

    @property
    def published_iters(self) -> Optional[int]:
        """The refinement count the architecture is published with where
        the CLIs' own ``--iters`` defaults (RAFT's: 12 to train, 20 for the
        demo, 24-32 to evaluate and serve) are not it: 6 for arch
        'unimatch' (``num_reg_refine``), else None.  The CLIs take it when
        ``--iters`` is not given (``cli.parse_with_arch``)."""
        return {"unimatch": 6}.get(self.arch)

    @property
    def global_matching(self) -> bool:
        """Whether the flow comes from a Transformer's softmax matching
        (``models/gmflow.py``: archs 'gmflow' and 'unimatch').  Such a model
        carries no state a server can hold between calls (no hidden state,
        pyramid or coordinates outside its one program): a request is one
        program, and ``flow_init``, slot batching, streaming sessions and
        early exit are refused by name."""
        return self.arch in ("gmflow", "unimatch")

    @property
    def attn_splits(self) -> tuple:
        """A scale's windows an axis for attention, coarsest scale first:
        ``(2,)`` for arch 'gmflow' (``models/gmflow.py SPLITS``), ``(2,
        8)`` for 'unimatch' (1/8, then 1/4), ``()`` where no attention
        runs over windows."""
        return {"gmflow": (2,), "unimatch": (2, 8)}.get(self.arch, ())

    @property
    def num_scales(self) -> int:
        """Feature scales the matching runs at: 1/8, then 1/4."""
        return len(self.attn_splits)

    @property
    def local_radii(self) -> tuple:
        """``(match radius, propagation radius)`` a scale, -1 for global:
        ``((-1, -1),)`` for arch 'gmflow', ``((-1, -1), (4, 1))`` for
        'unimatch'."""
        return ((-1, -1), (4, 1))[:self.num_scales]

    @property
    def pad_multiple(self) -> int:
        """What the model needs H and W to be multiples of: at each
        scale (1/8, 1/4) its downsampling times the windows an axis of
        its map is split into; 8 where nothing is split.
        ``evaluate.py`` and the serve engine pad and bucket by it."""
        return max([8] + [(8 >> s) * k
                          for s, k in enumerate(self.attn_splits)])

    @property
    def resolved_corr_dtype(self) -> str:
        validate_corr_dtype(self.corr_dtype)
        if self.corr_dtype == "auto":
            return ("bfloat16" if self.compute_dtype == "bfloat16"
                    else "float32")
        return self.corr_dtype

    @property
    def corr_dtype_is_quantized(self) -> bool:
        """True when the resolved storage dtype needs the calibrated
        per-level scale plumbing (int8 / fp8)."""
        return self.resolved_corr_dtype in QUANTIZED_CORR_DTYPES

    @property
    def resolved_corr_precision(self) -> str:
        validate_corr_precision(self.corr_precision)
        if self.corr_precision == "auto":
            return "highest"   # fp32 correlation (see above)
        return self.corr_precision

    def _pallas_dispatchable(self) -> bool:
        if self.pallas_offtpu == "interpret":
            return True
        if self.pallas_offtpu != "fallback":
            raise ValueError(f"unknown pallas_offtpu: "
                             f"{self.pallas_offtpu!r} (expected "
                             "'fallback' or 'interpret')")
        import jax

        return jax.default_backend() == "tpu"

    @property
    def resolved_corr_impl(self) -> str:
        """``corr_impl`` with the off-TPU Pallas fallback applied."""
        if (self.corr_impl in ("allpairs_pallas", "pallas")
                and not self._pallas_dispatchable()):
            sub = {"allpairs_pallas": "allpairs", "pallas": "chunked"}[
                self.corr_impl]
            _warn_pallas_fallback(f"corr_impl={self.corr_impl!r}", sub)
            return sub
        return self.corr_impl

    @property
    def resolved_upsample_loss_kernel(self) -> str:
        """``upsample_loss_kernel`` with the off-TPU Pallas fallback."""
        if (self.upsample_loss_kernel == "pallas"
                and not self._pallas_dispatchable()):
            _warn_pallas_fallback("upsample_loss_kernel='pallas'", "xla")
            return "xla"
        return self.upsample_loss_kernel

    @property
    def resolved_fused_gru(self) -> bool:
        """``fused_gru`` with the off-TPU Pallas fallback applied."""
        if not self.fused_gru:
            return False
        if not self._pallas_dispatchable():
            _warn_pallas_fallback("fused_gru=True",
                                  "unfused XLA gate chain")
            return False
        return True

    @property
    def resolved_upsample_dtype(self) -> str:
        if self.upsample_dtype == "auto":
            return ("bfloat16" if self.compute_dtype == "bfloat16"
                    else "float32")
        return self.upsample_dtype

    @property
    def corr_planes(self) -> int:
        # levels * (2r+1)^2, reference update.py:65,82
        return self.corr_levels * (2 * self.corr_radius + 1) ** 2

    @property
    def dtype(self):
        # np.dtype understands 'bfloat16' once jax/ml_dtypes is loaded;
        # resolve lazily so importing config (and raft_tpu.data) stays
        # jax-free in data-loader workers.
        try:
            return np.dtype(self.compute_dtype)
        except TypeError:
            import jax.numpy as jnp

            return jnp.dtype(self.compute_dtype)

    def replace(self, **kw) -> "RAFTConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training hyperparameters (reference ``train.py:218-239`` flags)."""

    name: str = "raft"
    stage: str = "chairs"
    restore_ckpt: Optional[str] = None
    validation: Tuple[str, ...] = ()
    lr: float = 4e-4
    num_steps: int = 100000
    batch_size: int = 6
    image_size: Tuple[int, int] = (384, 512)
    iters: int = 12
    wdecay: float = 1e-4
    epsilon: float = 1e-8
    clip: float = 1.0
    gamma: float = 0.8          # exponential weighting, train.py:47
    max_flow: float = 400.0     # loss exclusion threshold, train.py:47
    add_noise: bool = False
    seed: int = 1234
    # Validation / checkpoint cadence (train.py:185-198, VAL_FREQ=5000).
    val_freq: int = 5000
    log_freq: int = 100         # Logger SUM_FREQ, train.py:91
    freeze_bn: bool = False     # all stages but chairs, train.py:147-148
    # Compute the sequence loss inside the *upsample* scan, in
    # space-to-depth layout (models/raft.py:UpsampleLossStep): the
    # (iters, B, 8H, 8W, 2) stacked flows — and the pathological 6-D
    # (.., 9, 8, 8) layouts of the direct convex-upsample einsum — never
    # reach HBM.  Profiled round 2: the einsum formulation cost
    # ~250 ms/step in HBM-bound relayout traffic.  fused_loss=False
    # restores the stacked-flows path (public-API shape; numerically
    # identical when resolved_upsample_dtype is float32 — under bf16
    # compute the fused path upsamples bf16 while the stacked path
    # stays fp32, a bf16-rounding-level difference).
    fused_loss: bool = True
    # Gradient-accumulation microbatching: split the per-host batch into
    # ``accum_steps`` equal microbatches and run a lax.scan over them with
    # fp32 gradient accumulation before the single optax update.  The
    # parameter update equals the full-batch step at equal effective
    # batch (the sequence loss is a mean over batch elements), while peak
    # activation memory scales with ``batch/accum_steps`` — the path that
    # keeps the paper's effective batch 10 when HBM bounds the per-step
    # batch (FlyingThings 720p crops with spatial sharding off).  The
    # per-host batch must divide evenly; dropout draws a distinct RNG per
    # microbatch (identical at the default dropout=0).  1 = off.
    accum_steps: int = 1
    # Host-loader decode window in BATCHES (``ShardedLoader`` keeps this
    # many batches of samples in flight); 0 = the loader's legacy
    # default of max(2*batch, 2*workers) samples.
    prefetch_batches: int = 0
    # Device-prefetch buffer depth: batches decoded + host-prepped +
    # device_put'd ahead of the consuming step on a background producer
    # thread (raft_tpu/data/prefetch.py), so the H2D transfer of batch
    # N+1 overlaps the device step on batch N.  0 = the fully serial
    # fetch->prep->put->step path (for A/B); 2 = double buffering.
    device_prefetch: int = 2
    # Non-finite step guard (raft_tpu/obs/health.py): an in-graph
    # isfinite reduction over loss+grads gates the optimizer update —
    # a poisoned step (bf16 overflow, corrupt batch) leaves
    # params/opt_state untouched, bumps the nonfinite_steps counter in
    # TrainState, and flags the step's metrics for host-side forensics.
    # Pure device-side select; no extra syncs.  Off restores the
    # unguarded update (A/B; a NaN then destroys the params, as before).
    nonfinite_guard: bool = True
    # Host batches kept in the forensics ring (the most recent N steps'
    # post-noise inputs).  A step flagged non-finite whose batch is
    # still in the ring gets a fully replayable bundle; older ones get
    # step/rng/metrics only.  Guaranteed capture needs
    # log_freq <= forensic_keep (the flag is observed at Logger
    # cadence).  0 disables batch capture (bundles still written).
    forensic_keep: int = 8
    # Stall watchdog (raft_tpu/obs/watchdog.py): seconds without a
    # training-loop heartbeat before dumping all thread stacks and
    # emitting a `stall` telemetry event.  0 = off (default).  Pick
    # ~20x the rolling median step time, and above startup
    # trace+compile; the loop pauses it around save/validate.
    watchdog_timeout: float = 0.0
    # Hard-exit the process when the watchdog fires (exit code 42), so
    # a hung multi-host job fails fast and gets rescheduled instead of
    # burning a pod.  Off: dump + event only.
    watchdog_exit: bool = False
    # Distributed step tracing (raft_tpu/obs/trace.py): fraction of
    # steps that open a `train_step` trace with queue_wait / prep /
    # h2d / step_dispatch / ckpt_commit child spans, emitted as
    # ``trace_span`` events into the telemetry sink.  Errors, retries
    # and non-finite steps are always kept regardless of the sample
    # coin (tail-based keep).  0 = tracing compiled out of the hot
    # path (docs/OBSERVABILITY.md "Distributed tracing").
    trace_sample_rate: float = 0.0
    # On-demand XProf window: capture device profiles for steps
    # [start, stop) into ``<telemetry_dir>/xprof/`` and link the
    # directory from the step's trace spans.  None = off.
    profile_steps: Optional[Tuple[int, int]] = None
    ckpt_dir: str = "checkpoints"
    # Bound on in-flight background checkpoint commits
    # (train/checkpoint.py save_async): the step loop never waits on
    # checkpoint I/O unless this many saves are still uncommitted —
    # each in-flight commit holds one on-device snapshot of the full
    # TrainState, so the window is an HBM budget, not a speed knob.
    ckpt_commit_window: int = 2
    # Number of data-parallel shards (devices); resolved at runtime.
    num_devices: int = 0
