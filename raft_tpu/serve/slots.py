"""Slot-state programs for iteration-granular continuous batching.

The serve hot path (``serve/engine.py``) runs the forward pass as two
compiled programs instead of one whole-request forward:

- ``encode_admit(variables, image1, image2, state, admit, budgets)``:
  run the pre-scan half (:class:`raft_tpu.models.raft.RAFTEncode`) over
  the full slot batch and scatter the results into the lanes selected
  by ``admit`` (a ``(S,)`` bool mask), leaving every other lane's
  device-resident state untouched.  Inference encoders are per-lane
  independent (instance norm / stored batch statistics), so encoding a
  batch that carries ballast in the non-admitted lanes produces the
  same bits for the admitted lanes as any other batch content would.
- ``iter_step(variables, state, threshold, steps)``: a device loop of
  ``steps`` GRU refinement iterations (:class:`RAFTIterStep`) over every
  active lane, masked with ``lax``-selects so retired/free lanes are
  no-ops.  ``steps`` is a runtime int32 scalar (``lax.fori_loop`` with a
  traced bound), so ONE executable serves every count: request mode
  passes ``cfg.iters`` and a request is two program calls; slot mode,
  streaming and :class:`EarlyExitRunner` pass ``1`` and look at
  ``active`` on the host after every step.  The per-lane convergence
  predicate (max flow-update magnitude below ``threshold``,
  SEA-RAFT-style early exit) and the iteration-budget check both run
  in-graph, every step; lanes retiring at a step get their flow
  upsampled (:class:`RAFTUpsample`) there, guarded by a ``lax.cond`` so
  steps with no retiree skip the upsample, and written into their rows
  of the carried ``flow_up``.  The loop carries only what a step moves
  (``net``, ``coords1``, the lane bookkeeping, ``flow_up``) and the
  program returns only that (:func:`advance` puts it back into the
  state): the pyramid, ``inp``, ``coords0``, ``budget`` and
  ``attn`` are read, never copied — not in the loop, and not out of the
  program either, which would have to copy an input it was not donated
  (138 MB a lane at 440x1024) to hand it back.

The slot state is a flat dict pytree (sorted keys, so treedefs are
reproducible across processes for AOT export):

======================  =========================  =======================
key                     shape/dtype                meaning
======================  =========================  =======================
``active``              ``(S,) bool``              lane holds a live request
``attn``                ``(S, N, N)``              arch 'gma' only: attention
``budget``              ``(S,) int32``             per-lane max iterations
``converged``           ``(S,) bool``              early-exit predicate fired
``coords0``             ``(S, H/8, W/8, 2) f32``   base coordinate grid
``coords1``             ``(S, H/8, W/8, 2) f32``   current flow coordinates
``corr``                corr-state pytree          per-lane corr pyramid
``delta_max``           ``(S,) f32``               last step's max |Δflow|
``inp``                 ``(S, H/8, W/8, C)``       context features
``iters_done``          ``(S,) int32``             iterations consumed
``net``                 ``(S, H/8, W/8, C)``       GRU hidden state
======================  =========================  =======================

Both serve batching modes drive these same two compiled programs
(``batching=request`` in whole-batch lockstep — admit everyone, one
``iter_step`` call of ``iters`` steps with the threshold disabled —
``slot`` continuously, a step a call), which is what makes
slot-vs-request bitwise parity structural rather than numerical luck:
XLA specializes reduction and fusion order per program, so the same
math compiled into two different programs can differ in the last ulp
(see the note in ``models/raft.py``).

``threshold`` is a runtime f32 scalar, not a compile-time constant, so
sweeping it (``evaluate.py --early_exit_threshold``) never
recompiles.  ``threshold <= 0`` disables early exit: ``delta_max`` is a
max of norms, hence ``>= 0``, and the predicate is a strict ``<``.

Streaming sessions (docs/SERVING.md "Streaming sessions") add two more
programs over the SAME slot state — the state pytree above is untouched,
so ``iter_step`` is the same program with or without them:

- ``stash_carry(variables, image2, carry, admit)``: after a session's
  first (cold) pair is admitted through the unmodified ``encode_admit``
  (single-frame bit parity is structural), stash frame 2's feature map
  and raw context-encoder output into the lane's *carry* — a separate
  ``{"ctx", "fmap"}`` pytree the iter program never sees.
- ``encode_warm(variables, image2, carry, state, admit, budgets)``:
  admit streamed frame N+1 with only the NEW image — ``fmap1`` comes
  from the carry (consecutive-frame identity), the lane's previous flow
  (``coords1 - coords0``, still device-resident from its retirement) is
  forward-warped in-graph into the ``coords1`` init, and the new
  frame's features are returned as the next carry.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.config import RAFTConfig
from raft_tpu.models.raft import (RAFTEncode, RAFTEncodeWarm,
                                  RAFTFrameFeatures, RAFTIterStep,
                                  RAFTUpsample, refuse_loop_state)
from raft_tpu.ops.sampler import forward_warp_flow


def _lane_select(mask, new, old):
    """Per-leaf ``jnp.where`` with ``mask`` broadcast over a ``(S, ...)``
    leaf's trailing dims (leaves of any rank, including zero-size
    pyramid tails)."""
    m = mask.reshape(mask.shape + (1,) * (new.ndim - 1))
    return jnp.where(m, new, old.astype(new.dtype))


def _pack_state(net, inp, coords0, coords1, corr_state, active, budget,
                converged, delta_max, iters_done, attn=None):
    # Plain dict: insertion order is irrelevant to jax (dict pytrees
    # flatten in sorted key order), matching the docstring table.  The
    # ``attn`` key exists only where the model builds one (arch 'gma'),
    # so the other architectures' state trees are what they were.
    state = {} if attn is None else {"attn": attn}
    return {
        **state,
        "active": active,
        "budget": budget,
        "converged": converged,
        "coords0": coords0,
        "coords1": coords1,
        "corr": corr_state,
        "delta_max": delta_max,
        "inp": inp,
        "iters_done": iters_done,
        "net": net,
    }


def state_template(model_cfg: RAFTConfig, variables, slots: int,
                   bucket_hw: Tuple[int, int]) -> dict:
    """Host-side all-zeros slot state for ``slots`` lanes at bucket
    ``(H, W)`` — the engine's reset/initial state and the shape spec the
    programs are lowered against.  Built from ``jax.eval_shape`` of the
    encode program, so the corr-state leaf structure (including
    quantized ``QuantizedLevel`` levels) can never drift from what
    ``encode_admit`` actually produces."""
    H, W = bucket_hw
    spec = jax.ShapeDtypeStruct((slots, H, W, 3), jnp.float32)
    net, inp, coords0, coords1, corr, attn = jax.eval_shape(
        RAFTEncode(model_cfg).apply, variables, spec, spec)
    zeros = lambda s: np.zeros(s.shape, dtype=s.dtype)
    lanes = lambda dt: np.zeros((slots,), dtype=dt)
    return _pack_state(
        zeros(net), zeros(inp), zeros(coords0), zeros(coords1),
        jax.tree_util.tree_map(zeros, corr),
        lanes(np.bool_), lanes(np.int32), lanes(np.bool_),
        np.full((slots,), -1.0, np.float32), lanes(np.int32),
        attn=None if attn is None else zeros(attn))


def make_encode_fn(model_cfg: RAFTConfig):
    """``encode_admit(variables, image1, image2, state, admit, budgets)
    -> state'`` (pure; the engine jits/lowers it)."""
    enc = RAFTEncode(model_cfg)

    def encode_admit(variables, image1, image2, state, admit, budgets):
        net, inp, coords0, coords1, corr, attn = enc.apply(
            variables, image1, image2)
        sel = lambda new, old: _lane_select(admit, new, old)
        return _pack_state(
            sel(net, state["net"]),
            sel(inp, state["inp"]),
            sel(coords0, state["coords0"]),
            sel(coords1, state["coords1"]),
            jax.tree_util.tree_map(sel, corr, state["corr"]),
            state["active"] | admit,
            jnp.where(admit, budgets.astype(jnp.int32), state["budget"]),
            state["converged"] & ~admit,
            jnp.where(admit, jnp.float32(-1.0), state["delta_max"]),
            jnp.where(admit, jnp.int32(0), state["iters_done"]),
            attn=None if attn is None else sel(attn, state["attn"]),
        )

    return encode_admit


def carry_template(model_cfg: RAFTConfig, variables, slots: int,
                   bucket_hw: Tuple[int, int]) -> dict:
    """Host-side all-zeros streaming carry for ``slots`` lanes: the
    previous frame's feature map (``fmap``, fp32) and raw context
    output (``ctx``, model dtype), shaped via ``jax.eval_shape`` of the
    stash program so dtype/shape can never drift from what
    ``stash_carry`` produces.  Kept OUTSIDE the slot state on purpose:
    ``iter_step`` never reads it, so non-streaming engines pay nothing
    and existing AOT artifacts stay valid."""
    H, W = bucket_hw
    spec = jax.ShapeDtypeStruct((slots, H, W, 3), jnp.float32)
    fmap, ctx = jax.eval_shape(
        RAFTFrameFeatures(model_cfg).apply, variables, spec)
    zeros = lambda s: np.zeros(s.shape, dtype=s.dtype)
    return {"ctx": zeros(ctx), "fmap": zeros(fmap)}


def make_stash_fn(model_cfg: RAFTConfig):
    """``stash_carry(variables, image2, carry, admit) -> carry'``
    (pure; the engine jits/lowers it).

    Runs after a cold session admit: computes frame 2's features and
    scatters them into the admitted lanes' carry.  One extra encoder
    pass per session (frame 1 only) buys fmap reuse for every
    subsequent warm frame."""
    feats = RAFTFrameFeatures(model_cfg)

    def stash_carry(variables, image2, carry, admit):
        fmap, ctx = feats.apply(variables, image2)
        sel = lambda new, old: _lane_select(admit, new, old)
        return {"ctx": sel(ctx, carry["ctx"]),
                "fmap": sel(fmap, carry["fmap"])}

    return stash_carry


def make_warm_encode_fn(model_cfg: RAFTConfig):
    """``encode_warm(variables, image2, carry, state, admit, budgets)
    -> (state', carry')`` (pure; the engine jits/lowers it).

    The streaming admit: for lanes in ``admit``, frame 1 of the pair is
    the PREVIOUS streamed frame — its feature map and context come from
    the carry, so only ``image2`` (the new frame) runs through the
    encoders.  The lane's previous flow (``coords1 - coords0``, intact
    since its retirement: ``iter_step``'s masked commit never touches
    inactive lanes) is forward-warped on-device into the ``coords1``
    init — RAFT's video warm start.  The scatter semantics mirror
    :func:`make_encode_fn` exactly; ``carry'`` holds the new frame's
    features for the next warm frame."""
    enc = RAFTEncodeWarm(model_cfg)

    def encode_warm(variables, image2, carry, state, admit, budgets):
        flow_init = forward_warp_flow(state["coords1"] - state["coords0"])
        net, inp, coords0, coords1, corr, attn, fmap2, ctx2 = enc.apply(
            variables, image2, carry["fmap"], carry["ctx"], flow_init)
        sel = lambda new, old: _lane_select(admit, new, old)
        new_state = _pack_state(
            sel(net, state["net"]),
            sel(inp, state["inp"]),
            sel(coords0, state["coords0"]),
            sel(coords1, state["coords1"]),
            jax.tree_util.tree_map(sel, corr, state["corr"]),
            state["active"] | admit,
            jnp.where(admit, budgets.astype(jnp.int32), state["budget"]),
            state["converged"] & ~admit,
            jnp.where(admit, jnp.float32(-1.0), state["delta_max"]),
            jnp.where(admit, jnp.int32(0), state["iters_done"]),
            attn=None if attn is None else sel(attn, state["attn"]),
        )
        new_carry = {"ctx": sel(ctx2, carry["ctx"]),
                     "fmap": sel(fmap2, carry["fmap"])}
        return new_state, new_carry

    return encode_warm


def make_flow_fn(model_cfg: RAFTConfig):
    """``flow(variables, image1, image2) -> flow_up`` (pure; the engine
    jits/lowers it): the whole request as ONE program, for a model with
    no refinement loop (``RAFTConfig.refines`` false, arch 'gmflow').
    There is no state to admit into and no iteration to step, so neither
    program above is built for such a model; request mode calls this
    once a batch."""
    from raft_tpu.models.raft import RAFT

    model = RAFT(model_cfg)

    def flow(variables, image1, image2):
        return model.apply(variables, image1, image2, test_mode=True)[1]

    return flow


def advance(state: dict, moved: dict) -> dict:
    """The slot state after an ``iter_step`` call: ``moved`` over
    ``state``.  The leaves the program only reads stay the very arrays
    they were (no device work)."""
    return {**state, **moved}


def make_iter_fn(model_cfg: RAFTConfig):
    """``iter_step(variables, state, threshold, steps) -> (moved,
    flow_up)`` (pure; the engine jits/lowers it).  ``moved`` holds the
    state leaves a step moves (``active``, ``converged``, ``coords1``,
    ``delta_max``, ``iters_done``, ``net``) as they stand after the
    call; ``advance(state, moved)`` is the whole state.

    ``steps`` is a runtime int32 scalar: the program is a device loop
    (``lax.fori_loop`` with a traced bound, i.e. a ``while``) whose body
    is ONE refinement iteration, so one executable serves every count
    and ``steps=k`` is bit-identical to ``k`` calls with ``steps=1``
    (the same compiled body runs either way).

    ``flow_up`` is the full-resolution ``(S, H, W, 2)`` flow, carried
    through the loop: a lane's row is written at the step the lane
    retires (``active`` flipping true -> false), so it holds the flow at
    ITS retirement iteration whichever step of the call that was; rows
    of lanes that did not retire in this call are zeros.  Steps with no
    retiree skip the upsample entirely (``lax.cond``).

    What the body only reads — the corr pyramid, ``inp``, ``coords0``,
    ``budget`` and GMA's ``attn`` — is closed over, not carried, and not
    returned: the loop body holds no copy of a pyramid level, there is
    no program boundary between iterations to copy it across, and the
    program has no un-donated input to copy into an output."""
    step = RAFTIterStep(model_cfg)
    upsample = RAFTUpsample(model_cfg)

    def iter_step(variables, state, threshold, steps):
        inp, coords0, corr = state["inp"], state["coords0"], state["corr"]
        budget, attn = state["budget"], state.get("attn")

        def body(_, carry):
            (net0, coords1_0, active, was_converged, dmax0, iters_done,
             flow_up) = carry
            net, coords1 = step.apply(variables, net0, coords1_0, inp,
                                      coords0, corr, attn)
            # Masked commit: inactive lanes keep their state bit-for-bit
            # (free lanes carry zeros; a retired lane's state is dead
            # until the next admit overwrites it, but must not drift
            # meanwhile).
            net = _lane_select(active, net, net0)
            coords1 = _lane_select(active, coords1, coords1_0)

            delta = coords1 - coords1_0
            dmax = jnp.max(jnp.sqrt(jnp.sum(delta * delta, axis=-1)),
                           axis=(1, 2))
            dmax = jnp.where(active, dmax, dmax0)
            iters_done = iters_done + active.astype(jnp.int32)
            converged = active & (dmax < threshold)
            done = active & (converged | (iters_done >= budget))

            def _upsample(operands):
                n, f, up = operands
                return _lane_select(done, upsample.apply(variables, n, f),
                                    up)

            def _skip(operands):
                return operands[2]

            flow_up = jax.lax.cond(jnp.any(done), _upsample, _skip,
                                   (net, coords1 - coords0, flow_up))
            return (net, coords1, active & ~done,
                    was_converged | converged, dmax, iters_done, flow_up)

        S, H8, W8 = coords0.shape[:3]
        (net, coords1, active, converged, dmax, iters_done,
         flow_up) = jax.lax.fori_loop(
            0, steps, body,
            (state["net"], state["coords1"], state["active"],
             state["converged"], state["delta_max"], state["iters_done"],
             jnp.zeros((S, H8 * 8, W8 * 8, 2), jnp.float32)))
        moved = {"active": active, "converged": converged,
                 "coords1": coords1, "delta_max": dmax,
                 "iters_done": iters_done, "net": net}
        return moved, flow_up

    return iter_step


class EarlyExitRunner:
    """Offline (non-engine) driver of the slot programs over one fixed
    batch: encode, then iterate until every lane retires, returning the
    per-lane flow at ITS retirement iteration plus ``iters_used``.

    This is the measurement arm for the ``evaluate.py
    --early_exit_threshold`` quality gate and the early-exit tests:
    same compiled programs the serve path runs, no dispatcher in the
    way.  ``jax.jit`` call-site caching keys on shapes only, so
    sweeping thresholds re-uses one compile per batch shape."""

    def __init__(self, model_cfg: RAFTConfig):
        refuse_loop_state(model_cfg, "early exit")
        self.model_cfg = model_cfg
        self._encode = jax.jit(make_encode_fn(model_cfg))
        self._iter = jax.jit(make_iter_fn(model_cfg))

    def run(self, variables, image1, image2, iters: int,
            threshold: float = 0.0, return_residuals: bool = False):
        """``(flow_up (B, H, W, 2) f32, iters_used (B,) i32)`` for a
        ``/8``-aligned batch.  ``threshold <= 0`` reproduces the full
        ``iters``-step baseline.

        ``return_residuals=True`` appends the per-lane convergence
        residual ``delta_max`` (max flow-update magnitude, flow units
        at 1/8 resolution) captured at EACH lane's retirement
        iteration — the in-graph quality proxy ``obs/quality.py``
        calibrates against EPE.  Off by default so the baseline path
        transfers exactly what it always did."""
        B = int(np.asarray(image1).shape[0])
        admit = jnp.ones((B,), jnp.bool_)
        budgets = jnp.full((B,), int(iters), jnp.int32)
        state = state_template(self.model_cfg, variables, B,
                               tuple(np.asarray(image1).shape[1:3]))
        state = self._encode(variables, image1, image2, state, admit,
                             budgets)
        thr, one = jnp.float32(threshold), jnp.int32(1)
        out = None
        prev_active = np.ones((B,), bool)
        iters_used = np.zeros((B,), np.int32)
        residuals = np.full((B,), -1.0, np.float32)
        for _ in range(int(iters)):
            moved, flow_up = self._iter(variables, state, thr, one)
            state = advance(state, moved)
            active = np.asarray(state["active"])
            newly = prev_active & ~active
            if newly.any():
                flow_np = np.asarray(flow_up)
                if out is None:
                    out = np.zeros(flow_np.shape, np.float32)
                out[newly] = flow_np[newly]
                iters_used[newly] = np.asarray(state["iters_done"])[newly]
                if return_residuals:
                    residuals[newly] = np.asarray(
                        state["delta_max"])[newly]
            prev_active = active
            if not active.any():
                break
        assert out is not None and not prev_active.any(), \
            "lanes left active after their budget — iter_step retire " \
            "logic is broken"
        if return_residuals:
            return out, iters_used, residuals
        return out, iters_used
