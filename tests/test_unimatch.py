"""arch 'unimatch' (UniMatch's flow model: GMFlow at two scales with six
regression refinements) against its plain reference (tier-1, CPU, small
size).

``benchmark/reference_unimatch.py`` is float32 ``jax.numpy`` that imports
nothing of ``raft_tpu`` and uses UniMatch's own operations (a
``grid_sample`` of four gathers, explicit taps for the local match and the
refinement's correlation, an unfold for the local propagation); the weights
are ``benchmark/weights_unimatch.py``'s, seeded.  Held here at 64x96 (8x12
at 1/8 in windows of 4x6, 16x24 at 1/4 in windows of 2x3): the ten
predictions, the loss and its first gradient; each new part against the
reference's or a brute force (the trident's shared weight, the warp, the
local match, the local propagation, the refinement's volume and lookup with
its tap order, the Mosaic lookup at one level in the interpreter, one
Transformer at splits 2 and 8); the pad multiple of 32; what the
architecture refuses by name; the engine's one ``flow`` program against the
eval forward; the CLIs; the counts the loop and the engine quote.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_unimatch as ref  # noqa: E402
from benchmark import weights_unimatch  # noqa: E402
from raft_tpu.config import RAFTConfig  # noqa: E402
from raft_tpu.models import gmflow  # noqa: E402
from raft_tpu.models import raft as raft_mod  # noqa: E402
from raft_tpu.models.raft import RAFT  # noqa: E402

H, W, B = 64, 96, 2
ITERS = 6
CFG = RAFTConfig.preset("unimatch")     # fp32 compute: the reference's
GAMMA = 0.9
# What float32 leaves between two implementations of one function here, on
# ``weights_unimatch``'s draw: measured 1e-5 of the largest flow in every
# prediction (44-51 px), ~1e-6 in the loss and 2e-5 in the first gradient
# (whole tree): products summed in other orders, through a warp and six
# refinements.  The planted faults below read 1e-2 and more in the loss or
# the gradient.
FLOW_RTOL, LOSS_RTOL, GRAD_RTOL = 5e-5, 1e-5, 2e-4


def ref_cfg():
    with open(os.path.join(ROOT,
                           "benchmark/configs/unimatch_flow_s2r6.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def variables():
    return weights_unimatch.make_variables(RAFT(CFG), 2147483659)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(5)
    base = rng.uniform(0, 255, (B, H + 16, W + 16, 3)).astype(np.float32)
    gt = rng.normal(0, 4, (B, H, W, 2)).astype(np.float32)
    gt[0, :4, :4] = 500.0                       # over max_flow: masked
    return {"image1": base[:, 8:-8, 8:-8], "image2": base[:, 5:-11, 10:-6],
            "flow": gt,
            "valid": (rng.uniform(size=(B, H, W)) > 0.1).astype(np.float32)}


def program_loss(params, batch, model=None):
    per, metrics = (model or RAFT(CFG)).apply(
        {"params": params}, batch["image1"], batch["image2"], iters=ITERS,
        train=True, loss_targets=(batch["flow"], batch["valid"], 400.0))
    n = per.shape[0]
    w = GAMMA ** (n - 1.0 - jnp.arange(n))
    return jnp.sum(w * per), (per, metrics)


def reference_loss_and_grad(variables, batch, **kw):
    with ref.highest():
        return jax.jit(jax.value_and_grad(lambda p: ref.sequence_loss(
            ref_cfg(), {"params": p}, batch, ITERS, GAMMA, **kw)))(
                variables["params"])


def tree_gap(a, b):
    """|a - b| / |b| over the whole tree."""
    fa, fb = flatten_dict(a), flatten_dict(b)
    num = sum(float(jnp.sum((fa[k] - fb[k]) ** 2)) for k in fb)
    return (num / sum(float(jnp.sum(fb[k] ** 2)) for k in fb)) ** 0.5


@pytest.fixture(scope="module")
def reference(variables, batch):
    return reference_loss_and_grad(variables, batch)


def test_ten_predictions_match_the_reference(variables, batch):
    model = RAFT(CFG)
    with ref.highest():
        got = jax.jit(lambda v, a, b: model.apply(v, a, b, iters=ITERS))(
            variables, batch["image1"], batch["image2"])
        want = jax.jit(lambda v, a, b: ref.forward(
            ref_cfg(), v, a, b, ITERS, both=True))(
                variables, batch["image1"], batch["image2"])
        low, up = jax.jit(lambda v, a, b: model.apply(
            v, a, b, iters=ITERS, test_mode=True))(
                variables, batch["image1"], batch["image2"])
    assert got.shape == (4 + ITERS, B, H, W, 2)
    assert low.shape == (B, H // 4, W // 4, 2)
    for i in range(4 + ITERS):
        scale = float(np.abs(want[i]).max())
        assert scale > 1.0, i          # the flows are not degenerate
        np.testing.assert_allclose(got[i], want[i], atol=FLOW_RTOL * scale,
                                   err_msg=f"prediction {i}")
    # test mode returns the last refinement's flow upsampled
    np.testing.assert_allclose(up, want[-1],
                               atol=FLOW_RTOL * float(np.abs(want[-1]).max()))


def test_loss_and_first_gradient_match_the_reference(variables, batch,
                                                     reference):
    with ref.highest():
        (loss, (per, metrics)), grads = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(variables["params"], batch)
    want, want_grads = reference
    assert abs(float(loss) - float(want)) <= LOSS_RTOL * float(want)
    assert tree_gap(grads, want_grads) <= GRAD_RTOL
    assert per.shape == (4 + ITERS,)
    assert metrics["epe_iter"].shape == (4 + ITERS,)
    assert float(metrics["epe"]) == pytest.approx(
        float(metrics["epe_iter"][-1]))
    # every module learns: no leaf of the tree is without gradient
    dead = [k for k, g in flatten_dict(grads).items()
            if not np.any(np.asarray(g)) and k[-1] == "kernel"]
    assert not dead


def _faulty_gap(variables, batch, reference):
    with ref.highest():
        (loss, _), grads = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(variables["params"], batch)
    return (abs(float(loss) - float(reference[0])) / float(reference[0]),
            tree_gap(grads, reference[1]))


def test_taps_in_rafts_order_fail_the_comparison(variables, batch, reference,
                                                 monkeypatch):
    """The refinement reads UniMatch's y-major taps: left in the lookup's
    own x-major order, the loss and the gradient are another."""
    real = gmflow._lookup

    def x_major(cfg, corr_state, coords):
        t = real(cfg, corr_state, coords)
        b, h, w, _ = t.shape
        return jnp.swapaxes(t.reshape(b, h, w, 9, 9), 3, 4).reshape(
            b, h, w, 81)

    monkeypatch.setattr(gmflow, "_lookup", x_major)
    loss_gap, grad_gap = _faulty_gap(variables, batch, reference)
    # the random motion encoder reads a permutation of its inputs about as
    # well: the loss moves little (3e-4), the gradient a great deal
    assert loss_gap > 10 * LOSS_RTOL and grad_gap > 10 * GRAD_RTOL


def test_an_unwarped_second_scale_fails_the_comparison(
        variables, batch, reference, monkeypatch):
    monkeypatch.setattr(gmflow, "warp", lambda f2, flow: f2.astype(
        jnp.float32))
    loss_gap, grad_gap = _faulty_gap(variables, batch, reference)
    assert loss_gap > 100 * LOSS_RTOL and grad_gap > 10 * GRAD_RTOL


def test_the_reference_without_messages_is_told_apart(variables, batch,
                                                      reference):
    loss, grads = reference_loss_and_grad(variables, batch,
                                          drop_aggregate=True)
    assert abs(float(loss) - float(reference[0])) > 100 * LOSS_RTOL \
        * float(reference[0])
    assert tree_gap(grads, reference[1]) > 10 * GRAD_RTOL


def test_trident_shares_one_weight_between_the_scales(variables, batch):
    from raft_tpu.models.extractor import BasicEncoder

    enc = BasicEncoder(128, "instance", 0.0, jnp.float32,
                       num_output_scales=2)
    p = {"params": variables["params"]["backbone"]}
    x = jnp.asarray(batch["image1"]) / 255.0
    f8, f4 = enc.apply(p, x)
    assert f8.shape == (B, H // 8, W // 8, 128)
    assert f4.shape == (B, H // 4, W // 4, 128)
    # a 3x3, pad 1 convolution at stride 2 is the stride-1 one's even rows
    # and columns: one weight gives both maps
    np.testing.assert_allclose(f8, f4[:, ::2, ::2], rtol=1e-5, atol=1e-5)
    tri = flatten_dict(p["params"]["trident_conv"])
    assert list(tri) == [("kernel",)] and tri[("kernel",)].shape == (
        3, 3, 128, 128)
    # the third stage keeps 1/4 and projects its width change by a 1x1
    assert "downsample_conv" in p["params"]["layer3_0"]


def test_warp_is_grid_sample_with_zero_padding():
    rng = np.random.default_rng(0)
    f2 = jnp.asarray(rng.normal(size=(2, 6, 9, 5)), jnp.float32)
    flow = jnp.asarray(rng.uniform(-4, 4, (2, 6, 9, 2)), jnp.float32)
    got = gmflow.warp(f2, flow)
    want = ref.grid_sample(f2, ref.pixel_grid(2, 6, 9) + flow)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # a flow that leaves the map reads zeros
    far = gmflow.warp(f2, jnp.full((2, 6, 9, 2), 50.0))
    assert not np.any(np.asarray(far))


def test_local_match_is_a_masked_softmax_over_the_neighbours():
    rng = np.random.default_rng(1)
    h, w, C, r = 5, 7, 8, 2
    f1 = rng.normal(size=(1, h, w, C)).astype(np.float32)
    f2 = rng.normal(size=(1, h, w, C)).astype(np.float32)
    got = np.asarray(gmflow.local_match(jnp.asarray(f1), jnp.asarray(f2), r))
    want = np.zeros((h, w, 2))
    for y in range(h):
        for x in range(w):
            s, d = [], []
            for dy in range(-r, r + 1):
                for dx in range(-r, r + 1):
                    if 0 <= y + dy < h and 0 <= x + dx < w:
                        s.append(f1[0, y, x] @ f2[0, y + dy, x + dx]
                                 / np.sqrt(C))
                        d.append((dx, dy))
            p = np.exp(np.array(s) - max(s))
            want[y, x] = (p[:, None] * np.array(d)).sum(0) / p.sum()
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, np.asarray(ref.local_match(
        jnp.asarray(f1), jnp.asarray(f2), r)), rtol=1e-5, atol=1e-5)


def test_local_propagation_is_an_unfold_with_zero_padding(variables):
    rng = np.random.default_rng(2)
    f1 = jnp.asarray(rng.normal(size=(2, 6, 8, 128)), jnp.float32)
    flow = jnp.asarray(rng.normal(size=(2, 6, 8, 2)) * 3, jnp.float32)
    p = variables["params"]["feature_flow_attn"]
    got = gmflow.FlowPropagation(jnp.float32).apply({"params": p}, f1, flow,
                                                    1)
    with ref.highest():
        want = ref.local_propagate(p, f1, flow, 1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the zero keys off the map stay in the softmax: a corner's weights do
    # not sum to one over its in-map neighbours alone
    glob = gmflow.FlowPropagation(jnp.float32).apply({"params": p}, f1, flow)
    assert not np.allclose(got, glob)


@pytest.mark.parametrize("impl", ["allpairs", "allpairs_pallas", "chunked"])
def test_refinement_correlation_is_the_gather_formula(impl):
    """The one-level volume of the backbone's 1/4 maps read by the lookup
    (XLA's, the Mosaic kernel in the interpreter, and RAFT's on-demand
    blocks, which never hold the volume) is
    ``local_correlation_with_flow``: ``F1(x) . bil(F2, x + flow + d) /
    sqrt(C)`` with the window's offsets y-major; forward and the gradient
    into both maps."""
    from raft_tpu.models.raft import _build_corr_state

    cfg = RAFTConfig.preset("unimatch", corr_impl=impl,
                            pallas_offtpu="interpret")
    rng = np.random.default_rng(3)
    h, w, C = 8, 16, 16
    f1 = jnp.asarray(rng.normal(size=(1, h, w, C)), jnp.float32)
    f2 = jnp.asarray(rng.normal(size=(1, h, w, C)), jnp.float32)
    flow = jnp.asarray(rng.uniform(-3, 3, (1, h, w, 2)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(1, h, w, 81)), jnp.float32)

    def program(f1, f2):
        state = _build_corr_state(cfg, f1, f2)
        return gmflow._lookup(cfg, state, ref.pixel_grid(1, h, w) + flow)

    def formula(f1, f2):
        return ref.sampled_dots(f1, f2, ref.pixel_grid(1, h, w) + flow, 4)

    with ref.highest():
        got, vjp = jax.vjp(program, f1, f2)
        want, vjp_ref = jax.vjp(formula, f1, f2)
        g, g_ref = vjp(cot), vjp_ref(cot)
    assert raft_mod.corr_impl_at(cfg, h, w) == impl
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    # the centre tap is the 41st: d = (0, 0)
    np.testing.assert_allclose(
        got[..., 40], np.asarray(formula(f1, f2))[..., 40], rtol=1e-4,
        atol=1e-4)


@pytest.mark.parametrize("impl", ["allpairs", "allpairs_pallas"])
def test_bfloat16_volume_is_rafts_build_rounded_once(impl):
    """Under bfloat16 compute the 1/4 volume is written in its storage type
    and its backward multiplies the bfloat16 cotangent with each float32
    operand in two bfloat16 halves: the values are RAFT's float32 build
    rounded once to bfloat16, and the gradients into both maps agree with
    the float32 build's to ~1e-5 (the halves keep 16 bits of the
    operand)."""
    from raft_tpu.models.raft import _build_corr_state
    from raft_tpu.ops.corr import build_corr_pyramid, build_corr_pyramid_flat

    cfg = RAFTConfig.preset("unimatch", compute_dtype="bfloat16",
                            corr_impl=impl, pallas_offtpu="interpret")
    rng = np.random.default_rng(6)
    f1 = jnp.asarray(rng.normal(size=(2, 8, 16, 128)), jnp.float32)
    f2 = jnp.asarray(rng.normal(size=(2, 8, 16, 128)), jnp.float32)
    got, vjp = jax.vjp(lambda a, b: _build_corr_state(cfg, a, b)[0],
                       f1, f2)
    if impl == "allpairs":
        def build(a, b):
            return build_corr_pyramid(a, b, 1, "highest")[0]
    else:
        def build(a, b):
            return build_corr_pyramid_flat(a, b, 1, "highest", pad_q=128)[0]
    with ref.highest():
        want, vjp_ref = jax.vjp(build, f1, f2)
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want.astype(jnp.bfloat16),
                                          np.float32), rtol=1e-2, atol=1e-2)
    g = jnp.asarray(rng.normal(size=got.shape), jnp.bfloat16)
    for a, b in zip(vjp(g), vjp_ref(g.astype(jnp.float32))):
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) < 2e-5


@pytest.mark.parametrize("splits,hw", [(2, (8, 12)), (8, (16, 24))])
def test_one_transformer_runs_both_window_geometries(variables, splits, hw):
    h, w = hw
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2 * B, h * w, 128)), jnp.float32)
    p = variables["params"]["transformer"]
    got = gmflow.FeatureTransformer(jnp.float32).apply({"params": p}, x, h,
                                                       w, splits)
    with ref.highest():
        want = ref.transformer(p, x, h, w, splits)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # one set of weights: the tree has the six blocks once
    assert sorted(p) == [f"layers_{i}" for i in range(6)]
    mask = gmflow.shift_mask(h, w, splits)
    np.testing.assert_array_equal(mask, np.asarray(ref.shift_mask(
        h, w, splits)))


def test_pad_multiple_is_32_and_the_model_says_so(variables):
    assert CFG.pad_multiple == 32 and CFG.attn_splits == (2, 8)
    assert CFG.num_scales == 2 and CFG.local_radii == ((-1, -1), (4, 1))
    assert CFG.refines and CFG.global_matching
    # 64x80: 1/4 = 16x20, whose width does not split into 8 windows
    spec = jax.ShapeDtypeStruct((1, 64, 80, 3), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 32"):
        jax.eval_shape(lambda v, a, b: RAFT(CFG).apply(
            v, a, b, iters=2, test_mode=True), variables, spec, spec)


def test_counts_the_loop_and_the_engine_quote(variables):
    assert raft_mod.predictions(CFG, 6) == 10
    assert raft_mod.predictions(CFG, 2) == 6
    assert raft_mod.batch_norm_calls(CFG) == 0
    assert raft_mod.attention_bytes(CFG, 8, 48, 64) == 2 * 8 * 3072 ** 2 * 4
    assert raft_mod.window_attention_at(CFG, 48, 64) == ["xla", "xla"]
    assert raft_mod.refine_counters(CFG, 6, 48, 64) == {
        "lookup": {"path": "xla", "levels": 1, "map": [96, 128]},
        "refine_iters": 6}
    assert raft_mod.refine_counters(RAFTConfig.preset("gmflow"), 6, 48,
                                    64) == {}
    n = sum(int(np.prod(x.shape)) for x in
            jax.tree_util.tree_leaves(variables["params"]))
    assert n == ref_cfg()["parameters"] == 7361904
    assert abs(n - 7.4e6) / 7.4e6 < 0.02
    by = {}
    for path, x in flatten_dict(variables["params"]).items():
        by[path[0]] = by.get(path[0], 0) + int(np.prod(x.shape))
    assert by == {"backbone": 1197792, "transformer": 3150336,
                  "feature_flow_attn": 33024, "refine_proj": 33024,
                  "refine": 2947728}
    assert not variables["batch_stats"]


def test_what_needs_a_loop_state_is_refused_by_name(variables):
    from raft_tpu.cli import demo, evaluate, serve, train
    from raft_tpu.cli.evaluate import variables_arch
    from raft_tpu.serve import InferenceEngine, ServeConfig
    from raft_tpu.serve.slots import EarlyExitRunner

    spec = jax.ShapeDtypeStruct((1, H, W, 3), jnp.float32)
    with pytest.raises(ValueError, match="flow_init.*--arch unimatch"):
        jax.eval_shape(lambda v, a, b, f: RAFT(CFG).apply(
            v, a, b, flow_init=f, test_mode=True), variables, spec, spec,
            jax.ShapeDtypeStruct((1, H // 4, W // 4, 2), jnp.float32))
    with pytest.raises(ValueError, match="batching='slot'.*--arch unimatch"):
        InferenceEngine(variables, CFG, ServeConfig(batching="slot"))
    with pytest.raises(ValueError, match="early exit.*--arch unimatch"):
        EarlyExitRunner(CFG)
    # --iters is the number of refinements: every CLI takes it
    for cli, base in ((train, []), (serve, ["--random-init"]),
                      (evaluate, ["--model", "x", "--dataset", "chairs"]),
                      (demo, ["--model", "x"])):
        args = cli.parse_args(base + ["--arch", "unimatch", "--iters", "6"])
        assert args.arch == "unimatch" and args.iters == 6
    assert variables_arch(variables) == "unimatch"


def test_clis_default_to_the_published_six_refinements():
    """Without ``--iters`` every entry point runs the configuration's 6
    refinements (``RAFTConfig.published_iters``), not RAFT's defaults;
    a given ``--iters`` and the other architectures' defaults stand."""
    from raft_tpu.cli import demo, evaluate, serve, train

    for cli, base, rafts in ((train, [], 12), (serve, ["--random-init"], 32),
                             (evaluate, ["--model", "x", "--dataset",
                                         "chairs"], None),
                             (demo, ["--model", "x"], 20)):
        assert cli.parse_args(base + ["--arch", "unimatch"]).iters == 6
        assert cli.parse_args(
            base + ["--arch", "unimatch", "--iters", str(rafts or 24)]
        ).iters == (rafts or 24)
        assert cli.parse_args(base + ["--arch", "full"]).iters == rafts


def test_served_flow_is_the_eval_flow_at_a_pad_multiple_of_32(variables):
    """72x104 pads to 96x128 for this model (80x112 for 'gmflow'): the
    engine's one ``flow`` program, with its refinements inside, answers as
    ``evaluate.py``'s forward does."""
    from raft_tpu import evaluate
    from raft_tpu.obs import stages
    from raft_tpu.ops.pad import InputPadder
    from raft_tpu.serve import InferenceEngine, ServeConfig

    h, w = 72, 104
    rng = np.random.default_rng(3)
    im1 = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)
    im2 = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)

    class OnePair:
        image_list = [("a", "b")]

        def __len__(self):
            return 1

        def load(self, i):
            return {"image1": im1, "image2": im2}

    eval_fn = evaluate.make_eval_fn(CFG, 2)
    (_, ref_flow), = evaluate._batched_flows(
        variables, eval_fn, OnePair(), "sintel", 1,
        multiple=CFG.pad_multiple)
    assert ref_flow.shape == (h, w, 2)
    padder = InputPadder((h, w), mode="sintel", multiple=CFG.pad_multiple)
    assert padder.pad_np(im1).shape == (96, 128, 3)

    engine = InferenceEngine(variables, CFG, ServeConfig(
        iters=2, max_batch=1, batch_sizes=(1,), max_wait_ms=1))
    assert engine.cfg.bucket_multiple == 32
    with engine:
        got = engine.infer(im1, im2, timeout=300)
        stats = engine.stats()
        rec = stages.recent("serve")[-1]
    np.testing.assert_allclose(got, ref_flow, rtol=1e-4, atol=1e-4)
    assert engine.compile_counter.counts() == {((96, 128), 1, "flow"): 1}
    assert stats["model"] == "unimatch" and stats["iter_calls"] == 0
    assert rec["calls"] == 1


def _public_state_dict(variables):
    """UniMatch's flow checkpoint's names and layouts (OIHW, ``nn.Linear``
    weights ``(out, in)``, ``layers.N``, ``mlp.0|2``, the downsample
    Sequential, the GRU's separate z and r convolutions, ``refine.mask.0|2``;
    no bias on the backbone's 7x7 and 3x3 convolutions nor on the trident),
    made from a flax tree."""
    sd = {}
    for path, x in flatten_dict(jax.device_get(variables["params"])).items():
        x = np.asarray(x)
        parts = [q for p in path for q in (
            p.split("_") if p.startswith(("layer", "mlp_")) else [p])]
        if "downsample_conv" in parts:
            i = parts.index("downsample_conv")
            parts = parts[:i] + ["downsample", "0"] + parts[i + 1:]
        if parts[:2] == ["refine", "update_block"]:
            parts = ["refine"] + parts[2:]
        if parts[:2] == ["refine", "mask_head"]:
            parts = ["refine", "mask",
                     {"mask_conv1": "0", "mask_conv2": "2"}[parts[2]]] \
                + parts[3:]
        if (parts[0] == "backbone" and parts[-1] == "bias"
                and parts[-2] in ("conv1", "conv2") and len(parts) > 3
                or parts[:3] == ["backbone", "conv1", "bias"]):
            continue                        # bias=False in the public code
        if parts[-1] == "kernel":
            parts[-1] = "weight"
            x = x.T if x.ndim == 2 else x.transpose(3, 2, 0, 1)
        elif parts[-1] == "scale":
            parts[-1] = "weight"
        key = "module." + ".".join(parts)
        if ".gru.convzr" in key:
            z, r = np.split(x, 2, axis=0)
            sd[key.replace("convzr", "convz")] = z
            sd[key.replace("convzr", "convr")] = r
        else:
            sd[key] = x
    return sd


def test_convert_maps_the_public_state_dict_and_refuses_another_arch(
        variables):
    from raft_tpu import convert

    sd = _public_state_dict(variables)
    for key, shape in (
            ("module.backbone.trident_conv.weight", (128, 128, 3, 3)),
            ("module.backbone.layer3.0.downsample.0.weight", (128, 96, 1, 1)),
            ("module.refine_proj.weight", (256, 128, 1, 1)),
            ("module.refine.encoder.convc1.weight", (256, 81, 1, 1)),
            ("module.refine.gru.convz1.weight", (128, 384, 1, 5)),
            ("module.refine.flow_head.conv2.bias", (2,)),
            ("module.refine.mask.2.weight", (144, 256, 1, 1)),
            ("module.feature_flow_attn.k_proj.bias", (128,))):
        assert sd[key].shape == shape, key
    assert "module.backbone.trident_conv.bias" not in sd
    out = convert.convert_state_dict(sd, convert.make_template(CFG))
    got = flatten_dict(out["params"])
    want = flatten_dict(jax.device_get(variables["params"]))
    assert set(got) == set(want)
    for path, x in got.items():
        if convert._GMFLOW_INERT_BIAS.match("/".join(("params",) + path)):
            assert not np.any(x)
        else:
            np.testing.assert_array_equal(x, np.asarray(want[path]))
    with pytest.raises(ValueError, match=r"UniMatch flow checkpoint"):
        convert.convert_state_dict(
            sd, convert.make_template(RAFTConfig.preset("gmflow")))
    with pytest.raises(ValueError, match=r"'backbone\.trident_conv\.weight'"
                       " is the first key missing"):
        convert.convert_state_dict(
            {k: v for k, v in sd.items() if "refine" not in k
             and "trident" not in k}, convert.make_template(CFG))
