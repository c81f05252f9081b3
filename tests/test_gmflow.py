"""arch 'gmflow' (GMFlow, one scale) against its plain reference (tier-1,
CPU, small size).

``benchmark/reference_gmflow.py`` is float32 ``jax.numpy`` that imports
nothing of ``raft_tpu``; the weights are ``benchmark/weights_gmflow.py``'s,
seeded.  Held here at 64x96 (8x12 at 1/8, windows of 4x6): the two
predictions, the loss and its first gradient, and three planted faults that
each fail that comparison (Swin's mask left out of the odd blocks, the
Transformer's messages left out, the propagated flow not detached); the
shift mask against a brute-force "same region" mask; what the architecture
refuses by name (slot batching, streaming, ``flow_init``, a non-default
``--iters``, early exit); the pad multiple of 16 in ``evaluate.py`` and in
the engine, whose one ``flow`` program answers as the eval forward does; the
counts the loop and the engine quote; the converter's name map for the
public state dict.
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

from benchmark import reference_gmflow as ref  # noqa: E402
from benchmark import weights_gmflow  # noqa: E402
from raft_tpu.config import RAFTConfig  # noqa: E402
from raft_tpu.models import gmflow  # noqa: E402
from raft_tpu.models import raft as raft_mod  # noqa: E402
from raft_tpu.models.raft import RAFT  # noqa: E402

H, W, B = 64, 96, 2
CFG = RAFTConfig.preset("gmflow")      # fp32 compute: the reference's
GAMMA = 0.9
# What float32 leaves between two implementations of one function here,
# on ``weights_gmflow``'s draw (no softmax near one-hot: the correlation's
# logits have a standard deviation of ~1.5).  Measured 1.2e-6 (flow, of a
# largest flow of 44 px), under 1e-7 (loss) and 3.8e-6 (first gradient,
# whole tree); the planted faults below read 0.009-0.018 in the loss and
# 0.39-1.12 in the gradient.
FLOW_RTOL, LOSS_RTOL, GRAD_RTOL = 2e-5, 5e-6, 1e-4


def ref_cfg():
    with open(os.path.join(ROOT, "benchmark/configs/gmflow_base.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def variables():
    return weights_gmflow.make_variables(RAFT(CFG), 2147483659)


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
        {"params": params}, batch["image1"], batch["image2"], train=True,
        loss_targets=(batch["flow"], batch["valid"], 400.0))
    return GAMMA * per[0] + per[1], (per, metrics)


def reference_loss_and_grad(variables, batch, **kw):
    with ref.highest():
        return jax.jit(jax.value_and_grad(lambda p: ref.sequence_loss(
            ref_cfg(), {"params": p}, batch, GAMMA, **kw)))(
                variables["params"])


def tree_gap(a, b):
    """|a - b| / |b| over the whole tree."""
    fa, fb = flatten_dict(a), flatten_dict(b)
    num = sum(float(jnp.sum((fa[k] - fb[k]) ** 2)) for k in fb)
    return (num / sum(float(jnp.sum(fb[k] ** 2)) for k in fb)) ** 0.5


@pytest.fixture(scope="module")
def reference(variables, batch):
    return reference_loss_and_grad(variables, batch)


def test_both_predictions_match_the_reference(variables, batch):
    model = RAFT(CFG)
    with ref.highest():
        got = jax.jit(lambda v, a, b: model.apply(v, a, b))(
            variables, batch["image1"], batch["image2"])
        want = jax.jit(lambda v, a, b: ref.forward(
            ref_cfg(), v, a, b, both=True))(
                variables, batch["image1"], batch["image2"])
        low, up = jax.jit(lambda v, a, b: model.apply(
            v, a, b, iters=7, test_mode=True))(
                variables, batch["image1"], batch["image2"])
    assert got.shape == (2, B, H, W, 2) and low.shape == (B, H // 8, W // 8, 2)
    scale = float(np.abs(want).max())
    assert scale > 1.0          # the flows are not degenerate
    np.testing.assert_allclose(got, want, atol=FLOW_RTOL * scale)
    # test mode returns the second prediction, whatever ``iters`` says
    np.testing.assert_allclose(up, want[1], atol=FLOW_RTOL * scale)


def test_loss_and_first_gradient_match_the_reference(variables, batch,
                                                     reference):
    with ref.highest():
        (loss, (per, metrics)), grads = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(variables["params"], batch)
    want, want_grads = reference
    assert abs(float(loss) - float(want)) <= LOSS_RTOL * float(want)
    assert tree_gap(grads, want_grads) <= GRAD_RTOL
    assert per.shape == (2,) and metrics["epe_iter"].shape == (2,)
    assert float(metrics["epe"]) == pytest.approx(
        float(metrics["epe_iter"][1]))


def _faulty_gap(variables, batch, reference):
    with ref.highest():
        (loss, _), grads = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(variables["params"], batch)
    return (abs(float(loss) - float(reference[0])) / float(reference[0]),
            tree_gap(grads, reference[1]))


def test_an_odd_block_without_its_mask_fails_the_comparison(
        variables, batch, reference, monkeypatch):
    monkeypatch.setattr(gmflow, "shift_mask", lambda h, w, splits: np.zeros(
        (4, (h // 2) * (w // 2), (h // 2) * (w // 2)), np.float32))
    loss_gap, grad_gap = _faulty_gap(variables, batch, reference)
    assert loss_gap > 100 * LOSS_RTOL and grad_gap > 10 * GRAD_RTOL


def test_a_flow_that_is_not_detached_fails_the_comparison(
        variables, batch, reference, monkeypatch):
    """The second term sends no gradient into the matching: with the
    ``stop_gradient`` left out the loss is the same and the gradient is
    another one."""
    monkeypatch.setattr(gmflow, "_detached", lambda flow: flow)
    loss_gap, grad_gap = _faulty_gap(variables, batch, reference)
    assert loss_gap <= LOSS_RTOL and grad_gap > 10 * GRAD_RTOL


def test_the_reference_without_messages_is_told_apart(variables, batch,
                                                      reference):
    """``drop_aggregate`` (the benchmark's planted fault): every layer
    returns its source."""
    loss, grads = reference_loss_and_grad(variables, batch,
                                          drop_aggregate=True)
    assert abs(float(loss) - float(reference[0])) > 100 * LOSS_RTOL \
        * float(reference[0])
    assert tree_gap(grads, reference[1]) > 10 * GRAD_RTOL


@pytest.mark.parametrize("hw", [(8, 12), (48, 64), (6, 10)])
def test_shift_mask_is_the_same_region_mask(hw):
    """Token i may attend to token j of its rolled window iff, before the
    roll, both lay on the same side of the map's wrap-around seam on each
    axis: brute force over every pair."""
    h, w = hw
    wh, ww, sh, sw = h // 2, w // 2, h // 4, w // 4
    mask = gmflow.shift_mask(h, w)
    assert mask.shape == (4, wh * ww, wh * ww)
    np.testing.assert_array_equal(mask, np.asarray(ref.shift_mask(h, w)))
    # where each token of the rolled map came from
    ys = (np.arange(h) + sh) % h
    xs = (np.arange(w) + sw) % w
    for k in range(4):
        y0, x0 = (k // 2) * wh, (k % 2) * ww
        src = [(ys[y0 + i], xs[x0 + j]) for i in range(wh)
               for j in range(ww)]
        for a, (ya, xa) in enumerate(src):
            for b, (yb, xb) in enumerate(src):
                # contiguous before the roll: no wrap between them, i.e.
                # neither axis has one token from the rolled-in strip
                # (source index < shift) and the other from the rest
                same = ((ya < sh) == (yb < sh) or y0 == 0) \
                    and ((xa < sw) == (xb < sw) or x0 == 0)
                assert mask[k, a, b] == (0.0 if same else gmflow.MASK_VALUE)


def test_sine_position_is_the_references():
    np.testing.assert_allclose(gmflow.sine_position(4, 6),
                               np.asarray(ref.sine_position(4, 6, 128)),
                               atol=1e-6)


def test_counts_the_loop_and_the_engine_quote(variables):
    assert raft_mod.predictions(CFG, 12) == 2 == raft_mod.predictions(CFG, 0)
    assert raft_mod.batch_norm_calls(CFG) == 0
    # two float32 (N, N) softmaxes a pair kept for the backward pass
    assert raft_mod.attention_bytes(CFG, 16, 48, 64) == 2 * 16 * 3072 ** 2 * 4
    assert not CFG.refines and CFG.pad_multiple == 16
    assert CFG.global_matching and CFG.attn_splits == (gmflow.SPLITS,)
    assert all(RAFTConfig.preset(a).refines
               and RAFTConfig.preset(a).pad_multiple == 8
               for a in ("full", "small", "gma", "searaft"))
    n = sum(int(np.prod(x.shape)) for x in
            jax.tree_util.tree_leaves(variables["params"]))
    assert n == ref_cfg()["parameters"] == 4681504
    assert abs(n - 4.7e6) / 4.7e6 < 0.02
    by = {}
    for path, x in flatten_dict(variables["params"]).items():
        by[path[0]] = by.get(path[0], 0) + int(np.prod(x.shape))
    assert by == {"backbone": 1050336, "transformer": 3150336,
                  "feature_flow_attn": 33024, "upsampler": 447808}
    assert not variables["batch_stats"]


def test_what_needs_a_loop_is_refused_by_name(variables):
    from raft_tpu.cli import demo, evaluate, serve, train
    from raft_tpu.serve import InferenceEngine, ServeConfig
    from raft_tpu.serve.slots import EarlyExitRunner

    spec = jax.ShapeDtypeStruct((1, H, W, 3), jnp.float32)
    with pytest.raises(ValueError, match="flow_init.*--arch gmflow"):
        jax.eval_shape(lambda v, a, b, f: RAFT(CFG).apply(
            v, a, b, flow_init=f, test_mode=True), variables, spec, spec,
            jax.ShapeDtypeStruct((1, H // 8, W // 8, 2), jnp.float32))
    with pytest.raises(ValueError, match="batching='slot'.*--arch gmflow"):
        InferenceEngine(variables, CFG, ServeConfig(batching="slot"))
    with pytest.raises(ValueError, match="early exit.*--arch gmflow"):
        InferenceEngine(variables, CFG,
                        ServeConfig(early_exit_threshold=0.1))
    with pytest.raises(ValueError, match="early exit.*--arch gmflow"):
        EarlyExitRunner(CFG)
    with pytest.raises(ValueError, match="warm_start.*--arch gmflow"):
        from raft_tpu.evaluate import create_sintel_submission

        create_sintel_submission(variables, CFG, warm_start=True)
    engine = InferenceEngine(variables, CFG, ServeConfig())
    try:
        engine.start()
        im = np.zeros((H, W, 3), np.float32)
        with pytest.raises(ValueError,
                           match="streaming session.*--arch gmflow"):
            engine.stream_open("s", im)
        with pytest.raises(ValueError, match="iters budget.*--arch gmflow"):
            engine.submit(im, im, iters=3)
    finally:
        engine.stop()
    # --iters: each CLI's default passes, another value does not
    for cli, base in ((train, []), (serve, ["--random-init"]),
                      (evaluate, ["--model", "x", "--dataset", "chairs"]),
                      (demo, ["--model", "x"])):
        args = cli.parse_args(base + ["--arch", "gmflow"])
        assert args.arch == "gmflow"
        with pytest.raises(SystemExit, match="--arch gmflow has no "
                           "refinement loop, so --iters 5"):
            cli.parse_args(base + ["--arch", "gmflow", "--iters", "5"])
        assert cli.parse_args(base + ["--arch", "full", "--iters",
                                      "5"]).iters == 5


def test_served_flow_is_the_eval_flow_at_a_pad_multiple_of_16(variables):
    """72x104 is /8-aligned with H/8 = 9 and W/8 = 13 odd: the model
    cannot split that map into 2x2 windows, so ``evaluate.py`` and the
    engine both pad to 80x112, and one ``flow`` program answers."""
    from raft_tpu import evaluate
    from raft_tpu.obs import stages
    from raft_tpu.ops.pad import InputPadder
    from raft_tpu.serve import InferenceEngine, ServeConfig

    h, w = 72, 104
    rng = np.random.default_rng(3)
    im1 = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)
    im2 = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)
    spec = jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 16"):
        jax.eval_shape(lambda v, a, b: RAFT(CFG).apply(
            v, a, b, test_mode=True), variables, spec, spec)

    class OnePair:
        image_list = [("a", "b")]

        def __len__(self):
            return 1

        def load(self, i):
            return {"image1": im1, "image2": im2}

    eval_fn = evaluate.make_eval_fn(CFG, 32)
    (_, ref_flow), = evaluate._batched_flows(
        variables, eval_fn, OnePair(), "sintel", 1,
        multiple=CFG.pad_multiple)
    assert ref_flow.shape == (h, w, 2)
    padder = InputPadder((h, w), mode="sintel", multiple=CFG.pad_multiple)
    assert padder.pad_np(im1).shape == (80, 112, 3)

    engine = InferenceEngine(variables, CFG, ServeConfig(
        max_batch=1, batch_sizes=(1,), max_wait_ms=1))
    assert engine.cfg.bucket_multiple == 16
    with engine:
        got = engine.infer(im1, im2, timeout=300)
        stats = engine.stats()
        rec = stages.recent("serve")[-1]
    np.testing.assert_allclose(got, ref_flow, rtol=1e-4, atol=1e-4)
    assert engine.compile_counter.counts() == {((80, 112), 1, "flow"): 1}
    assert stats["model"] == "gmflow" and stats["iter_calls"] == 0
    assert rec["calls"] == 1 and rec["model"] == "gmflow"
    assert list(stats["cost"]) == ["80x112/b1/flow"]
    prog = [r for r in stages.recent("compile")
            if r.get("name") == "80x112/b1/flow"][-1]
    assert prog["model"] == "gmflow" and prog["predictions"] == 1


def _torch_state_dict(variables):
    """The public GMFlow state dict's names and layouts (OIHW, ``nn.Linear``
    weights ``(out, in)``, ``layers.N``, ``mlp.0|2``, ``upsampler.0|2``, the
    downsample Sequential; no bias on the backbone's 7x7 and 3x3
    convolutions), made from a flax tree."""
    sd = {}
    for path, x in flatten_dict(jax.device_get(variables["params"])).items():
        x = np.asarray(x)
        parts = [q for p in path for q in (
            p.split("_") if p.startswith(("layer", "mlp_")) else [p])]
        if "downsample_conv" in parts:
            i = parts.index("downsample_conv")
            parts = parts[:i] + ["downsample", "0"] + parts[i + 1:]
        if parts[0] == "upsampler":
            parts[1] = {"conv1": "0", "conv2": "2"}[parts[1]]
        if (parts[0] == "backbone" and parts[-1] == "bias"
                and parts[-2] in ("conv1", "conv2") and len(parts) > 3
                or parts[:3] == ["backbone", "conv1", "bias"]):
            continue                        # bias=False in the public code
        if parts[-1] == "kernel":
            parts[-1] = "weight"
            x = x.T if x.ndim == 2 else x.transpose(3, 2, 0, 1)
        elif parts[-1] == "scale":
            parts[-1] = "weight"
        sd["module." + ".".join(parts)] = x
    return sd


def test_convert_maps_the_public_state_dict_and_refuses_another_arch(
        variables):
    from raft_tpu import convert

    sd = _torch_state_dict(variables)
    for key, shape in (
            ("module.backbone.conv1.weight", (64, 3, 7, 7)),
            ("module.backbone.layer2.0.downsample.0.bias", (96,)),
            ("module.backbone.conv2.bias", (128,)),
            ("module.transformer.layers.3.self_attn.q_proj.weight",
             (128, 128)),
            ("module.transformer.layers.5.cross_attn_ffn.mlp.0.weight",
             (1024, 256)),
            ("module.transformer.layers.0.cross_attn_ffn.norm2.weight",
             (128,)),
            ("module.feature_flow_attn.k_proj.bias", (128,)),
            ("module.upsampler.0.weight", (256, 130, 3, 3)),
            ("module.upsampler.2.bias", (576,))):
        assert sd[key].shape == shape, key
    assert "module.backbone.layer1.0.conv1.bias" not in sd
    assert "module.backbone.conv1.bias" not in sd
    out = convert.convert_state_dict(sd, convert.make_template(CFG))
    got = flatten_dict(out["params"])
    want = flatten_dict(jax.device_get(variables["params"]))
    assert set(got) == set(want)
    zeros = 0
    for path, x in got.items():
        if convert._GMFLOW_INERT_BIAS.match("/".join(("params",) + path)):
            assert not np.any(x)
            zeros += x.size
        else:
            np.testing.assert_array_equal(x, np.asarray(want[path]))
    assert zeros == 1216
    with pytest.raises(ValueError, match=r"GMFlow checkpoint "
                       r"\('module\.backbone\.conv1\.weight'"):
        convert.convert_state_dict(
            sd, convert.make_template(RAFTConfig.full()))
    with pytest.raises(ValueError, match=r"'backbone\.conv1\.weight' is "
                       "the first key missing"):
        convert.convert_state_dict(
            {"module.fnet.conv1.weight": np.zeros((64, 3, 7, 7))},
            convert.make_template(CFG))


def test_checkpoint_names_its_architecture(variables, tmp_path):
    from raft_tpu.cli.evaluate import load_model_variables, variables_arch
    from raft_tpu.train.checkpoint import save_variables

    assert variables_arch(variables) == "gmflow"
    path = str(tmp_path / "ck")
    save_variables(path, jax.device_get(variables))
    assert variables_arch(load_model_variables(path, "gmflow")) == "gmflow"
    with pytest.raises(SystemExit, match="holds a 'gmflow' model"):
        load_model_variables(path, "full")
