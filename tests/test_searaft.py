"""arch 'searaft' (SEA-RAFT (M)) against its plain reference (tier-1, CPU,
small size).

``benchmark/reference_searaft.py`` is float32 ``jax.numpy`` that imports
nothing of ``raft_tpu``; the weights are ``benchmark/weights_searaft.py``'s,
seeded, with the ConvNeXt blocks' ``gamma`` of order 1 so that the branch
cannot drop out unseen.  Held here: the forward flow and ``info``, the
mixture loss and its first gradient, three AdamW steps with batch statistics
in three encoder calls, the loss's L1 limit, the count of predictions and of
parameters, the serving split through the slot state, the model in the
engine's keys, the refusal of streaming sessions, the CLIs' ``--arch`` and
the converter's name map for the public state dict.
"""

import dataclasses
import json
import math
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_searaft as ref  # noqa: E402
from benchmark import weights_searaft  # noqa: E402
from raft_tpu.config import RAFTConfig, TrainConfig  # noqa: E402
from raft_tpu.models import raft as raft_mod  # noqa: E402
from raft_tpu.models.raft import RAFT  # noqa: E402

H, W, ITERS, B = 48, 64, 2, 2
GAMMA = 0.85
# fp32 compute: comparable to the reference; one loop body to compile
CFG = RAFTConfig.searaft(scan_unroll=1)


def ref_cfg():
    with open(os.path.join(ROOT, "benchmark/configs/searaft_m.json")) as f:
        return json.load(f)


TCFG = TrainConfig(stage="chairs", lr=4e-4, num_steps=1000, batch_size=B,
                   image_size=(H, W), iters=ITERS, gamma=GAMMA)


@pytest.fixture(scope="module")
def variables():
    # weights.make_variables draws ~400 leaves in one jitted call: 40 s of
    # XLA:CPU compile here.  The same function with its ``jax.jit`` left
    # off draws the same leaves from the same keys one by one in 12 s (the
    # jitted maker itself runs in the cell's ``--rehearse-tiny``).
    with mock.patch.object(jax, "jit", lambda f: f):
        v = weights_searaft.make_variables(RAFT(CFG), 2147483659)
    for blk in ("refine_0", "refine_1"):
        g = np.asarray(v["params"]["refine"]["update_block"][blk]["gamma"])
        assert g.shape == (384,) and 0.5 <= g.min() and g.max() <= 1.5
    return v


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(7)
    base = rng.uniform(0, 255, (B, H + 8, W + 8, 3)).astype(np.float32)
    image1, image2 = base[:, 4:-4, 4:-4], base[:, 2:-6, 5:-3]
    flow = rng.normal(0, 2, (B, H, W, 2)).astype(np.float32)
    valid = (rng.uniform(size=(B, H, W)) > 0.1).astype(np.float32)
    return {"image1": image1, "image2": image2, "flow": flow, "valid": valid}


@pytest.fixture(scope="module")
def ref_loss_and_grad():
    """One compiled reference loss-and-gradient for the file, in blocks of
    one row as the benchmark runs it at full size (batch norm still spans
    the batch): the program, which has no blocks, is held to that."""
    return ref.make_loss_and_grad(ref_cfg(), ITERS, block=1)


@pytest.fixture(scope="module")
def prog_loss_and_grad():
    """One compiled loss-and-gradient of the program for the file: the
    train step's own differentiated core (``train/step.py make_loss_fn``:
    the architecture's loss fused into the upsample scan, its weights over
    ``iters + 1`` predictions, batch statistics), ``f(params, batch_stats,
    batch) -> ((loss, (metrics, new batch_stats)), grads)``."""
    from raft_tpu.train.step import make_loss_fn

    f = jax.jit(jax.value_and_grad(make_loss_fn(RAFT(CFG), TCFG),
                                   has_aux=True))
    return lambda params, stats, batch: f(params, stats, batch,
                                          jax.random.PRNGKey(0))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def flat(tree):
    return np.concatenate([np.ravel(x) for x in
                           jax.tree_util.tree_leaves(tree)])


def test_forward_flow_and_info_match_the_reference(variables, batch):
    model = RAFT(CFG)
    out = jax.jit(lambda v, a, b: model.apply(v, a, b, iters=ITERS))(
        variables, batch["image1"], batch["image2"])
    flow_up, info_up = out["final"], out["info"][-1]
    np.testing.assert_array_equal(flow_up, out["flow"][-1])
    with ref.highest():
        want, want_info = jax.jit(lambda v, a, b: ref.forward(
            ref_cfg(), v, a, b, ITERS, with_info=True))(
                variables, batch["image1"], batch["image2"])
        dropped = ref.forward(ref_cfg(), variables, batch["image1"],
                              batch["image2"], ITERS, drop_aggregate=True)
    assert flow_up.shape == want.shape == (B, H, W, 2)
    assert info_up.shape == want_info.shape == (B, H, W, 4)
    # float32 on both sides; what is left is summation order
    assert rel(flow_up, want) < 2e-4
    assert rel(info_up, want_info) < 2e-4
    # and the branch matters (the regressed first flow is most of the
    # answer at two iterations; the planted fault still stands 50x off)
    assert rel(dropped, want) > 0.005
    # test_mode keeps every architecture's pair of values
    assert len(model.apply(variables, batch["image1"], batch["image2"],
                           iters=1, test_mode=True)) == 2


def test_loss_and_first_gradient_match_the_reference(
        variables, batch, ref_loss_and_grad, prog_loss_and_grad):
    from raft_tpu.train.step import make_loss_fn

    (loss, (metrics, _)), grads = prog_loss_and_grad(
        variables["params"], variables["batch_stats"], batch)
    assert metrics["loss_iter"].shape == (ITERS + 1,)
    with ref.highest():
        ref_loss, ref_grads = ref_loss_and_grad(variables, batch)
    assert abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)) < 1e-4
    got = jax.tree_util.tree_leaves_with_path(grads)
    want = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(got) == len(want)
    assert rel(flat(grads), flat(ref_grads)) < 2e-3
    # the new leaves, each on its own
    for path, g in got:
        name = jax.tree_util.keystr(path)
        if any(k in name for k in ("dwconv", "pwconv", "gamma", "final",
                                   "init_conv", "flow_head")) \
                or "['refine_0']['norm']" in name:
            assert np.linalg.norm(want[path]) > 0, name
            assert rel(g, want[path]) < 1e-2, name
    # the unfused path (stacked predictions, train/loss.py) is the same
    # loss, chosen by the architecture and not by what the model returned
    unfused = make_loss_fn(RAFT(CFG), dataclasses.replace(
        TCFG, fused_loss=False))
    stacked, _ = jax.jit(unfused)(variables["params"],
                                  variables["batch_stats"], batch,
                                  jax.random.PRNGKey(0))
    assert abs(float(stacked) - float(ref_loss)) / abs(float(ref_loss)) \
        < 1e-4


def test_three_adamw_steps_with_batch_statistics(
        variables, batch, ref_loss_and_grad, prog_loss_and_grad):
    """Three steps of the loop's optimiser (clip, AdamW, one-cycle:
    ``make_optimizer``) on the train step's loss-and-gradient against the
    reference's three steps; the jitted ``make_train_step`` round it runs
    in the cell's ``--rehearse-tiny``.  Each of the three encoder calls
    normalises alone: a feature encoder that saw both images in one batch
    norm call reads another loss."""
    import optax

    from raft_tpu.train.optim import make_optimizer

    tx = make_optimizer(TCFG.lr, TCFG.num_steps, TCFG.wdecay, TCFG.epsilon,
                        TCFG.clip)
    update = jax.jit(tx.update)
    params, stats0 = variables["params"], variables["batch_stats"]
    stats, opt = stats0, tx.init(params)
    batches = [batch, {k: v[::-1].copy() for k, v in batch.items()}, batch]
    losses = []
    for b in batches:
        (loss, (_, stats)), grads = prog_loss_and_grad(params, stats, b)
        updates, opt = update(grads, opt, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    # ref.train_steps, with the file's one compiled loss-and-gradient
    want_params, want_losses = variables["params"], []
    mu = nu = jax.tree_util.tree_map(jnp.zeros_like, want_params)
    adamw_step = jax.jit(ref.adamw_step)     # ~330 leaves: one program
    with ref.highest():
        for k, b in enumerate(batches):
            loss, grads = ref_loss_and_grad(
                {"params": want_params, "batch_stats": stats0}, b)
            want_params, mu, nu, _ = adamw_step(
                want_params, grads, mu, nu, float(k),
                ref.onecycle(float(k), TCFG.lr, TCFG.num_steps))
            want_losses.append(float(loss))
    for got, want in zip(losses, want_losses):
        assert abs(got - want) / abs(want) < 2e-3, (losses, want_losses)
    p0 = flat(variables["params"])
    # Adam's first steps are all but the gradient's sign: entries whose
    # gradient is near nought flip on float32 summation order
    assert rel(flat(params) - p0, flat(want_params) - p0) < 0.15
    # running statistics moved in both encoders (twice a step in fnet)
    for enc in ("fnet", "cnet"):
        assert rel(flat(stats[enc]), flat(stats0[enc])) > 1e-3
    # One batch-norm call over both images is another function: on a pair
    # whose second image is darker (other statistics), the program agrees
    # with three calls and not with fnet(both images stacked).
    dark = dict(batch, image2=0.4 * batch["image2"])
    (loss, _), _ = prog_loss_and_grad(variables["params"], stats0, dark)
    with ref.highest():
        apart, _ = ref_loss_and_grad(variables, dark)
        both = ref.resnet(
            jnp.concatenate([ref._scale(dark["image1"]),
                             ref._scale(dark["image2"])]),
            variables["params"]["fnet"], None, True, None, False)
        c = ref.encode(variables, dark["image1"], dark["image2"],
                       train=True)[2]
        stacked = ref.sequence_loss(ref_cfg(), variables, dark, ITERS,
                                    feats=(both[:B], both[B:], c))
    assert abs(float(loss) - float(apart)) / float(apart) < 5e-4
    assert abs(float(stacked) - float(apart)) / float(apart) > 5e-3
    assert raft_mod.batch_norm_calls(CFG) == 3
    assert raft_mod.batch_norm_calls(RAFTConfig.full()) == 1


@pytest.mark.parametrize("nll", ["program", "reference"])
def test_equal_logits_and_zero_scale_give_l1_plus_log2(nll):
    from raft_tpu.train.loss import mixture_nll

    fn = mixture_nll if nll == "program" else ref.mixture_nll
    rng = np.random.default_rng(3)
    err = jnp.asarray(np.abs(rng.normal(0, 3, (5, 7))), jnp.float32)
    a = jnp.asarray(rng.normal(0, 2, (5, 7)), jnp.float32)
    for b in (0.0, -3.0):      # clip(b, 0, 10): nothing under 0 counts
        got = fn(err, a, a, jnp.full((5, 7), b, jnp.float32))
        np.testing.assert_allclose(got, err + math.log(2.0), rtol=1e-5,
                                   atol=1e-5)
    # a wide first component that the logits prefer costs its log-scale
    wide = fn(err, a + 20.0, a, jnp.full((5, 7), 2.0, jnp.float32))
    np.testing.assert_allclose(
        wide, math.log(2.0) + 2.0 + err * math.exp(-2.0), rtol=1e-4,
        atol=1e-4)


@pytest.mark.parametrize("iters", [1, 4])
def test_predictions_are_iterations_plus_one(variables, iters):
    model = RAFT(CFG)
    spec = jax.ShapeDtypeStruct((1, H, W, 3), jnp.float32)
    out = jax.eval_shape(lambda v, a, b: model.apply(v, a, b, iters=iters),
                         variables, spec, spec)
    assert out["flow"].shape == (iters + 1, 1, H, W, 2)
    assert out["info"].shape == (iters + 1, 1, H, W, 4)
    assert out["final"].shape == (1, H, W, 2)
    assert raft_mod.predictions(CFG, iters) == iters + 1
    assert raft_mod.predictions(RAFTConfig.gma(), iters) == iters
    gt = jax.ShapeDtypeStruct((1, H, W, 2), jnp.float32)
    va = jax.ShapeDtypeStruct((1, H, W), jnp.float32)
    per_iter, metrics = jax.eval_shape(
        lambda v, a, b, g, m: model.apply(v, a, b, iters=iters,
                                          loss_targets=(g, m, 400.0)),
        variables, spec, spec, gt, va)
    assert per_iter.shape == metrics["epe_iter"].shape == (iters + 1,)


def test_the_first_prediction_does_not_read_the_feature_encoder(variables,
                                                                batch):
    model = RAFT(CFG)
    fwd = jax.jit(lambda v, a, b: model.apply(v, a, b, iters=1))
    out = fwd(variables, batch["image1"], batch["image2"])
    other = dict(variables, params=dict(
        variables["params"], fnet=jax.tree_util.tree_map(
            lambda x: 1.05 * x, variables["params"]["fnet"])))
    moved = fwd(other, batch["image1"], batch["image2"])
    np.testing.assert_array_equal(out["flow"][0], moved["flow"][0])
    np.testing.assert_array_equal(out["info"][0], moved["info"][0])
    assert rel(moved["flow"][1], out["flow"][1]) > 1e-3
    # it does read both images
    swapped = fwd(variables, batch["image1"], batch["image1"])
    assert rel(swapped["flow"][0], out["flow"][0]) > 1e-3


def test_parameter_count_of_the_m_preset(variables):
    n = sum(int(np.prod(x.shape)) for x in
            jax.tree_util.tree_leaves(variables["params"]))
    assert 19.6e6 < n < 19.8e6
    assert n == ref_cfg()["parameters"]
    assert (CFG.hidden_dim, CFG.context_dim, CFG.corr_levels,
            CFG.corr_radius) == (128, 128, 4, 4)
    # one preset, no option a dimension
    names = {f.name for f in dataclasses.fields(RAFTConfig)}
    assert names == {f.name for f in dataclasses.fields(RAFTConfig.full())}
    assert RAFTConfig.preset("searaft") == RAFTConfig.searaft()


def test_serving_split_matches_the_reference_forward(variables, batch):
    """``encode_admit`` (whose state starts ``coords1`` at the grid plus
    the regressed first flow) then ``iter_step``s through the slot state,
    against the reference's whole forward pass."""
    from raft_tpu.serve import slots

    state = slots.state_template(CFG, variables, B, (H, W))
    assert "attn" not in state
    enc = jax.jit(slots.make_encode_fn(CFG))(
        variables, batch["image1"], batch["image2"], state,
        jnp.ones((B,), bool), jnp.full((B,), ITERS, jnp.int32))
    first = np.asarray(enc["coords1"] - enc["coords0"])
    assert np.abs(first).max() > 1e-3          # not the zero flow
    runner = slots.EarlyExitRunner(CFG)
    flow, used = runner.run(variables, batch["image1"], batch["image2"],
                            ITERS)
    assert list(used) == [ITERS] * B
    with ref.highest():
        want = ref.forward(ref_cfg(), variables, batch["image1"],
                           batch["image2"], ITERS)
    assert rel(flow, want) < 2e-4


def test_an_engine_for_full_is_not_reused_and_streaming_is_refused(
        variables, tmp_path):
    """The exported key names the model, and a 'searaft' engine refuses a
    'full' engine's programs by that name; ``stats()`` says that ``enc``
    regresses a first flow; a streaming session is refused by name: the
    context needs both frames, so there is no per-frame context to carry."""
    from raft_tpu.obs import stages
    from raft_tpu.serve import InferenceEngine, ServeConfig

    serve_cfg = ServeConfig(iters=ITERS, batch_sizes=(1,), max_batch=1)
    full_cfg = RAFTConfig.full()
    full = InferenceEngine(
        weights_searaft.make_variables(RAFT(full_cfg), 5), full_cfg,
        serve_cfg)
    full.warmup([(H, W)])
    manifest = full.export_aot(str(tmp_path))
    assert {k["arch"] for k in manifest["keys"]} == {"full"}
    assert full.stats()["first_flow"] is False

    sea = InferenceEngine(variables, CFG, dataclasses.replace(
        serve_cfg, aot_dir=str(tmp_path)))
    assert sea.aot_info["ok"] is False and sea.aot_info["imported"] == 0
    assert "'searaft'" in sea.aot_info["error"]
    sea.warmup([(H, W)])           # builds its own
    st = sea.stats()
    assert st["model"] == "searaft" and st["first_flow"] is True
    assert st["attn_bytes"] == {}
    progs = [r for r in stages.recent("compile")
             if r.get("kind") == "program"]
    assert progs[-1]["model"] == "searaft"
    assert progs[-1]["predictions"] == ITERS + 1
    with sea:
        rng = np.random.default_rng(0)
        img = rng.uniform(0, 255, (H, W, 3)).astype(np.float32)
        flow = sea.submit(img, img[::-1].copy()).result(timeout=600)
    assert flow.shape == (H, W, 2) and np.isfinite(flow).all()
    assert stages.recent("serve")[-1]["model"] == "searaft"

    slot = InferenceEngine(variables, CFG, dataclasses.replace(
        serve_cfg, batching="slot", slots=1))
    with slot:
        with pytest.raises(ValueError, match="searaft.*both frames"):
            slot.stream_open("s", img)
    # and the programs themselves refuse, whoever builds them
    from raft_tpu.serve import slots

    with pytest.raises(ValueError, match="streaming sessions are not"):
        slots.carry_template(CFG, variables, 1, (H, W))


def test_arch_option_checkpoint_name_and_flow_init(variables, tmp_path):
    from raft_tpu.cli import arch_from_args, demo, evaluate, serve, train
    from raft_tpu.cli.evaluate import load_model_variables, variables_arch
    from raft_tpu.train.checkpoint import save_variables

    for cli, base in ((train, []), (serve, ["--random-init"]),
                      (evaluate, ["--model", "x", "--dataset", "chairs"]),
                      (demo, ["--model", "x"])):
        assert arch_from_args(cli.parse_args(base + ["--arch", "searaft"])) \
            == "searaft"
    assert variables_arch(variables) == "searaft"
    path = str(tmp_path / "ck")
    save_variables(path, jax.device_get(variables))
    assert variables_arch(load_model_variables(path, "searaft")) == "searaft"
    with pytest.raises(SystemExit, match="holds a 'searaft' model"):
        load_model_variables(path, "full")
    # no warm start: the model regresses its own first flow
    spec = jax.ShapeDtypeStruct((1, H, W, 3), jnp.float32)
    with pytest.raises(ValueError, match="takes no flow_init"):
        jax.eval_shape(lambda v, a, b, f: RAFT(CFG).apply(
            v, a, b, iters=1, flow_init=f, test_mode=True), variables, spec,
            spec, jax.ShapeDtypeStruct((1, H // 8, W // 8, 2), jnp.float32))


def _torch_state_dict(variables):
    """The public SEA-RAFT state dict's names and layouts (OIHW, the two
    ``nn.Linear`` weights ``(out, in)``, ``bnN``, ``final_conv``, the heads'
    Sequentials, the downsample alias), made from a flax tree."""
    from flax.traverse_util import flatten_dict

    def torch_name(path):
        parts = list(path)
        if parts[0] == "refine":
            parts = parts[1:]
        if parts[0] == "upsampler":
            parts = ["upsample_weight",
                     {"mask_conv1": "0", "mask_conv2": "2"}[parts[2]],
                     parts[3]]
        if parts[0] == "flow_head":
            parts[1] = {"conv1": "0", "conv2": "2"}[parts[1]]
        if parts[0] in ("fnet", "cnet"):
            parts = [q for p in parts for q in
                     (p.split("_") if p.startswith("layer") else [p])]
            parts = ["bn" + p[4:] if p.startswith("norm") else p
                     for p in parts]
            if parts[1] == "conv2" and len(parts) == 3:
                parts[1] = "final_conv"
            if "downsample_conv" in parts:
                i = parts.index("downsample_conv")
                parts = parts[:i] + ["downsample", "0"] + parts[i + 1:]
        parts = [f"refine.{p[7:]}" if p.startswith("refine_") else p
                 for p in parts]
        return [p for p in parts if p != "BatchNorm_0"]

    sd = {}
    for path, x in flatten_dict(jax.device_get(variables["params"])).items():
        x, parts = np.asarray(x), torch_name(path)
        if parts[-1] == "kernel":
            parts[-1] = "weight"
            x = x[0, 0].T if "pwconv" in parts[-2] \
                else x.transpose(3, 2, 0, 1)
        elif parts[-1] == "scale":
            parts[-1] = "weight"
        sd["module." + ".".join(parts)] = x
    for path, x in flatten_dict(jax.device_get(
            variables["batch_stats"])).items():
        parts = torch_name(path)
        parts[-1] = {"mean": "running_mean", "var": "running_var"}[parts[-1]]
        sd["module." + ".".join(parts)] = np.asarray(x)
        sd["module." + ".".join(parts[:-1] + ["num_batches_tracked"])] = \
            np.zeros((), np.int64)
    # the downsample Sequential registers its norm a second time
    for k in [k for k in sd if ".bn3." in k]:
        sd[k.replace(".bn3.", ".downsample.1.")] = sd[k]
    return sd


def test_convert_maps_the_public_state_dict_and_refuses_another_arch(
        variables):
    from raft_tpu import convert

    sd = _torch_state_dict(variables)
    assert sd["module.update_block.refine.0.dwconv.weight"].shape \
        == (384, 1, 7, 7)
    assert sd["module.update_block.refine.1.pwconv1.weight"].shape \
        == (512, 384)
    for key in ("module.init_conv.weight", "module.flow_head.2.bias",
                "module.upsample_weight.0.weight",
                "module.cnet.layer2.0.downsample.1.running_mean",
                "module.fnet.final_conv.weight", "module.cnet.bn1.weight",
                "module.update_block.refine.0.norm.weight",
                "module.update_block.refine.0.gamma"):
        assert key in sd, key
    assert sd["module.cnet.conv1.weight"].shape == (64, 6, 7, 7)
    out = convert.convert_state_dict(sd, convert.make_template(CFG))
    for coll in ("params", "batch_stats"):
        a = jax.tree_util.tree_leaves_with_path(out[coll])
        b = dict(jax.tree_util.tree_leaves_with_path(
            jax.device_get(variables[coll])))
        assert len(a) == len(b)
        for path, x in a:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(b[path]))
    # into another architecture: refused, the first key without a place
    with pytest.raises(ValueError, match=r"SEA-RAFT checkpoint "
                       r"\('module\.flow_head\.0\.bias'"):
        convert.convert_state_dict(
            sd, convert.make_template(RAFTConfig.full()))
    # and a RAFT state dict into this one: the first key missing
    with pytest.raises(ValueError, match=r"'init_conv\.weight' is the "
                       "first key missing"):
        convert.convert_state_dict(
            {k: v for k, v in sd.items()
             if k.startswith(("module.fnet", "module.cnet"))},
            convert.make_template(CFG))
