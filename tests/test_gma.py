"""arch 'gma' (RAFT + global motion aggregation) against its plain reference
(tier-1, CPU, small size).

``benchmark/reference_gma.py`` is float32 ``jax.numpy`` that imports nothing
of ``raft_tpu``; the weights are ``benchmark/weights_gma.py``'s, seeded, with
``gamma != 0`` so that the attention block cannot drop out unseen.  Held
here: the forward flow, the loss and its first gradient, the attention's rows,
that ``A`` is built once whatever the iteration count, the serving split
(``encode_admit`` then ``iter_step``s through the slot state) against the
reference's whole forward, the model in the program's keys (AOT artifacts,
``engine.stats()``, the stage records), the CLIs' ``--arch`` and the
converter's name map for the public GMA state dict.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_gma, weights_gma  # noqa: E402
from raft_tpu.cli import arch_from_args  # noqa: E402
from raft_tpu.config import RAFTConfig  # noqa: E402
from raft_tpu.models.raft import RAFT, attention_bytes  # noqa: E402

H, W, ITERS = 48, 64, 3
CFG = RAFTConfig.gma()            # fp32 compute: comparable to the reference


def ref_cfg():
    with open(os.path.join(ROOT, "benchmark/configs/gma_full.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def variables():
    v = weights_gma.make_variables(RAFT(CFG), 2147483659)
    gamma = float(v["params"]["refine"]["update_block"]["aggregator"][
        "gamma"][0])
    assert 0.5 <= gamma <= 1.5
    return v


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(7)
    B = 2
    base = rng.uniform(0, 255, (B, H + 8, W + 8, 3)).astype(np.float32)
    image1, image2 = base[:, 4:-4, 4:-4], base[:, 2:-6, 5:-3]
    flow = rng.normal(0, 2, (B, H, W, 2)).astype(np.float32)
    valid = (rng.uniform(size=(B, H, W)) > 0.1).astype(np.float32)
    return {"image1": image1, "image2": image2, "flow": flow, "valid": valid}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_forward_flow_matches_the_reference(variables, batch):
    model = RAFT(CFG)
    _, flow_up = jax.jit(lambda v, a, b: model.apply(
        v, a, b, iters=ITERS, test_mode=True))(
            variables, batch["image1"], batch["image2"])
    with reference_gma.highest():
        ref = jax.jit(lambda v, a, b: reference_gma.forward(
            ref_cfg(), v, a, b, ITERS))(variables, batch["image1"],
                                        batch["image2"])
        dropped = reference_gma.forward(ref_cfg(), variables,
                                        batch["image1"], batch["image2"],
                                        ITERS, drop_aggregate=True)
    assert flow_up.shape == ref.shape == (2, H, W, 2)
    # float32 on both sides; what is left is summation order
    assert rel(flow_up, ref) < 2e-4
    # and the block matters: the planted fault is three orders away
    assert rel(dropped, ref) > 0.05


def test_loss_and_first_gradient_match_the_reference(variables, batch):
    model = RAFT(CFG.replace(scan_unroll=1))
    stats = variables["batch_stats"]

    def loss_fn(params):
        (per_iter, _), _ = model.apply(
            {"params": params, "batch_stats": stats}, batch["image1"],
            batch["image2"], iters=ITERS, train=True,
            loss_targets=(batch["flow"], batch["valid"], 400.0),
            mutable=["batch_stats"])
        w = 0.8 ** (ITERS - 1.0 - jnp.arange(ITERS, dtype=jnp.float32))
        return jnp.sum(w * per_iter)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(variables["params"])
    with reference_gma.highest():
        ref_loss, ref_grads = reference_gma.make_loss_and_grad(
            ref_cfg(), ITERS, block=2)(variables, batch)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 1e-4
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat) == len(ref_flat)
    total = rel(np.concatenate([np.ravel(g) for _, g in flat]),
                np.concatenate([np.ravel(ref_flat[p]) for p, _ in flat]))
    assert total < 2e-3
    # the new leaves, each on its own
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        if "att" in name or "aggregator" in name:
            assert np.linalg.norm(ref_flat[path]) > 0, name
            assert rel(g, ref_flat[path]) < 5e-3, name


def test_attention_rows_sum_to_one_and_match_the_reference(variables):
    from raft_tpu.models.update import Attention

    rng = np.random.default_rng(3)
    inp = jax.nn.relu(jnp.asarray(rng.normal(size=(2, 6, 8, 128)),
                                  jnp.float32))
    p = variables["params"]["att"]
    A = Attention(128).apply({"params": p}, inp)
    assert A.shape == (2, 48, 48)
    np.testing.assert_allclose(np.asarray(A).sum(-1), 1.0, atol=1e-5)
    with reference_gma.highest():
        ref = reference_gma.attention(p, inp)
    np.testing.assert_allclose(np.asarray(A), np.asarray(ref), atol=1e-5)
    assert attention_bytes(CFG, 2, 6, 8) == 2 * 48 * 48 * 4
    assert attention_bytes(RAFTConfig.full(), 2, 6, 8) == 0


def _count(jaxpr, pred):
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(pred(eqn))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, pred)
    return n


@pytest.mark.parametrize("iters", [1, 4])
def test_attention_is_built_once_whatever_the_iterations(variables, iters):
    """One ``q k^T`` (an N x N result from 128-wide operands; the all-pairs
    volume's are 256 wide) in the program; the ``A v`` product sits in the
    scanned body, traced once."""
    model = RAFT(CFG.replace(scan_unroll=1, remat=False))
    z = jnp.zeros((1, H, W, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda v, a, b: model.apply(
        v, a, b, iters=iters, test_mode=True))(variables, z, z).jaxpr
    n = (H // 8) * (W // 8)

    def qk(eqn):
        return (eqn.primitive.name == "dot_general"
                and eqn.invars[0].aval.shape == (1, n, 128)
                and eqn.outvars[0].aval.shape == (1, n, n))

    def av(eqn):
        return (eqn.primitive.name == "dot_general"
                and eqn.invars[0].aval.shape == (1, n, n))

    assert _count(jaxpr, qk) == 1
    assert _count(jaxpr, av) == 1


def test_serving_split_matches_the_reference_forward(variables, batch):
    """``encode_admit`` then ``iter_step``s through the slot state (which
    carries ``attn`` beside the pyramid) against the reference's whole
    forward pass."""
    from raft_tpu.serve import slots

    state = slots.state_template(CFG, variables, 2, (H, W))
    n = (H // 8) * (W // 8)
    assert state["attn"].shape == (2, n, n)
    assert "attn" not in slots.state_template(
        RAFTConfig.full(), weights_gma.make_variables(
            RAFT(RAFTConfig.full()), 1), 1, (H, W))
    runner = slots.EarlyExitRunner(CFG)
    flow, used = runner.run(variables, batch["image1"], batch["image2"],
                            ITERS)
    assert list(used) == [ITERS, ITERS]
    with reference_gma.highest():
        ref = reference_gma.forward(ref_cfg(), variables, batch["image1"],
                                    batch["image2"], ITERS)
    assert rel(flow, ref) < 2e-4


def test_engine_keys_carry_the_model(variables, tmp_path):
    """A ``gma`` engine does not take a ``full`` engine's programs: the
    exported key names the model, and the import is refused by that name.
    ``stats()`` and the stage clock's records say which model ran."""
    from raft_tpu.obs import stages
    from raft_tpu.serve import InferenceEngine, ServeConfig

    serve_cfg = ServeConfig(iters=2, batch_sizes=(1,), max_batch=1)
    full_cfg = RAFTConfig.full()
    full_vars = weights_gma.make_variables(RAFT(full_cfg), 5)
    full = InferenceEngine(full_vars, full_cfg, serve_cfg)
    full.warmup([(H, W)])
    manifest = full.export_aot(str(tmp_path))
    assert {k["arch"] for k in manifest["keys"]} == {"full"}
    assert full.stats()["model"] == "full"
    assert full.stats()["attn_bytes"] == {}

    import dataclasses

    gma = InferenceEngine(variables, CFG, dataclasses.replace(
        serve_cfg, aot_dir=str(tmp_path)))
    assert gma.aot_info["ok"] is False and gma.aot_info["imported"] == 0
    assert "'gma'" in gma.aot_info["error"]
    assert "full" in gma.aot_info["error"]
    gma.warmup([(H, W)])           # builds its own
    st = gma.stats()
    n = (H // 8) * (W // 8)
    assert st["model"] == "gma"
    assert st["attn_bytes"] == {f"{H}x{W}/b1": n * n * 4}
    assert st["compiles"][f"{H}x{W}/b1/iter"] == 1
    progs = [r for r in stages.recent("compile")
             if r.get("kind") == "program"]
    assert progs[-1]["model"] == "gma"
    with gma:
        rng = np.random.default_rng(0)
        img = rng.uniform(0, 255, (H, W, 3)).astype(np.float32)
        flow = gma.submit(img, img[::-1].copy()).result(timeout=300)
    assert flow.shape == (H, W, 2) and np.isfinite(flow).all()
    assert stages.recent("serve")[-1]["model"] == "gma"


def test_arch_option_and_its_alias():
    from raft_tpu.cli import demo, evaluate, serve, train

    assert train.parse_args(["--arch", "gma"]).arch == "gma"
    for cli, base in ((train, []), (serve, ["--random-init"]),
                      (evaluate, ["--model", "x", "--dataset", "chairs"]),
                      (demo, ["--model", "x"])):
        assert arch_from_args(cli.parse_args(base)) == "full"
        assert arch_from_args(cli.parse_args(base + ["--small"])) == "small"
        assert arch_from_args(cli.parse_args(base + ["--arch", "gma"])) \
            == "gma"
        # the two cannot disagree silently
        with pytest.raises(SystemExit, match="disagree"):
            arch_from_args(cli.parse_args(base + ["--small", "--arch",
                                                  "gma"]))
    assert RAFTConfig.preset("gma").arch == "gma"
    assert RAFTConfig.preset("small").small
    with pytest.raises(ValueError, match="unknown arch"):
        RAFTConfig(arch="large")


def test_a_checkpoint_names_its_model(variables, tmp_path):
    from raft_tpu.cli.evaluate import load_model_variables, variables_arch
    from raft_tpu.train.checkpoint import save_variables

    assert variables_arch(variables) == "gma"
    path = str(tmp_path / "ck")
    save_variables(path, jax.device_get(variables))
    assert variables_arch(load_model_variables(path, "gma")) == "gma"
    with pytest.raises(SystemExit, match="holds a 'gma' model"):
        load_model_variables(path, "full")


def _torch_state_dict(variables, keep_pos_emb=True):
    """The public GMA state dict's names and layouts (OIHW, separate z/r
    gates, ``module.`` prefix), made from a flax tree."""
    from flax.traverse_util import flatten_dict

    sd = {}
    for path, x in flatten_dict(jax.device_get(variables["params"])).items():
        x = np.asarray(x)
        parts = list(path)
        if parts[0] == "refine":
            parts = parts[1:]
        if parts[0] == "upsampler":
            parts = ["update_block", "mask",
                     {"mask_conv1": "0", "mask_conv2": "2"}[parts[2]],
                     parts[3]]
        parts = [q for p in parts for q in
                 (p.split("_") if p.startswith("layer") else [p])]
        leaf = parts[-1]
        if "downsample_conv" in parts:
            i = parts.index("downsample_conv")
            parts = parts[:i] + ["downsample", "0"] + parts[i + 1:]
        if leaf == "kernel":
            x, parts[-1] = x.transpose(3, 2, 0, 1), "weight"
        elif leaf == "scale":
            parts[-1] = "weight"
        parts = [p for p in parts if p not in ("BatchNorm_0", "GroupNorm_0")]
        name = "module." + ".".join(parts)
        if ".gru.convzr" in name:
            z, r = np.split(x, 2, axis=0)
            sd[name.replace("convzr", "convz")] = z
            sd[name.replace("convzr", "convr")] = r
        else:
            sd[name] = x
    for path, x in flatten_dict(jax.device_get(
            variables["batch_stats"])).items():
        parts = [p for p in path if p != "BatchNorm_0"]
        parts = [q for p in parts for q in
                 (p.split("_") if p.startswith("layer") else [p])]
        parts[-1] = {"mean": "running_mean", "var": "running_var"}[parts[-1]]
        sd["module." + ".".join(parts)] = np.asarray(x)
    if keep_pos_emb:
        sd["module.att.pos_emb.rel_height.weight"] = np.zeros((319, 128))
        sd["module.att.pos_emb.rel_width.weight"] = np.zeros((319, 128))
        sd["module.att.pos_emb.rel_ind"] = np.zeros((160, 160), np.int64)
    return sd


def test_convert_maps_the_public_gma_state_dict(variables):
    from raft_tpu import convert

    sd = _torch_state_dict(variables)
    assert "module.att.to_qk.weight" in sd
    assert sd["module.att.to_qk.weight"].shape == (256, 128, 1, 1)
    assert "module.update_block.aggregator.gamma" in sd
    assert "module.update_block.gru.convz1.weight" in sd
    out = convert.convert_state_dict(sd, convert.make_template(CFG))
    a = jax.tree_util.tree_leaves_with_path(out["params"])
    b = dict(jax.tree_util.tree_leaves_with_path(
        jax.device_get(variables["params"])))
    assert len(a) == len(b)
    for path, x in a:
        np.testing.assert_array_equal(np.asarray(x), np.asarray(b[path]))
    # a GMA state dict into a RAFT-full model: refused, the keys named
    with pytest.raises(ValueError, match=r"att\.to_qk\.weight.*"
                       r"aggregator\.gamma|aggregator.*att"):
        convert.convert_state_dict(
            sd, convert.make_template(RAFTConfig.full()))
    # and a RAFT state dict into a GMA model
    raft_sd = {k: v for k, v in sd.items()
               if ".att." not in k and ".aggregator." not in k}
    with pytest.raises(ValueError, match="not a GMA checkpoint"):
        convert.convert_state_dict(raft_sd, convert.make_template(CFG))
