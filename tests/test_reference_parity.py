"""``raft_full`` and ``raft_small`` against the plain reference, piece by
piece (tier-1, CPU, small size).

``benchmark/reference.py`` is float32 ``jax.numpy`` that imports nothing of
``raft_tpu``; the weights are ``benchmark/weights.py``'s, seeded.  The chip
holds the whole program to it at the published size (``correct``, PERF.md
section 2); here each piece is held on its own, so that a fault names its
piece: the feature encoder, the correlation pyramid, the lookup at the
configuration's radius, one step of the update block, the upsampling, the
forward flow, and the loss with its first gradient.  ``tests/test_gma.py``
holds ``gma_full`` to ``reference_gma.py`` the same way.
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

from benchmark import reference, weights  # noqa: E402
from raft_tpu.config import RAFTConfig  # noqa: E402
from raft_tpu.models.extractor import BasicEncoder, SmallEncoder  # noqa: E402
from raft_tpu.models.raft import RAFT  # noqa: E402
from raft_tpu.models.update import (BasicUpdateBlock, MaskHead,  # noqa: E402
                                    SmallUpdateBlock)
from raft_tpu.ops.corr import build_corr_pyramid, corr_lookup  # noqa: E402
from raft_tpu.ops.sampler import upflow8  # noqa: E402
from raft_tpu.ops.upsample import convex_upsample  # noqa: E402

# 8 x 10 at 1/8: all four levels hold something (8x10, 4x5, 2x2, 1x1), and
# the third drops an odd edge
H, W = 64, 80
H8, W8 = H // 8, W // 8
# fp32 compute: comparable to the reference
CFGS = {"raft_full": RAFTConfig.full(), "raft_small": RAFTConfig.small_model()}
both = pytest.mark.parametrize("name", sorted(CFGS))


def ref_cfg(name):
    with open(os.path.join(ROOT, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def variables():
    return {name: weights.make_variables(RAFT(cfg), 2147483659)
            for name, cfg in CFGS.items()}


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(7)
    B = 2
    base = rng.uniform(0, 255, (B, H + 8, W + 8, 3)).astype(np.float32)
    image1, image2 = base[:, 4:-4, 4:-4], base[:, 2:-6, 5:-3]
    flow = rng.normal(0, 2, (B, H, W, 2)).astype(np.float32)
    valid = (rng.uniform(size=(B, H, W)) > 0.1).astype(np.float32)
    return {"image1": image1, "image2": image2, "flow": flow, "valid": valid}


@pytest.fixture(scope="module")
def maps():
    """Inputs at 1/8 resolution, as the loop's pieces see them: two feature
    maps, the GRU's state and context, a flow field of a few pixels."""
    rng = np.random.default_rng(11)

    def of(width):
        return jnp.asarray(rng.normal(size=(2, H8, W8, width)), jnp.float32)

    return {"f1": of(32), "f2": of(32), "flow": 3.0 * of(2), "of": of}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@both
def test_feature_encoder_matches_the_reference(name, variables, batch):
    cfg, rc = CFGS[name], ref_cfg(name)
    p = variables[name]["params"]["fnet"]
    x = 2.0 * (batch["image1"] / 255.0) - 1.0
    enc = (SmallEncoder if cfg.small else BasicEncoder)(
        rc["fnet_dim"], rc["fnet_norm"])
    got = jax.jit(lambda p, x: enc.apply({"params": p}, x))(p, x)
    with reference.highest():
        ref = jax.jit(lambda p, x: reference.encoder(
            x, p, None, rc["fnet_norm"], cfg.small, False, None, False))(p, x)
    assert got.shape == ref.shape == (2, H8, W8, rc["fnet_dim"])
    assert rel(got, ref) < 2e-5


@both
def test_corr_pyramid_matches_the_reference(name, maps):
    levels = ref_cfg(name)["corr_levels"]
    assert levels == CFGS[name].corr_levels
    got = build_corr_pyramid(maps["f1"], maps["f2"], levels)
    with reference.highest():
        ref = reference.corr_pyramid(maps["f1"], maps["f2"], levels)
    assert len(got) == len(ref) == levels
    for lvl, (g, r) in enumerate(zip(got, ref)):
        assert g.shape == r.shape == (2, H8 * W8, H8 >> lvl, W8 >> lvl)
        assert rel(g, r) < 1e-6, lvl


@both
def test_corr_lookup_matches_the_reference_at_its_radius(name, maps):
    rc = ref_cfg(name)
    radius = rc["corr_radius"]
    assert radius == CFGS[name].corr_radius
    with reference.highest():
        pyramid = reference.corr_pyramid(maps["f1"], maps["f2"],
                                         rc["corr_levels"])
        # off the integer grid, and past every edge by more than a window
        coords = reference.grid(2, H8, W8) + 2.5 * maps["flow"]
        ref = reference.corr_lookup(pyramid, coords, radius)
    got = corr_lookup(pyramid, coords, radius)
    k = rc["corr_levels"] * (2 * radius + 1) ** 2
    assert got.shape == ref.shape == (2, H8, W8, k)
    assert float(jnp.min(coords)) < -radius
    assert rel(got, ref) < 1e-6


@both
def test_one_update_block_step_matches_the_reference(name, variables, maps):
    cfg, rc = CFGS[name], ref_cfg(name)
    p = variables[name]["params"]["refine"]["update_block"]
    k = rc["corr_levels"] * (2 * rc["corr_radius"] + 1) ** 2
    net = jnp.tanh(maps["of"](rc["hidden_dim"]))
    inp = jax.nn.relu(maps["of"](rc["context_dim"]))
    corr = maps["of"](k)
    block = (SmallUpdateBlock if cfg.small else BasicUpdateBlock)(
        rc["hidden_dim"])
    net2, delta = jax.jit(lambda p, *a: block.apply({"params": p}, *a))(
        p, net, inp, corr, maps["flow"])
    with reference.highest():
        ref_net, ref_delta = jax.jit(lambda p, *a: reference.update_block(
            p, *a, cfg.small, None))(p, net, inp, corr, maps["flow"])
    assert net2.shape == ref_net.shape == net.shape
    assert delta.shape == ref_delta.shape == (2, H8, W8, 2)
    assert rel(net2, ref_net) < 2e-5
    assert rel(delta, ref_delta) < 2e-5


@both
def test_upsampling_matches_the_reference(name, variables, maps):
    """Convex upsampling through the mask head (full), bilinear x8 with
    corners aligned (small)."""
    rc = ref_cfg(name)
    if rc["upsample"] == "bilinear":
        assert CFGS[name].small
        got = upflow8(maps["flow"])
        with reference.highest():
            ref = reference.upflow8(maps["flow"])
    else:
        p = variables[name]["params"]["upsampler"]["mask_head"]
        net = jnp.tanh(maps["of"](rc["hidden_dim"]))
        mask = MaskHead(rc["hidden_dim"]).apply({"params": p}, net)
        got = convex_upsample(maps["flow"], mask)
        with reference.highest():
            ref = reference.convex_upsample(p, net, maps["flow"], None)
    assert got.shape == ref.shape == (2, H, W, 2)
    assert rel(got, ref) < 2e-5


@both
def test_forward_flow_matches_the_reference(name, variables, batch):
    model, v = RAFT(CFGS[name]), variables[name]
    _, flow_up = jax.jit(lambda v, a, b: model.apply(
        v, a, b, iters=3, test_mode=True))(v, batch["image1"],
                                           batch["image2"])
    with reference.highest():
        ref = jax.jit(lambda v, a, b: reference.forward(
            ref_cfg(name), v, a, b, 3))(v, batch["image1"], batch["image2"])
    assert flow_up.shape == ref.shape == (2, H, W, 2)
    # float32 on both sides; what is left is summation order
    assert rel(flow_up, ref) < 2e-4


@both
def test_loss_and_first_gradient_match_the_reference(name, variables, batch):
    iters = 2
    model = RAFT(CFGS[name].replace(scan_unroll=1))
    v = variables[name]
    stats = v.get("batch_stats", {})

    def loss_fn(params):
        (per_iter, _), _ = model.apply(
            {"params": params, "batch_stats": stats}, batch["image1"],
            batch["image2"], iters=iters, train=True,
            loss_targets=(batch["flow"], batch["valid"], 400.0),
            mutable=["batch_stats"])
        w = 0.8 ** (iters - 1.0 - jnp.arange(iters, dtype=jnp.float32))
        return jnp.sum(w * per_iter)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(v["params"])
    with reference.highest():
        ref_loss, ref_grads = reference.make_loss_and_grad(
            ref_cfg(name), iters, block=2)(v, batch)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 1e-4
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat) == len(ref_flat)
    total = rel(np.concatenate([np.ravel(g) for _, g in flat]),
                np.concatenate([np.ravel(ref_flat[p]) for p, _ in flat]))
    assert total < 2e-3
