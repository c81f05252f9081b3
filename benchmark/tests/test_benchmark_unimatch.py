"""Tests of what the ``unimatch_flow_s2r6`` configuration adds to the
yardstick: its count of operations (by hand, and beside XLA's own cost
analysis of the step), the readers of its three per-layer metrics on a
hand-built trace summary over the labels the step compiled for a v5e holds,
and one ``--rehearse-tiny`` of ``train_unimatch_chairs`` (a child process
on the CPU: the kind ``train_arch`` end to end, the program against
``reference_unimatch.py`` over the loop's own first three steps).  The cases
marked ``slow`` are the planted fault and the lower-precision control, each
of which has to read ``correct: false``.  Tier-1 collects every case not
marked ``slow`` (``tests/test_benchmark_unit_unimatch.py``).
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_gmflow, flops_unimatch  # noqa: E402
from benchmark.tests.test_benchmark import last_line, rehearse  # noqa: E402

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def um_cfg():
    with open(os.path.join(ROOT,
                           "benchmark/configs/unimatch_flow_s2r6.json")) as f:
        return json.load(f)


def test_unimatch_ops_worked_by_hand():
    cfg = um_cfg()
    H, W, C = 384, 512, 128
    n8, n4 = 48 * 64, 96 * 128
    # a block at 1/4: 8 projections, the FFN 2C -> 8C -> C, two attentions
    # over 64 windows of 192 tokens
    macs = n4 * (8 * C * C + 2 * C * 8 * C + 8 * C * C) \
        + 2 * 2 * 64 * 192 * 192 * C
    assert flops_unimatch.block_ops(cfg, H, W, 1) == 2 * macs
    # at 1/8 the block is gmflow_base's
    with open(os.path.join(ROOT, "benchmark/configs/gmflow_base.json")) as f:
        gm = json.load(f)
    assert flops_unimatch.block_ops(cfg, H, W, 0) \
        == flops_gmflow.block_ops(gm, H, W)
    # global match and propagation at 1/8; local match over 81 and local
    # propagation over 9 neighbours with two projections at 1/4
    assert flops_unimatch.match_ops(cfg, H, W) == (
        flops_gmflow.match_ops(gm, H, W)
        + 2 * n4 * 81 * (C + 2) + 2 * 2 * n4 * C * C + 2 * n4 * 9 * (C + 2))
    # a refinement: the lookup, RAFT's update block with 81 correlation
    # channels, the mask head of 144 and the convex combination x4
    ref = flops_unimatch.refine_ops(cfg, H, W)
    assert ref == (flops.lookup_cost(cfg, 96, 128, 2, 2)[0]
                   + flops.update_ops(cfg, 96, 128)
                   + 2 * n4 * (9 * 128 * 256 + 256 * 144 + 9 * 16 * 2))
    # the backbone: RAFT's stages with the third at 1/4 and a 1x1 for its
    # width, the projection at 1/4, the trident at 1/8 and at 1/4
    full8 = flops.encoder_ops({"small": False}, H, W, C)
    third = 2 * n4 * (9 * 96 * 128 + 9 * 128 * 128 + 96 * 128
                      + 2 * 9 * 128 * 128) + 2 * n4 * 128 * C
    third8 = 2 * n8 * (9 * 96 * 128 + 9 * 128 * 128 + 96 * 128
                       + 2 * 9 * 128 * 128) + 2 * n8 * 128 * C
    assert flops_unimatch.backbone_ops(cfg, H, W) == (
        full8 - third8 + third + 2 * n8 * 9 * C * C + 2 * n4 * 9 * C * C)
    fwd = flops_unimatch.forward_ops(cfg, H, W, 6)
    assert fwd == (2 * flops_unimatch.backbone_ops(cfg, H, W)
                   + 12 * (flops_unimatch.block_ops(cfg, H, W, 0)
                           + flops_unimatch.block_ops(cfg, H, W, 1))
                   + flops_unimatch.match_ops(cfg, H, W)
                   + 2 * n4 * n4 * C + 2 * n4 * C * 256 + 6 * ref)
    assert flops_unimatch.train_ops(cfg, H, W, 6) == 3 * fwd
    # ~815 GFLOP a pair forward, the refinement at 1/4
    # ~53 % of it and the second scale ~23 %
    assert 0.80e12 < fwd < 0.83e12
    assert 0.50 < 6 * ref / fwd < 0.56
    second = (12 * flops_unimatch.block_ops(cfg, H, W, 1)
              + 2 * n4 * 81 * (C + 2) + 2 * 2 * n4 * C * C
              + 2 * n4 * 9 * (C + 2))
    assert 0.21 < second / fwd < 0.25


def test_train_ops_beside_xlas_own_count_of_the_step():
    """XLA's cost analysis of the CPU loss-and-gradient counts what
    ``train_ops`` leaves out on purpose (norms, activations, softmaxes,
    the bilinear upsamplings and the warp, the loss) and what it cannot
    see the same way (the lookup's einsums on the CPU): held between 1.0
    and 2.0 at 64x96, so that a product missing from either count falls
    out."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    H, W = 64, 96
    model = RAFT(RAFTConfig.preset("unimatch", remat=False))
    S = jax.ShapeDtypeStruct
    im = S((1, H, W, 3), jnp.float32)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros(im.shape), jnp.zeros(im.shape),
                             iters=2), jax.random.PRNGKey(0))

    def loss(p, a, b, gt, valid):
        per, _ = model.apply({"params": p}, a, b, iters=6, train=True,
                             loss_targets=(gt, valid, 400.0))
        return jnp.sum(per)

    compiled = jax.jit(jax.grad(loss)).lower(
        shapes["params"], im, im, S((1, H, W, 2), jnp.float32),
        S((1, H, W), jnp.float32)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    ratio = cost["flops"] / flops_unimatch.train_ops(um_cfg(), H, W, 6)
    assert 1.0 < ratio < 2.0, ratio


def metric_spec(name):
    with open(os.path.join(ROOT, "benchmark/metrics", name + ".json")) as f:
        return json.load(f)


# Labels as the trace reducer makes them (``benchmark/trace.label``) of the
# step compiled for a v5e at the cell's size (PERF.md section 5): the
# window kernels at both geometries, the lookup's forward and backward, and
# operations of the refinement and of the rest at the 1/4 map.
WINDOW = {
    "%window_attention_fwd.{} custom-call (bf16[16,2,24,2,32,128], "
    "f32[16,2,2,1,768])": 12,
    "%window_attention_fwd.{} custom-call (bf16[16,8,12,8,16,128], "
    "f32[16,8,8,1,192])": 12,
    "%window_attention_bwd.{} custom-call": 24,
}
LOOKUP = {
    "%gmflow_refine.{} custom-call bf16[8,81,12288]": 6,
    "%gmflow_refine.{} custom-call bf16[8,96,128,12288]": 6,
}
REFINE = {
    "%fusion.{} fusion bf16[8,96,128,384]": 6,
    "%fusion.{} fusion (bf16[8,96,128,192], bf16[8,96,128,192])": 6,
    "%fusion.{} fusion bf16[6,8,96,128,144]": 1,
    "%fusion.{} fusion bf16[5,1,384,256]": 6,
}
OTHER = {
    "%fusion.{} fusion f32[8,96,128,81]": 1,        # the local match
    "%fusion.{} fusion bf16[16,12288,1024]": 12,   # the FFN at 1/4
    "%fusion.{} fusion bf16[16,96,128,128]": 4,    # the backbone at 1/4
    "%fusion.{} fusion f32[8,96,128,128]": 2,      # the warp
}


def summary():
    names, i = {}, 0
    groups = {}
    for group, labels in (("window", WINDOW), ("lookup", LOOKUP),
                          ("refine", REFINE), ("other", OTHER)):
        for pattern, n in labels.items():
            for _ in range(n):
                i += 1
                name = pattern.format(i)
                names[name] = 0.0001 * i
                groups.setdefault(group, []).append(name)
    return {"busy_s": 4.0, "window_s": 4.0, "planes": ["/device:TPU:0"],
            "by_name_s": names, "by_name_n": {k: 1 for k in names}}, groups


def ctx_of(s, cfg=None):
    return {"trace": s, "peaks": PEAKS, "config": cfg or um_cfg(),
            "facts": {"lookup": {"h": 48, "w": 64, "pairs_per_call": 8}}}


def test_window_kernel_roofline_reads_both_geometries():
    from benchmark.readers import window_kernel_roofline

    s, groups = summary()
    spent = sum(s["by_name_s"][k] for k in groups["window"])
    least = 0.0
    for n, windows in ((768, 64), (192, 1024)):
        ops, moved = flops_gmflow.attention_cost(n, windows, 128, 2)
        fwd = max(ops / PEAKS["flops_bf16"],
                  moved / PEAKS["hbm_bytes_per_s"])
        bwd = max(2 * ops / PEAKS["flops_bf16"],
                  1.75 * moved / PEAKS["hbm_bytes_per_s"])
        least += 12 * (fwd + bwd)
    # 768 tokens: bound by operations; 192: by bytes
    assert flops_gmflow.attention_cost(768, 64, 128, 2)[0] \
        / PEAKS["flops_bf16"] > flops_gmflow.attention_cost(
            768, 64, 128, 2)[1] / PEAKS["hbm_bytes_per_s"]
    assert flops_gmflow.attention_cost(192, 1024, 128, 2)[0] \
        / PEAKS["flops_bf16"] < flops_gmflow.attention_cost(
            192, 1024, 128, 2)[1] / PEAKS["hbm_bytes_per_s"]
    got = window_kernel_roofline.read(ctx_of(s), **metric_spec(
        "window_kernel_roofline.train").get("args", {}))
    assert got == pytest.approx(100 * least / spent)
    # nothing to read: nothing, never 0
    assert window_kernel_roofline.read(ctx_of(None)) is None
    bare = dict(s, by_name_s={k: v for k, v in s["by_name_s"].items()
                              if "window" not in k})
    assert window_kernel_roofline.read(ctx_of(bare)) is None


def test_refine_share_and_lookup_roofline_read_the_refinement():
    from benchmark.readers import refine_lookup_roofline, refine_share

    s, groups = summary()
    spent = sum(s["by_name_s"][k] for k in groups["lookup"] + groups["refine"])
    got = refine_share.read(ctx_of(s), **metric_spec(
        "refine_share.train")["args"])
    assert got == pytest.approx(100 * spent / 4.0)
    # the patterns carry no crop and no batch: at another size the
    # refinement's arrays are not matched (the lookup and the weight
    # gradients still are)
    other = dict(ctx_of(s), facts={"lookup": {"h": 46, "w": 62,
                                              "pairs_per_call": 16}})
    look = sum(s["by_name_s"][k] for k in groups["lookup"])
    weights = sum(v for k, v in s["by_name_s"].items() if "[5,1,384" in k)
    assert refine_share.read(other, **metric_spec(
        "refine_share.train")["args"]) == pytest.approx(
            100 * (look + weights) / 4.0)
    assert refine_share.read(dict(ctx_of(s), facts={}), **metric_spec(
        "refine_share.train")["args"]) is None
    # the lookup's least time is counted over the 1/4 map, one level
    cfg = um_cfg()
    spec = metric_spec("refine_lookup_roofline.train")["args"]
    least = 0.0
    for backward, name in ((False, "bf16[8,81,12288]"),
                           (True, "bf16[8,96,128,12288]")):
        ops, nbytes = flops.lookup_cost(cfg, 96, 128, 2, 2,
                                        backward=backward)
        least += 6 * 8 * max(ops / PEAKS["flops_bf16"],
                             nbytes / PEAKS["hbm_bytes_per_s"])
    assert refine_lookup_roofline.read(ctx_of(s), **spec) \
        == pytest.approx(100 * least / look)
    assert refine_lookup_roofline.read(dict(ctx_of(s), facts={}),
                                       **spec) is None
    # corr_lookup_roofline.train reads the RAFT cells' lookups by their
    # scope's name and nothing here
    from benchmark.readers import kernel_roofline

    assert kernel_roofline.read(ctx_of(s), **metric_spec(
        "corr_lookup_roofline.train")["args"]) is None


def test_unimatch_rehearsal_is_correct():
    line = last_line(rehearse("train_unimatch_chairs"))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_pairs_per_s_per_chip", "setup_s"}
    assert "not a measurement" in line["rehearsal"]
    for row in line["check"].values():
        assert row["value"] < row["limit"]


@pytest.mark.slow
def test_unimatch_fault_the_messages_left_out_is_not_correct():
    line = last_line(rehearse("train_unimatch_chairs", "--fault",
                              "no_aggregate"))
    assert line["correct"] is False
    prog = line["info"]["program"]
    assert all(prog[k] < row["limit"] for k, row in line["check"].items())


@pytest.mark.slow
def test_unimatch_control_fp8_is_not_correct():
    line = last_line(rehearse("train_unimatch_chairs", "--reference-quant",
                              "fp8"))
    assert line["correct"] is False
    assert set(line["info"]["controls"]) == {"fp8"}
