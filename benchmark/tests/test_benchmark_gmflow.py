"""Tests of what the ``gmflow_base`` configuration adds to the yardstick: its
count of operations (by hand, and beside XLA's own cost analysis of the
step), the readers of its three per-layer metrics on a hand-built trace
summary over labels a chip run printed, and one ``--rehearse-tiny`` of
``train_gmflow_chairs`` (a child process on the CPU: the control flow of the
kind ``train_arch`` end to end, the program against ``reference_gmflow.py``
over the loop's own first three steps).  The cases marked ``slow`` are the
planted fault and the lower-precision control, each of which has to read
``correct: false``.  Run as ``benchmark/tests/test_benchmark.py`` is; tier-1
collects every case not marked ``slow``
(``tests/test_benchmark_unit_gmflow.py``).
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_gmflow  # noqa: E402
from benchmark.tests.test_benchmark import last_line, rehearse  # noqa: E402

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def gm_cfg():
    with open(os.path.join(ROOT, "benchmark/configs/gmflow_base.json")) as f:
        return json.load(f)


def test_gmflow_ops_worked_by_hand():
    cfg = gm_cfg()
    H, W = 384, 512
    N, n, C = 48 * 64, 24 * 32, 128
    # one window attention over one map: q k^T and P v in 4 windows of 768
    assert flops_gmflow.attention_ops(cfg, H, W) == 4 * (2 * 2 * n * n * C)
    # a block over one map, multiply-adds by hand: 8 projections of C x C,
    # W1 2C -> 8C, W2 8C -> C, two attentions
    macs = N * (8 * C * C + 2 * C * 8 * C + 8 * C * C) + 2 * 4 * 2 * n * n * C
    assert flops_gmflow.block_ops(cfg, H, W) == 2 * macs
    # matching F1 F2^T and P G; propagation q k^T, P flow, two projections
    assert flops_gmflow.match_ops(cfg, H, W) == 2 * (
        2 * (N * N * C + 2 * N * N) + 2 * N * C * C)
    assert flops_gmflow.upsample_ops(cfg, H, W) == 2 * N * (
        9 * 130 * 256 + 256 * 576 + 9 * 64 * 2)
    fwd = flops_gmflow.forward_ops(cfg, H, W)
    assert fwd == (2 * flops.encoder_ops({"small": False}, H, W, 128)
                   + 12 * flops_gmflow.block_ops(cfg, H, W)
                   + flops_gmflow.match_ops(cfg, H, W)
                   + flops_gmflow.upsample_ops(cfg, H, W))
    # no iterations: whatever the kind hands in is not read
    assert flops_gmflow.train_ops(cfg, H, W, 12) == 3 * fwd \
        == flops_gmflow.train_ops(cfg, H, W, 0)
    assert 0.125e12 < fwd < 0.132e12
    transformer = 12 * flops_gmflow.block_ops(cfg, H, W)
    assert 0.51 < transformer / fwd < 0.54
    assert 0.55 < (transformer + flops_gmflow.match_ops(cfg, H, W)) / fwd
    with open(os.path.join(ROOT, "benchmark/configs/raft_full.json")) as f:
        full = flops.train_ops(json.load(f), 368, 496, 12)
    assert 0.42 < 3 * fwd / full < 0.46


def test_attention_and_match_cost_are_the_least_of_the_mathematics():
    ops, nbytes = flops_gmflow.attention_cost(768, 128, 128, 2)
    assert ops == 128 * 4 * 768 * 768 * 128
    assert nbytes == 128 * 4 * 768 * 128 * 2       # q, k, v in, result out
    # ~384 operations a byte: over the v5e's ~240, operations bound it
    assert ops / PEAKS["flops_bf16"] > nbytes / PEAKS["hbm_bytes_per_s"]
    ops, nbytes = flops_gmflow.match_cost(3072, 16, 128, 4)
    assert ops == 16 * (2 * 3072 ** 2 * 128 + 4 * 3072 ** 2)
    assert nbytes == 16 * (2 * 3072 * 128 + 2 * 3072) * 4


def test_train_ops_beside_xlas_own_count_of_the_step():
    """XLA's cost analysis of the CPU loss-and-gradient, nothing rebuilt,
    counts what ``train_ops`` leaves out on purpose (norms, activations,
    softmaxes, the bilinear and convex upsampling's elementwise work, the
    loss): it read 1.28x and 1.30x ``train_ops`` at 64x96 and 128x192 (and
    7.73 TFLOP a step, 1.25x, compiled for a v5e at the cell's size).  Held
    between 1.1 and 1.5: a product missing from either count falls out."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    H, W = 64, 96
    model = RAFT(RAFTConfig.preset("gmflow", remat=False))
    S = jax.ShapeDtypeStruct
    im = S((1, H, W, 3), jnp.float32)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros(im.shape), jnp.zeros(im.shape)),
        jax.random.PRNGKey(0))

    def loss(p, a, b, gt, valid):
        per, _ = model.apply({"params": p}, a, b, train=True,
                             loss_targets=(gt, valid, 400.0))
        return 0.9 * per[0] + per[1]

    compiled = jax.jit(jax.grad(loss)).lower(
        shapes["params"], im, im, S((1, H, W, 2), jnp.float32),
        S((1, H, W), jnp.float32)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    ratio = cost["flops"] / flops_gmflow.train_ops(gm_cfg(), H, W)
    assert 1.1 < ratio < 1.5, ratio


def metric_spec(name):
    with open(os.path.join(ROOT, "benchmark/metrics", name + ".json")) as f:
        return json.load(f)


def summary():
    """Labels as the traced run of the cell on a v5e printed them (PERF.md
    section 5), with made-up seconds."""
    with open(os.path.join(ROOT, "benchmark/tests",
                           "gmflow_trace_labels.json")) as f:
        labels = json.load(f)
    names, i = {}, 0
    for group in ("attention", "match", "other"):
        for k, n in labels[group].items():
            i += 1
            names[k] = (0.001 * i, n)
    return {"busy_s": 2.0, "window_s": 2.0, "planes": ["/device:TPU:0"],
            "by_name_s": {k: v[0] for k, v in names.items()},
            "by_name_n": {k: v[1] for k, v in names.items()}}, labels


def test_the_three_readers_match_what_their_what_says_and_nothing_else():
    import re

    from benchmark.readers import attention_roofline, attention_share

    s, labels = summary()
    ctx = {"trace": s, "peaks": PEAKS, "config": gm_cfg(),
           "facts": {"lookup": {"h": 48, "w": 64, "pairs_per_call": 16},
                     "aggregate": {"n": 3072, "pairs_per_call": 16,
                                   "bytes": 2}}}
    roof = metric_spec("window_attention_roofline.train")
    spent = sum(s["by_name_s"][k] for k in labels["attention"])
    t_product = flops_gmflow.attention_cost(768, 128, 128, 2)[0] \
        / PEAKS["flops_bf16"] / 2
    products = 0.0
    for k, n in labels["attention"].items():
        hit = [e for e in roof["args"]["kernels"] if re.search(
            attention_roofline.filled(e["match"], {"n": 768, "b": 128}), k)]
        assert len(hit) == 1, k             # one entry a label
        products += n * hit[0].get("products", 1)
    # a step is 12 attentions: 2 products forward, 4 backward (72), the
    # scores rebuilt once more on the way back (12 events of 0 products)
    assert products == 72 * labels["steps"]
    share = attention_roofline.read(ctx, **roof["args"])
    assert share == pytest.approx(100 * products * t_product / spent)
    assert 0 < share
    assert attention_share.read(
        ctx, **metric_spec("window_attention_share.train")["args"]) \
        == pytest.approx(100 * spent / 2.0)
    match_spent = sum(s["by_name_s"][k] for k in labels["match"])
    assert attention_share.read(
        ctx, **metric_spec("global_match_share.train")["args"]) \
        == pytest.approx(100 * match_spent / 2.0)
    # the patterns carry no crop and no batch: at another size none matches
    other = dict(ctx, facts={"lookup": {"h": 46, "w": 62,
                                        "pairs_per_call": 8}})
    for name in ("window_attention_share.train", "global_match_share.train"):
        assert attention_share.read(
            other, **metric_spec(name)["args"]) is None
        assert attention_share.read(
            dict(ctx, facts={}), **metric_spec(name)["args"]) is None
    # nothing to read (no trace, a program without the facts, no such
    # operation, a configuration that counts no attention): nothing, never 0
    args = roof["args"]
    assert attention_roofline.read(dict(ctx, trace=None), **args) is None
    assert attention_roofline.read(dict(ctx, facts={}), **args) is None
    assert attention_roofline.read(ctx, [{"match": "no_such_op"}]) is None
    with open(os.path.join(ROOT, "benchmark/configs/searaft_m.json")) as f:
        assert attention_roofline.read(dict(ctx, config=json.load(f)),
                                       **args) is None


def test_gmflow_rehearsal_is_correct():
    line = last_line(rehearse("train_gmflow_chairs"))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_pairs_per_s_per_chip", "setup_s"}
    assert "not a measurement" in line["rehearsal"]
    for row in line["check"].values():
        assert row["value"] < row["limit"]


@pytest.mark.slow
def test_gmflow_fault_the_messages_left_out_is_not_correct():
    line = last_line(rehearse("train_gmflow_chairs", "--fault",
                              "no_aggregate"))
    assert line["correct"] is False
    prog = line["info"]["program"]
    assert all(prog[k] < row["limit"] for k, row in line["check"].items())


@pytest.mark.slow
def test_gmflow_control_fp8_is_not_correct():
    line = last_line(rehearse("train_gmflow_chairs", "--reference-quant",
                              "fp8,bf16_scores,bf16_corr"))
    assert line["correct"] is False
    assert set(line["info"]["controls"]) == {"fp8", "bf16_scores",
                                             "bf16_corr"}
