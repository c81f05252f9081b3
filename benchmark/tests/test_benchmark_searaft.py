"""Tests of what the ``searaft_m`` configuration adds to the yardstick: its
count of operations, the two readers of its per-layer metrics on a
hand-built trace summary, and one ``--rehearse-tiny`` of
``train_searaft_chairs`` (a child process on the CPU, minutes: the control
flow of the kind ``train_arch`` end to end, the program against
``reference_searaft.py`` over the loop's own first three steps).  The cases
marked ``slow`` are the planted fault and the lower-precision control, each
of which has to read ``correct: false``.  Run as
``benchmark/tests/test_benchmark.py`` is; tier-1 collects every case not
marked ``slow`` (``tests/test_benchmark_unit_searaft.py``).
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_searaft  # noqa: E402
from benchmark.tests.test_benchmark import last_line, rehearse  # noqa: E402


def sea_cfg():
    with open(os.path.join(ROOT, "benchmark/configs/searaft_m.json")) as f:
        return json.load(f)


def test_searaft_ops_worked_by_hand():
    cfg = sea_cfg()
    # one ResNet pass over a 368x496 image, multiply-adds by hand: the stem
    # and 6 convolutions of 64 channels at 184x248; at 92x124 the entry
    # block (64 -> 128, its 1x1 shortcut) and 6 more of 128; at 46x62 the
    # entry block (128 -> 256) and 10 more of 256, then 1x1 256 -> 256
    n2, n4, n8 = 184 * 248, 92 * 124, 46 * 62
    macs = (n2 * (49 * 3 * 64 + 6 * 9 * 64 * 64)
            + n4 * (9 * 64 * 128 + 64 * 128 + 7 * 9 * 128 * 128)
            + n8 * (9 * 128 * 256 + 128 * 256 + 11 * 9 * 256 * 256
                    + 256 * 256))
    assert flops_searaft.resnet_ops(368, 496, 3, 256) == 2 * macs
    assert (flops_searaft.resnet_ops(368, 496, 6, 256) - 2 * macs
            == 2 * n2 * 49 * 3 * 64)
    # one ConvNeXt block at one position: 49 taps over 384 channels,
    # 384 -> 512 -> 384, 384 -> 128
    assert flops_searaft.convnext_ops(cfg, 1, 1) == 2 * (
        49 * 384 + 384 * 512 + 512 * 384 + 384 * 128)
    # an iteration: RAFT-full's motion encoder, two blocks, the 6-channel
    # head (RAFT-full's iteration less its GRU and 2-channel head)
    motion = 324 * 256 + 9 * 256 * 192 + 49 * 2 * 128 + 9 * 128 * 64 \
        + 9 * 256 * 126
    head = 9 * 128 * 256 + 9 * 256 * 6
    assert flops_searaft.update_ops(cfg, 1, 1) == 2 * motion + 2 * head \
        + 2 * flops_searaft.convnext_ops(cfg, 1, 1)
    # 4 iterations train 5 predictions
    fwd = flops_searaft.forward_ops(cfg, 368, 496, 4, 5)
    assert flops_searaft.train_ops(cfg, 368, 496, 4) == 3 * fwd
    assert 0.32e12 < fwd < 0.34e12
    enc = 2 * flops_searaft.resnet_ops(368, 496, 3, 256) \
        + flops_searaft.resnet_ops(368, 496, 6, 256)
    assert 0.77 < enc / fwd < 0.80                  # a step of encoders
    with open(os.path.join(ROOT, "benchmark/configs/raft_full.json")) as f:
        full = flops.train_ops(json.load(f), 368, 496, 12)
    assert 1.10 < 3 * fwd / full < 1.14


def test_dwconv_cost_is_two_passes_over_the_block_input():
    ops, nbytes = flops_searaft.dwconv_cost(46, 62, 16, 384, 2)
    n = 16 * 46 * 62 * 384
    assert ops == 2 * 49 * n and nbytes == 2 * n * 2
    # bound by bytes on a v5e: 24.5 operations a byte < 240
    assert ops / 197e12 < nbytes / 819e9


def metric_args(name):
    with open(os.path.join(ROOT, "benchmark/metrics", name + ".json")) as f:
        return json.load(f)["args"]


def summary():
    """Labels as the traced run of the cell on a v5e printed them (PERF.md
    section 5), with made-up seconds; the last three are not the depthwise
    convolution's."""
    with open(os.path.join(ROOT, "benchmark/tests",
                           "searaft_trace_labels.json")) as f:
        labels = json.load(f)
    names = {k: (0.001 * (i + 1), n) for i, (k, n) in
             enumerate(labels["dwconv"].items())}
    names.update({k: (0.5, n) for k, n in labels["other"].items()})
    return {"busy_s": 2.0, "window_s": 2.0, "planes": ["/device:TPU:0"],
            "by_name_s": {k: v[0] for k, v in names.items()},
            "by_name_n": {k: v[1] for k, v in names.items()}}, labels


def test_dwconv_roofline_and_share_read_the_depthwise_products():
    from benchmark.readers import dwconv_roofline, matched_share

    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    s, labels = summary()
    ctx = {"trace": s, "peaks": peaks, "config": sea_cfg(),
           "facts": {"lookup": {"h": 46, "w": 62, "pairs_per_call": 16}}}
    spent = sum(s["by_name_s"][k] for k in labels["dwconv"])
    products = sum(n for k, n in labels["dwconv"].items()
                   if k in labels["products"])
    t_one = flops_searaft.dwconv_cost(46, 62, 16, 384, 2)[1] / 819e9
    share = dwconv_roofline.read(
        ctx, **metric_args("convnext_dwconv_roofline.train"))
    assert share == pytest.approx(100 * products * t_one / spent)
    assert 0 < share
    assert matched_share.read(
        ctx, **metric_args("convnext_dwconv_share.train")) \
        == pytest.approx(100 * spent / 2.0)
    # nothing to read (no trace, no such operation; a configuration that
    # counts no depthwise convolution): nothing, never 0
    args = metric_args("convnext_dwconv_roofline.train")
    assert dwconv_roofline.read(dict(ctx, trace=None), **args) is None
    assert dwconv_roofline.read(dict(ctx, facts={}), **args) is None
    assert dwconv_roofline.read(ctx, [{"match": "no_such_op"}], 384) is None
    assert matched_share.read(ctx, ["no_such_op"]) is None
    with open(os.path.join(ROOT, "benchmark/configs/raft_full.json")) as f:
        assert dwconv_roofline.read(dict(ctx, config=json.load(f)),
                                    **args) is None


def test_searaft_rehearsal_is_correct():
    line = last_line(rehearse("train_searaft_chairs"))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_pairs_per_s_per_chip", "setup_s"}
    assert "not a measurement" in line["rehearsal"]
    for row in line["check"].values():
        assert row["value"] < row["limit"]


@pytest.mark.slow
def test_searaft_fault_the_branch_left_out_is_not_correct():
    line = last_line(rehearse("train_searaft_chairs", "--fault",
                              "no_aggregate"))
    assert line["correct"] is False
    prog = line["info"]["program"]
    assert prog["grad_gap_median"] \
        < line["check"]["grad_gap_median"]["limit"]


@pytest.mark.slow
def test_searaft_control_fp8_is_not_correct():
    line = last_line(rehearse("train_searaft_chairs", "--reference-quant",
                              "fp8"))
    assert line["correct"] is False
