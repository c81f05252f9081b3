"""Tests of what the ``gma_full`` configuration adds to the yardstick: its
count of operations, the two readers of its per-layer metrics on a
hand-built trace summary, and one ``--rehearse-tiny`` of ``train_gma_chairs``
(a child process on the CPU, 2-3 minutes: the control flow of the kind
``train_arch`` end to end, the program against ``reference_gma.py`` over the
loop's own first three steps).  The cases marked ``slow`` are the planted
fault and the lower-precision control, each of which has to read
``correct: false``.  Run as ``benchmark/tests/test_benchmark.py`` is; tier-1
collects every case not marked ``slow`` (``tests/test_benchmark_unit.py``).
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_gma  # noqa: E402
from benchmark.tests.test_benchmark import last_line, rehearse  # noqa: E402


def gma_cfg():
    with open(os.path.join(ROOT, "benchmark/configs/gma_full.json")) as f:
        return json.load(f)


def test_gma_ops_are_raft_fulls_plus_the_block_worked_by_hand():
    cfg = gma_cfg()
    h, w, n = 46, 62, 46 * 62
    qk = 2 * n * 128 * 256 + 2 * n * n * 128
    av = 2 * n * n * 128
    to_v = 2 * n * 128 * 128
    # six 1x5 / 5x1 convolutions (2 passes x (zr double-width + q)) over
    # 128 more input channels
    gru = 2 * (2 * n * 5 * 128 * 256 + 2 * n * 5 * 128 * 128)
    assert flops_gma.attention_ops(cfg, h, w) == qk
    assert (flops_gma.update_ops(cfg, h, w) - flops.update_ops(cfg, h, w)
            == av + to_v + gru)
    assert (flops_gma.forward_ops(cfg, 368, 496, 12, 12)
            - flops.forward_ops(cfg, 368, 496, 12, 12)
            == qk + 12 * (av + to_v + gru))
    ratio = (flops_gma.train_ops(cfg, 368, 496, 12)
             / flops.train_ops(cfg, 368, 496, 12))
    assert 1.20 < ratio < 1.23


def test_aggregate_cost_is_one_pass_over_an_n_by_n_array_a_product():
    n, lanes = 2852, 16
    ops, nbytes = flops_gma.aggregate_cost(n, lanes, 2)
    assert ops == lanes * 2 * n * n * 128
    assert nbytes == lanes * (n * n + 2 * n * 128) * 2
    # bound by reading A on a v5e: 128 operations a byte of A < 240
    assert ops / 197e12 < nbytes / 819e9


def summary():
    names = {"%fusion.1219 fusion bf16[16,2852,128]": (0.006, 12),
             "%convolution_convert_fusion.24 fusion bf16[16,2852,128]":
             (0.012, 24),
             "%convolution_convert_fusion fusion bf16[16,2852,2852]":
             (0.005, 11),
             "%copy.77 copy bf16[16,2852,128]": (0.002, 36),
             "%divide_convert_fusion fusion bf16[16,2852,2852]": (0.5, 1),
             "%fusion.9 fusion bf16[16,46,62,128]": (0.5, 40)}
    return {"busy_s": 2.0, "window_s": 2.0, "planes": ["/device:TPU:0"],
            "by_name_s": {k: v[0] for k, v in names.items()},
            "by_name_n": {k: v[1] for k, v in names.items()}}


def metric_args(name):
    with open(os.path.join(ROOT, "benchmark/metrics", name + ".json")) as f:
        return json.load(f)["args"]


def test_aggregate_roofline_costs_each_event_by_its_product():
    from benchmark.readers import aggregate_roofline

    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": summary(), "peaks": peaks, "config": gma_cfg(),
           "facts": {"aggregate": {"n": 2852, "pairs_per_call": 16,
                                   "bytes": 2}}}
    args = metric_args("global_aggregate_roofline.train")
    t_one = flops_gma.aggregate_cost(2852, 16, 2)[1] / 819e9
    # 12 + 24 + 11 products; the 36 relayouts add time and no need
    want = 100 * 47 * t_one / (0.006 + 0.012 + 0.005 + 0.002)
    share = aggregate_roofline.read(ctx, **args)
    assert share == pytest.approx(want)
    assert 0 < share < 100
    # a program without the block (the parent, raft_full): nothing, never 0
    assert aggregate_roofline.read(dict(ctx, facts={}), **args) is None
    with open(os.path.join(ROOT, "benchmark/configs/raft_full.json")) as f:
        assert aggregate_roofline.read(dict(ctx, config=json.load(f)),
                                       **args) is None
    assert aggregate_roofline.read(ctx, [{"match": "no_such_op"}]) is None


def test_matched_share_is_matched_seconds_over_busy_seconds():
    from benchmark.readers import matched_share

    ctx = {"trace": summary(),
           "facts": {"aggregate": {"n": 2852, "pairs_per_call": 16,
                                   "bytes": 2}}}
    args = metric_args("global_aggregate_share.train")
    assert matched_share.read(ctx, **args) == pytest.approx(
        100 * (0.006 + 0.012 + 0.005 + 0.002) / 2.0)
    assert matched_share.read(dict(ctx, facts={}), **args) is None
    assert matched_share.read(ctx, ["no_such_op"]) is None


def test_gma_rehearsal_is_correct():
    line = last_line(rehearse("train_gma_chairs"))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_pairs_per_s_per_chip", "setup_s"}
    assert "not a measurement" in line["rehearsal"]
    for row in line["check"].values():
        assert row["value"] < row["limit"]


@pytest.mark.slow
def test_gma_fault_the_aggregate_left_out_is_not_correct():
    line = last_line(rehearse("train_gma_chairs", "--fault",
                              "no_aggregate"))
    assert line["correct"] is False
    prog = line["info"]["program"]
    assert prog["grad_gap"] < line["check"]["grad_gap"]["limit"]


@pytest.mark.slow
def test_gma_control_fp8_is_not_correct():
    line = last_line(rehearse("train_gma_chairs", "--reference-quant",
                              "fp8"))
    assert line["correct"] is False
