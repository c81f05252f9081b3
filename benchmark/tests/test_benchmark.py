"""Tests of the yardstick itself.  Run from the repo root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

The unit cases take seconds.  The cases marked ``slow`` start
``benchmark/run.py --rehearse-tiny`` as a child process on the CPU (the
train rehearsal compiles a RAFT-full step for XLA:CPU, minutes): the last
line's shape, the refusal to measure without a TPU, the planted faults and
the lower-precision (fp8) control, each of which has to come out ``correct: false``.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check, flops, trace  # noqa: E402


def full_cfg():
    with open(os.path.join(ROOT, "benchmark/configs/raft_full.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- reducer

def test_trace_reducer_on_a_hand_built_trace():
    # one device, window 0..1000 ns: a 0-300, b 200-500 (overlaps a),
    # idle 500-800, a 800-900, idle 900-1000 (window given)
    dev = [("a", 0, 300), ("b", 200, 300), ("a", 800, 100)]
    host = [("prepare_batch", 450, 300), ("$dull", 0, 1000),
            ("wait_reply", 880, 200)]
    r = trace.reduce_events(dev, host, window=(0, 1000))
    assert r["busy_s"] == pytest.approx(600e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["by_name_s"]["a"] == pytest.approx(400e-9)
    assert r["by_name_n"] == {"a": 2, "b": 1}
    assert r["device_ops"][0][0] == "a"
    assert r["idle_gaps"][0] == ["prepare_batch", pytest.approx(300e-9)]
    assert r["idle_gaps"][1] == ["wait_reply", pytest.approx(100e-9)]
    secs, names = trace.seconds_matching(r, "^a$")
    assert secs == pytest.approx(400e-9) and names == ["a"]
    assert trace.seconds_matching(r, "no_such_kernel") is None


def test_trace_reducer_without_events_reads_nothing():
    assert trace.reduce_events([]) is None


# ------------------------------------------------------------------ counts

def test_update_block_ops_against_hand_worked_value():
    # RAFT-full, one 1/8-resolution position, multiply-adds by hand:
    # convc1 324*256, convc2 9*256*192, convf1 49*2*128, convf2 9*128*64,
    # conv 9*256*126, two GRU passes of 5*384*(256+128), flow head
    # 9*128*256 + 9*256*2
    macs = (324 * 256 + 9 * 256 * 192 + 49 * 2 * 128 + 9 * 128 * 64
            + 9 * 256 * 126 + 2 * 5 * 384 * 384 + 9 * 128 * 256
            + 9 * 256 * 2)
    assert macs == 2675968
    assert flops.update_ops(full_cfg(), 1, 1) == 2 * macs
    # the all-pairs volume at the chairs crop: 2 * 2852^2 * 256
    assert flops.volume_ops(full_cfg(), 46, 62) == 2 * 2852 ** 2 * 256


def test_forward_ops_chairs_crop_is_about_0_3_tflop_a_pair():
    ops = flops.forward_ops(full_cfg(), 368, 496, 12, 12)
    assert 0.25e12 < ops < 0.35e12
    assert flops.train_ops(full_cfg(), 368, 496, 12) == 3 * ops


def test_lookup_cost_counts_windows_not_the_volume():
    ops, nbytes = flops.lookup_cost(full_cfg(), 46, 62, 2, 2)
    n = 46 * 62
    assert ops == n * 4 * 81 * 8
    assert nbytes == n * 4 * 100 * 2 + n * 4 * 81 * 2 + n * 8
    assert flops.lookup_cost(full_cfg(), 46, 62, 2, 2, backward=True)[1] \
        == nbytes + n * 4 * 100 * 2


def test_kernel_roofline_reads_time_by_name_and_calls_by_lanes():
    from benchmark.readers import kernel_roofline

    cfg = full_cfg()
    ops, nbytes = flops.lookup_cost(cfg, 46, 62, 2, 2)
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    per_pair = max(ops / 197e12, nbytes / 819e9)
    summary = {"by_name_s": {"%refine.1 custom-call bf16[16,324,2944]": 0.02,
                             "%fusion.9 fusion bf16[16,46,62,256]": 0.5},
               "by_name_n": {"%refine.1 custom-call bf16[16,324,2944]": 4,
                             "%fusion.9 fusion bf16[16,46,62,256]": 4}}
    ctx = {"trace": summary, "peaks": peaks, "config": cfg,
           "facts": {"lookup": {"h": 46, "w": 62, "pairs_per_call": 16}}}
    kernels = [{"match": r"custom-call bf16\[(?P<lanes>\d+),\d+,\d+\]$",
                "events_per_call": 1, "backward": False}]
    share = kernel_roofline.read(ctx, kernels)
    assert share == pytest.approx(100 * 4 * 16 * per_pair / 0.02)
    assert 0 < share < 100
    # nothing matched: nothing returned, never 0
    assert kernel_roofline.read(ctx, [{"match": "no_such_kernel"}]) is None


def test_every_metric_file_names_a_reader_and_matches_benchmark_json():
    import importlib

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        with open(os.path.join(ROOT, "benchmark/metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert m["moves"] in e2e
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        assert callable(reader.read)
    for w in bench["workloads"]:
        for part in ("traffic/" + w["traffic"], "limits/" + w["name"]):
            assert os.path.exists(os.path.join(ROOT, "benchmark",
                                               part + ".json"))


# ------------------------------------------------------------- comparison

def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 1e-9, "c": 2.0}
    prog = {"a": 1.1, "b": 3e-9, "c": 2.0}
    gap, leaf = check.worst_leaf_gap(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)
    assert check.dead_leaves(ref) == {"b"}
    # a leaf that did not move reads 1
    assert check.worst_leaf_gap({"a": 0.0, "b": 1e-9, "c": 2.0},
                                ref)[0] == pytest.approx(1.0)


def test_touchy_leaves_are_named_by_the_reference_alone():
    # norms of the reference's first gradient, and of the same reference's
    # on weights rounded to the compute type: "b" moves by 27 % of itself,
    # "d" is dead (its own rule), the others move by under a tenth
    ref = {"a": 1.0, "b": 1.0, "c": 2.0, "d": 1e-9}
    rounded = {"a": 1.03, "b": 0.73, "c": 1.95, "d": 5e-9}
    assert check.touchy_leaves(rounded, ref) == {"b": pytest.approx(0.27)}
    # the worst leaf of the others is what is compared
    prog = {"a": 1.02, "b": 0.77, "c": 2.0, "d": 1e-9}
    assert check.worst_leaf_gap(prog, ref)[1] == "b"
    gap, leaf = check.worst_leaf_gap(prog, ref, skip={"b"})
    assert leaf == "a" and gap == pytest.approx(0.02)
    # a leaf the program left unmoved still reads 1 when another is touchy
    assert check.worst_leaf_gap({**prog, "c": 0.0}, ref,
                                skip={"b"})[0] == pytest.approx(1.0)


def test_tree_diff_and_median_leaf_gap():
    ref = {"a": {"w": [3.0, 4.0]}, "b": [0.0]}
    assert check.tree_diff(ref, ref) == 0.0
    assert check.tree_diff({"a": {"w": [3.0, 4.0]}, "b": [5.0]},
                           ref) == pytest.approx(1.0)
    assert check.median_leaf_gap({"a": 1.1, "b": 1.0, "c": 2.0},
                                 {"a": 1.0, "b": 1.0, "c": 2.0}) == 0.0


def test_judge_fails_on_a_missing_number_or_limit():
    ok, table = check.judge({"x": 0.1}, {"x": 0.2})
    assert ok and table["x"] == {"value": 0.1, "limit": 0.2}
    assert not check.judge({"x": 0.3}, {"x": 0.2})[0]
    assert not check.judge({"x": None}, {"x": 0.2})[0]
    assert check.judge({"x": 0.1, "y": 0.9}, {"x": 0.2})[0]   # y: no limit
    assert not check.judge({"y": 0.1}, {"x": 0.2})[0]
    assert not check.judge({"x": 0.1}, {})[0]
    assert not check.judge({"x": float("nan")}, {"x": 0.2})[0]


# --------------------------------------------------------- the whole run

def rehearse(workload, *extra, tiny=True, seconds="2"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(ROOT, "benchmark/run.py"),
           "--workload", workload, "--seed", "2147483659",
           "--seconds", seconds, "--trace", "0"]
    if tiny:
        cmd.append("--rehearse-tiny")
    return subprocess.run(cmd + list(extra), cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=1500)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_without_a_tpu_the_measured_path_fails_and_prints_no_result():
    proc = rehearse("serve_small_sintel", tiny=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.slow
def test_last_line_shape_at_a_tiny_size():
    line = last_line(rehearse("serve_small_sintel"))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"     # it says where it ran
    assert "not a measurement" in line["rehearsal"]
    assert set(line["metrics"]) == {"serve_pairs_per_s", "setup_s",
                                    "serve_latency_p50_ms",
                                    "serve_latency_p95_ms"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for row in line["check"].values():
        assert row["value"] < row["limit"]


@pytest.mark.slow
def test_serve_fault_an_answer_altered_is_not_correct():
    line = last_line(rehearse("serve_small_sintel", "--fault",
                              "answer_altered"))
    assert line["correct"] is False


@pytest.mark.slow
def test_serve_control_fp8_is_not_correct():
    # the reference in the program's place, one precision below bfloat16
    line = last_line(rehearse("serve_small_sintel", "--reference-quant",
                              "fp8"))
    assert line["correct"] is False
    # the same process read the program, and that reading is sound
    prog = line["info"]["program"]
    assert prog["flow_gap_vs_bf16"] < line["check"]["flow_gap_vs_bf16"][
        "limit"]


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_faults_are_not_correct(fault):
    line = last_line(rehearse("train_full_chairs", "--fault", fault))
    assert line["correct"] is False


@pytest.mark.slow
def test_train_rehearsal_is_correct_and_its_control_is_not():
    line = last_line(rehearse("train_full_chairs"))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_pairs_per_s_per_chip", "setup_s"}
    line = last_line(rehearse("train_full_chairs", "--reference-quant",
                              "fp8"))
    assert line["correct"] is False
