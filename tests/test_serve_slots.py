"""Continuous-batching (slot-mode) serving tests (tier-1).

The contracts pinned here are the PR-11 acceptance criteria:

- **Bitwise parity**: with early exit off, a full slot batch of
  requests returns bit-identical flows to the request-mode engine —
  structural, because BOTH modes drive the same compiled
  ``encode``/``iter_step`` program pair (serve/slots.py docstring).
- **Compile ledger**: slot mode compiles exactly one ``enc`` + one
  ``iter`` program per ``(bucket, slots)``.
- **Join/leave determinism**: requests admitted into a pool whose
  other lanes are mid-flight (or freshly reset) produce the same bits
  as requests admitted any other way — lane math is masked and
  per-lane independent, and a re-run of the same arrival pattern is
  bit-identical.
- **Early-exit monotonicity**: a looser (larger) threshold never
  increases any lane's ``iters_used``; threshold 0 reproduces the full
  budget bitwise.
- **Chaos**: an injected transient ``device_err`` mid-iteration is
  retried to a bit-identical result; with retries off it fails the
  active lanes only — waiting requests are served from a reset pool
  with unchanged bits.

Small model, fp32, tiny shapes — compiles stay in the fast tier.
"""

import numpy as np
import pytest

from raft_tpu import chaos
from raft_tpu.chaos import FaultPlan, InjectedDeviceError
from raft_tpu.config import RAFTConfig
from raft_tpu.serve import InferenceEngine, ServeConfig

CFG = RAFTConfig.small_model()  # fp32 compute: bit-comparable
ITERS = 3
SHAPE = (36, 52)  # -> bucket (40, 56)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Chaos is process-global state: never leak a plan across tests."""
    chaos.uninstall()
    yield
    chaos.uninstall()


class _RecordingSink:
    """EventSink stand-in: collects (event, fields) for assertions."""

    def __init__(self):
        self.events = []

    def emit(self, event, step=None, **fields):
        self.events.append((event, fields))

    def of(self, event):
        return [f for e, f in self.events if e == event]


def _images(rng, hw=SHAPE):
    h, w = hw
    return (rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
            rng.uniform(0, 255, (h, w, 3)).astype(np.float32))


@pytest.fixture(scope="module")
def variables():
    import jax

    from raft_tpu.models.raft import RAFT

    img = jax.numpy.zeros((1, 40, 56, 3))
    rng = jax.random.PRNGKey(0)
    return RAFT(CFG).init({"params": rng, "dropout": rng},
                          img, img, iters=1)


@pytest.fixture(scope="module")
def request_flows(variables):
    """The parity oracle: four seeded frame pairs served by the
    request-mode engine (one compile pair at (40,56)x4 lanes)."""
    rng = np.random.default_rng(11)
    pairs = [_images(rng) for _ in range(4)]
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, max_batch=4, batch_sizes=(4,), max_wait_ms=15))
    with eng:
        futs = [eng.submit(a, b) for a, b in pairs]
        flows = [f.result(timeout=120) for f in futs]
    return pairs, flows


def test_slot_parity_bitwise_and_compile_ledger(variables,
                                                request_flows):
    """Early exit off + a full slot batch: every slot-mode flow is
    BIT-identical to the request-mode engine's, and the ledger shows
    exactly one encode + one iter_step compile for (bucket, slots)."""
    pairs, ref = request_flows
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, batching="slot", slots=4, max_wait_ms=15))
    with eng:
        futs = [eng.submit(a, b) for a, b in pairs]
        got = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)
    counts = eng.compile_counter.counts()
    assert counts == {((40, 56), 4, "enc"): 1,
                      ((40, 56), 4, "iter"): 1}, counts
    assert stats["batching"] == "slot"
    assert stats["completed"] == 4
    # every lane ran the full budget (threshold 0 disables early exit)
    assert stats["iters_used"]["p50"] == float(ITERS)
    assert stats["iters_used"]["count_total"] == 4
    assert stats["slot_steps"] >= ITERS
    assert 0 < stats["occupancy"] <= 1.0


def test_slot_join_leave_determinism(variables, request_flows):
    """Seeded staggered arrivals: a request admitted while other lanes
    are mid-flight (and one admitted into a drained pool) still returns
    the request-mode bits, and a re-run of the same arrival pattern is
    bit-identical run-to-run."""
    pairs, ref = request_flows

    def staggered_run():
        eng = InferenceEngine(variables, CFG, ServeConfig(
            iters=ITERS, batching="slot", slots=4))
        with eng:
            # r0 alone: admitted into a fresh pool, runs to retirement
            f0 = eng.submit(*pairs[0])
            r0 = f0.result(timeout=120)
            # r1 then r2/r3: r1 is likely mid-flight when r2/r3 join
            f1 = eng.submit(*pairs[1])
            f2 = eng.submit(*pairs[2])
            f3 = eng.submit(*pairs[3])
            rest = [f.result(timeout=120) for f in (f1, f2, f3)]
        return [r0] + rest

    a = staggered_run()
    b = staggered_run()
    for got_a, got_b, r in zip(a, b, ref):
        np.testing.assert_array_equal(got_a, got_b)  # run-to-run
        np.testing.assert_array_equal(got_a, r)      # vs the oracle


def test_early_exit_monotonic_iters_and_bounded_delta(variables):
    """EarlyExitRunner (the offline measurement arm): ascending
    thresholds never increase any lane's iters_used; threshold 0
    reproduces the full-budget baseline bitwise; every arm's EPE delta
    vs that baseline is finite."""
    from raft_tpu.serve.slots import EarlyExitRunner

    rng = np.random.default_rng(3)
    im1 = np.stack([_images(rng, (40, 56))[0] for _ in range(2)])
    im2 = np.stack([_images(rng, (40, 56))[0] for _ in range(2)])
    runner = EarlyExitRunner(CFG)
    iters = 6

    base, base_used = runner.run(variables, im1, im2, iters,
                                 threshold=0.0)
    assert base_used.tolist() == [iters, iters]

    prev_used = None
    for thr in (0.0, 0.01, 0.3, 1e9):
        flow, used = runner.run(variables, im1, im2, iters,
                                threshold=thr)
        assert np.isfinite(flow).all()
        assert ((1 <= used) & (used <= iters)).all()
        if thr == 0.0:
            np.testing.assert_array_equal(flow, base)  # bitwise
        if prev_used is not None:  # looser cut, per-lane monotone
            assert (used <= prev_used).all(), (thr, used, prev_used)
        prev_used = used
        epe_delta = float(np.mean(np.sqrt(
            ((flow - base) ** 2).sum(-1))))
        assert np.isfinite(epe_delta)
    # an absurdly loose threshold retires every lane on iteration 1
    assert prev_used.tolist() == [1, 1]


def test_slot_per_request_budget_and_convergence_retire(variables):
    """Per-request ``iters`` budgets are honored (capped at cfg.iters)
    and the convergence predicate retires lanes with the telemetry to
    prove it: ``serve_retire`` carries iters + converged."""
    rng = np.random.default_rng(5)
    im1, im2 = _images(rng)

    sink = _RecordingSink()
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, batching="slot", slots=2), sink=sink)
    with eng:
        with pytest.raises(ValueError, match="iters"):
            eng.submit(im1, im2, iters=0)
        assert eng.infer(im1, im2, iters=1, timeout=120).shape \
            == SHAPE + (2,)
        # over-budget asks are capped at cfg.iters, not rejected
        assert eng.infer(im1, im2, iters=99, timeout=120).shape \
            == SHAPE + (2,)
    retired = sink.of("serve_retire")
    assert [r["iters"] for r in retired] == [1, ITERS]
    assert all(r["converged"] is False for r in retired)

    # an absurdly loose threshold: every request converges on iter 1
    sink2 = _RecordingSink()
    eng2 = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, batching="slot", slots=2,
        early_exit_threshold=1e9), sink=sink2)
    with eng2:
        flow = eng2.infer(im1, im2, timeout=120)
    assert flow.shape == SHAPE + (2,) and np.isfinite(flow).all()
    (ev,) = sink2.of("serve_retire")
    assert ev["iters"] == 1 and ev["converged"] is True
    assert eng2.stats()["iters_used"]["p50"] == 1.0


def test_chaos_device_err_mid_iteration_retried_bit_identical(
        variables):
    """An injected transient device error on an iter_step mid-request
    is retried and the result is BIT-identical to the clean run — the
    programs are pure, so a failed attempt never corrupts the
    device-resident slot state."""
    rng = np.random.default_rng(6)
    im1, im2 = _images(rng)
    sink = _RecordingSink()
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, batching="slot", slots=1, device_retries=1,
        retry_backoff_s=0.0, retry_jitter=0.0), sink=sink)
    with eng:
        clean = eng.infer(im1, im2, timeout=120)   # cycles 1..3
        # fire on cycle 5 = the second request's SECOND iteration
        chaos.install(FaultPlan.parse("device_err@batch=5"))
        faulted = eng.infer(im1, im2, timeout=120)  # cycles 4..6
        chaos.uninstall()
        stats = eng.stats()
    np.testing.assert_array_equal(clean, faulted)
    assert stats["retries"] == 1 and stats["completed"] == 2
    assert stats["failed_lanes"] == 0
    (ev,) = sink.of("serve_retry")
    assert ev["attempt"] == 1


def test_chaos_device_err_exhausted_fails_actives_not_waiters(
        variables):
    """Retries off: the injected fault fails the ACTIVE lane with the
    device error, while a waiting request is served afterwards from
    the reset pool — bit-identical to an undisturbed run."""
    rng = np.random.default_rng(7)
    im1, im2 = _images(rng)
    sink = _RecordingSink()
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, batching="slot", slots=1, device_retries=0),
        sink=sink)
    with eng:
        clean = eng.infer(im1, im2, timeout=120)   # cycles 1..3
        chaos.install(FaultPlan.parse("device_err@batch=5"))
        doomed = eng.submit(im1, im2)              # admitted cycle 4
        survivor = eng.submit(im1, im2)            # waits (1 slot)
        with pytest.raises(InjectedDeviceError):
            doomed.result(timeout=120)
        out = survivor.result(timeout=120)
        chaos.uninstall()
        stats = eng.stats()
    np.testing.assert_array_equal(clean, out)
    assert stats["failed_lanes"] == 1 and stats["errors"] == 1
    assert stats["completed"] == 2
    assert len(sink.of("serve_iter_error")) == 1


class _SynthDataset:
    """Three fixed-resolution pairs with a known GT flow, standing in
    for FlyingChairs via the ``EARLY_EXIT_DATASETS`` seam."""

    def __init__(self, n=3, seed=21):
        rng = np.random.default_rng(seed)
        h, w = SHAPE
        self.samples = [
            {"image1": rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
             "image2": rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
             "flow": rng.normal(0, 2, (h, w, 2)).astype(np.float32)}
            for _ in range(n)
        ]

    def __len__(self):
        return len(self.samples)

    def load(self, i):
        return self.samples[i]


def test_evaluate_early_exit_delta_record(variables, monkeypatch):
    """The eval-side accuracy gate: baseline arm pins delta 0 and full
    iters; a huge threshold retires every lane at iteration 1; the
    record is JSON-shaped for check_regression."""
    from raft_tpu import evaluate

    monkeypatch.setitem(evaluate.EARLY_EXIT_DATASETS, "chairs",
                        lambda **kw: _SynthDataset())
    rec = evaluate.evaluate_early_exit_delta(
        variables, CFG, [0.01, 1e9], dataset="chairs", iters=ITERS,
        batch_size=2, bucket=False)
    assert rec["dataset"] == "chairs" and rec["iters"] == ITERS
    assert rec["thresholds"] == ["0", "0.01", "1e+09"]
    base = rec["per_threshold"]["0"]
    assert base["epe_delta"] == 0.0
    assert base["iters_p50"] == float(ITERS)
    for arm in rec["per_threshold"].values():
        assert set(arm) == {"epe", "epe_delta", "iters_mean",
                            "iters_p50", "iters_p95",
                            "residual_mean", "residual_p50"}
        assert np.isfinite(arm["epe"])
        # Retirement residual: delta_max is a max of norms, so any lane
        # that ran >= 1 iteration carries a value >= 0 (never the -1
        # "untouched" sentinel).
        assert arm["residual_mean"] >= 0.0
        assert arm["residual_p50"] >= 0.0
    # Monotone: larger threshold can only retire earlier.
    p50s = [rec["per_threshold"][k]["iters_p50"]
            for k in rec["thresholds"]]
    assert p50s == sorted(p50s, reverse=True)
    assert rec["per_threshold"]["1e+09"]["iters_p50"] == 1.0
    assert set(rec["delta_vs_full"]) == {"0.01", "1e+09"}
    with pytest.raises(ValueError):
        evaluate.evaluate_early_exit_delta(variables, CFG, [],
                                           dataset="chairs")
    with pytest.raises(ValueError):
        evaluate.evaluate_early_exit_delta(variables, CFG, [-0.1],
                                           dataset="chairs")
    with pytest.raises(ValueError):
        evaluate.evaluate_early_exit_delta(variables, CFG, [0.1],
                                           dataset="nope")


def test_cli_early_exit_threshold_flag():
    from raft_tpu.cli import evaluate as cli

    args = cli.parse_args(["--model", "m", "--dataset", "chairs",
                           "--early_exit_threshold", "0.05, 0.2"])
    assert args.early_exit_threshold == [0.05, 0.2]
    for bad in ["", "a,b", "-0.1", "0.1,,-2"]:
        with pytest.raises(SystemExit):
            cli.parse_args(["--model", "m", "--dataset", "chairs",
                            "--early_exit_threshold", bad])


def test_bench_serve_workload_and_preset():
    """bench_serve's mixed-difficulty workload is seed-deterministic
    (both batching arms replay identical requests) and the tiny preset
    saturates the closed loop (concurrency > slots, --batching both)."""
    import importlib.util
    import os.path as osp

    repo = osp.dirname(osp.dirname(osp.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_serve", osp.join(repo, "scripts", "bench_serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    args = mod.parse_args(["--tiny"])
    assert args.batching == "both"
    assert args.concurrency > args.slots  # queueing regime, not vacuous
    assert args.iters == 3

    mk = lambda: mod._make_workload([(64, 96), (36, 52)], 10, 3, 0.5,
                                    np.random.default_rng(7))
    w1, w2 = mk(), mk()
    assert len(w1) == 10
    for (a1, b1, i1), (a2, b2, i2) in zip(w1, w2):
        assert i1 == i2
        np.testing.assert_array_equal(a1, a2)
        np.testing.assert_array_equal(b1, b2)
    iters = [i for _, _, i in w1]
    assert any(i < 3 for i in iters) and any(i == 3 for i in iters)
    assert all(1 <= i <= 3 for i in iters)

    with pytest.raises(SystemExit):  # slot-mode fleets are future work
        mod.parse_args(["--batching", "slot", "--replicas", "2"])
    with pytest.raises(SystemExit):
        mod.parse_args(["--easy-frac", "1.5"])


def test_slot_parity_with_fused_gru(variables, request_flows):
    """``fused_gru=True`` (interpret-mode Pallas gate chains) through
    the slot engine matches the unfused request-mode oracle to float
    tolerance — the fused kernel slots into serve's compiled
    ``encode``/``iter_step`` pieces without touching the batching,
    masking, or lane-independence contracts (PR-13 acceptance)."""
    pairs, ref = request_flows
    cfg = CFG.replace(fused_gru=True, pallas_offtpu="interpret")
    assert cfg.resolved_fused_gru is True
    eng = InferenceEngine(variables, cfg, ServeConfig(
        iters=ITERS, batching="slot", slots=4, max_wait_ms=15))
    with eng:
        futs = [eng.submit(a, b) for a, b in pairs]
        got = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    for r, g in zip(ref, got):
        np.testing.assert_allclose(r, g, rtol=1e-5, atol=1e-5)
    assert stats["batching"] == "slot" and stats["completed"] == 4


# ---------------------------------------------------------------------------
# The iteration program's runtime step count (PR 29)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stepped(variables):
    """One jitted ``encode_admit`` / ``iter_step`` pair over three lanes
    and the admitted state to start from: per-lane budgets that differ
    (1, ITERS, 2), so lanes retire at different steps of one call."""
    import jax

    from raft_tpu.serve import slots

    rng = np.random.default_rng(29)
    im1 = np.stack([_images(rng, (40, 56))[0] for _ in range(3)])
    im2 = np.stack([_images(rng, (40, 56))[0] for _ in range(3)])
    it = jax.jit(slots.make_iter_fn(CFG))
    state = jax.jit(slots.make_encode_fn(CFG))(
        variables, im1, im2,
        slots.state_template(CFG, variables, 3, (40, 56)),
        np.ones((3,), bool), np.array([1, ITERS, 2], np.int32))
    # A threshold between two lanes' first updates: one of the lanes
    # with budget left converges at step 1, the other runs on.  Read
    # off one step, not guessed.
    first, _ = it(variables, state, np.float32(0.0), np.int32(1))
    assert sorted(first) == ["active", "converged", "coords1",
                             "delta_max", "iters_done", "net"]
    d = np.asarray(first["delta_max"])
    assert d[1] != d[2]
    early = np.float32((d[1] + d[2]) / 2)
    return it, state, early


def _one_step_at_a_time(it, variables, state, thr, k):
    """``k`` calls with ``steps=1``, the caller putting what each call
    moved back into the state and keeping each lane's row of ``flow_up``
    from the call the lane retired in (what the slot engine and
    ``EarlyExitRunner`` do)."""
    from raft_tpu.serve import slots

    prev = np.asarray(state["active"])
    out = None
    for _ in range(k):
        moved, flow_up = it(variables, state, thr, np.int32(1))
        state = slots.advance(state, moved)
        flow_up = np.asarray(flow_up)
        out = np.zeros_like(flow_up) if out is None else out
        active = np.asarray(state["active"])
        newly = prev & ~active
        out[newly] = flow_up[newly]
        # a row of a lane that did not retire in this call says nothing
        assert not flow_up[~newly].any()
        prev = active
    return state, out


@pytest.mark.parametrize("case", ["budgets", "early_exit"])
@pytest.mark.parametrize("k", [1, 2, ITERS])
def test_iter_step_k_steps_equal_k_calls_bitwise(variables, stepped, k,
                                                 case):
    """``iter_step(..., steps=k)`` is ``k`` calls with ``steps=1`` bit
    for bit — every state leaf and every lane's ``flow_up`` row, a lane
    retiring at whichever step of the call its budget or the threshold
    says — because both run the same compiled loop body.  What the loop
    only reads is not returned at all: the state after a call holds the
    very arrays it held before."""
    import jax

    from raft_tpu.serve import slots

    it, state, early = stepped
    thr = np.float32(0.0) if case == "budgets" else early
    moved, got_flow = it(variables, state, thr, np.int32(k))
    got_state = slots.advance(state, moved)
    for leaf in ("inp", "coords0", "budget"):
        assert got_state[leaf] is state[leaf]
    assert all(a is b for a, b in zip(got_state["corr"], state["corr"]))
    want_state, want_flow = _one_step_at_a_time(it, variables, state,
                                                thr, k)
    got = jax.tree_util.tree_flatten_with_path(got_state)[0]
    want = jax.tree_util.tree_flatten_with_path(want_state)[0]
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, a), (_, b) in zip(got, want):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=jax.tree_util.keystr(path))
    np.testing.assert_array_equal(np.asarray(got_flow), want_flow)
    # the lanes retire at different steps of the one call: by their
    # budgets, and under the threshold one of them before its budget
    budgets = np.array([1, ITERS, 2])
    done = np.asarray(got_state["iters_done"])
    converged = np.asarray(got_state["converged"])
    if case == "budgets":
        assert done.tolist() == np.minimum(budgets, k).tolist()
        assert not converged.any()
        assert (~np.asarray(got_state["active"])).all() == (k == ITERS)
    else:
        assert (converged[1:] & (done[1:] < budgets[1:])).any()
        assert done[1] != done[2] or k == 1
    retired = ~np.asarray(got_state["active"])
    assert retired[0]
    assert [bool(np.asarray(got_flow)[i].any()) for i in range(3)] \
        == retired.tolist()


def test_request_mode_is_one_call_of_iters_steps_slot_mode_one_step(
        variables):
    """The counters that say the mechanism engages: a request-mode batch
    is 2 program calls (``enc``, ``iter``) and its one ``iter`` call
    runs ``cfg.iters`` steps; slot mode calls the same program with one
    step a call."""
    from raft_tpu.obs import stages

    rng = np.random.default_rng(31)
    pairs = [_images(rng) for _ in range(2)]
    newest = max([r["n"] for r in stages.recent("serve")], default=0)
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, max_batch=1, batch_sizes=(1,), max_wait_ms=1))
    with eng:
        for a, b in pairs:
            eng.infer(a, b, timeout=120)
        stats = eng.stats()
    recs = [r for r in stages.recent("serve") if r["n"] > newest]
    assert len(recs) == 2 and all(r["calls"] == 2 for r in recs)
    assert stats["iter_calls"] == 2
    assert stats["iter_steps"] / stats["iter_calls"] == ITERS

    slot = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, batching="slot", slots=1))
    with slot:
        for a, b in pairs:
            slot.infer(a, b, timeout=120)
        stats = slot.stats()
    assert stats["iter_calls"] == stats["slot_steps"] == 2 * ITERS
    assert stats["iter_steps"] / stats["iter_calls"] == 1
