"""Flow-quality observability tests (tier-1): the label-free proxy
math (``raft_tpu/obs/quality.py``), its calibration against ground
truth, the PSI drift detector, the serve-engine sampled-scoring
integration, and the end-to-end drill
(``scripts/quality_smoke.py --tiny``).

The two load-bearing pins:

- **Calibration** (the reason the proxies are trustworthy at all): on
  a difficulty-graded labeled fixture, the photometric AND residual
  proxies rank-correlate with true EPE at Spearman >= 0.6 — the same
  statistic ``evaluate.py --quality-proxies`` stamps for real
  datasets.
- **Zero overhead when off**: at ``quality_sample_rate=0`` (the
  default) the engine builds no monitor, compiles nothing beyond the
  imported AOT artifacts, and emits no quality telemetry — serving is
  bit-for-bit the pre-quality hot path.

Budget discipline: ONE engine compiles the single slot-mode
``(40, 56) x s2`` enc/iter pair and exports it (module ``aot_dir``);
the engine-integration tests import that artifact and serve with
CompileCounter == 0.
"""

import importlib.util
import json
import os
import os.path as osp
import time

import numpy as np
import pytest

from raft_tpu.config import RAFTConfig
from raft_tpu.obs.quality import (DriftDetector, QualityMonitor,
                                  canary_score, cycle_error,
                                  photometric_error, score_pair,
                                  spearman)
from raft_tpu.obs.registry import MetricRegistry
from raft_tpu.serve import InferenceEngine, ServeConfig

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))

CFG = RAFTConfig.small_model()  # fp32: CPU-friendly
ITERS = 2
SHAPE = (36, 52)                # -> bucket (40, 56)


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, osp.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _RecordingSink:
    def __init__(self):
        self.events = []

    def emit(self, event, step=None, **fields):
        self.events.append((event, fields))

    def of(self, event):
        return [f for e, f in self.events if e == event]


def _wait_for(pred, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _smooth(rng, h, w, pad, passes=1, k=5):
    """Box-blurred noise: a smooth textured scene (photometric warp
    error is meaningful; pure white noise would alias under 1 px)."""
    base = rng.uniform(0.0, 255.0, (h + 2 * pad, w + 2 * pad, 3))
    kern = np.ones(k) / k
    for _ in range(passes):
        for ax in (0, 1):
            base = np.apply_along_axis(
                lambda v: np.convolve(v, kern, mode="same"), ax, base)
    base -= base.min()
    base *= 255.0 / max(base.max(), 1e-6)
    return base


def _shifted_pair(rng, shift=2, pad=12):
    """``(im1, im2)`` where the true flow is a uniform ``(+shift, 0)``:
    ``im2`` is the scene panned ``shift`` px, so warping im2 by that
    flow reconstructs im1 (obs/quality.py warp convention)."""
    h, w = SHAPE
    base = _smooth(rng, h, w, pad)
    im1 = base[pad:pad + h, pad:pad + w]
    im2 = base[pad:pad + h, pad - shift:pad - shift + w]
    return im1.astype(np.float32), im2.astype(np.float32)


def _const_flow(fx, fy=0.0):
    fl = np.zeros(SHAPE + (2,), np.float32)
    fl[..., 0] = fx
    fl[..., 1] = fy
    return fl


@pytest.fixture(scope="module")
def variables():
    import jax

    from raft_tpu.models.raft import RAFT

    img = jax.numpy.zeros((1, 40, 56, 3))
    rng = jax.random.PRNGKey(0)
    return RAFT(CFG).init({"params": rng, "dropout": rng},
                          img, img, iters=1)


@pytest.fixture(scope="module")
def aot_dir(variables, tmp_path_factory):
    """The file's ONE compile: warm a slot-mode engine and export."""
    d = str(tmp_path_factory.mktemp("aot"))
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, batching="slot", slots=2, max_wait_ms=5))
    eng.start()
    try:
        eng.warmup([SHAPE])
        eng.export_aot(d)
    finally:
        eng.stop()
    return d


# ---------------------------------------------------------------------------
# proxy math
# ---------------------------------------------------------------------------


def test_photometric_ranks_correct_flow_best():
    """The proxy's one job: the flow that actually explains the frame
    pair scores lower than zero flow, which scores lower than the
    wrong-direction flow."""
    rng = np.random.default_rng(7)
    im1, im2 = _shifted_pair(rng, shift=2)
    scores = {fx: score_pair(im1, im2, _const_flow(fx))
              for fx in (2.0, 0.0, -2.0)}
    assert scores[2.0]["photometric"] < scores[0.0]["photometric"] \
        < scores[-2.0]["photometric"]
    # In-bounds accounting: a 2 px shift invalidates ~2 columns.
    assert scores[2.0]["valid_frac"] > 0.85
    for s in scores.values():
        assert s["canary"] == pytest.approx(
            s["photometric"] + (1.0 - s["valid_frac"]))


def test_photometric_oob_guard():
    """Degenerate flow mapping every pixel out of frame: the masked
    error alone would be a perfect 0; the canary score stays monotone
    in badness via the out-of-bounds term."""
    rng = np.random.default_rng(7)
    im1, im2 = _shifted_pair(rng)
    s = score_pair(im1, im2, _const_flow(500.0, 500.0))
    assert s["valid_frac"] == 0.0
    assert s["photometric"] == 0.0
    assert s["canary"] == pytest.approx(1.0)
    good = score_pair(im1, im2, _const_flow(2.0))
    assert canary_score(good["photometric"],
                        good["valid_frac"]) < s["canary"]


def test_photometric_census_survives_brightness_shift():
    """The census variant keeps ranking correct flow best under a
    global exposure shift between the frames."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    im1, im2 = _shifted_pair(rng, shift=2)
    im2 = np.clip(im2 + 60.0, 0, 255).astype(np.float32)  # exposure
    errs = {}
    for fx in (2.0, -2.0):
        err, vf = photometric_error(
            jnp.asarray(im1[None]), jnp.asarray(im2[None]),
            jnp.asarray(_const_flow(fx)[None]), census=True)
        errs[fx] = float(err[0])
        assert 0.8 < float(vf[0]) <= 1.0
    assert errs[2.0] < errs[-2.0]


def test_cycle_error_perfect_and_broken():
    """Forward/backward flows that agree cycle to ~0 with no occlusion
    flagged; a backward flow equal to the forward one (maximally
    inconsistent) scores the full 2x magnitude and flags everything."""
    import jax.numpy as jnp

    fw = jnp.asarray(_const_flow(2.0)[None])
    err, occ = cycle_error(fw, jnp.asarray(_const_flow(-2.0)[None]))
    assert float(err[0]) == pytest.approx(0.0, abs=1e-5)
    assert float(occ[0]) == pytest.approx(0.0, abs=1e-5)
    err, occ = cycle_error(fw, fw)
    assert float(err[0]) == pytest.approx(4.0, abs=1e-4)
    assert float(occ[0]) == pytest.approx(1.0, abs=1e-3)


def test_spearman_ties_constant_and_errors():
    assert spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
    assert spearman([1, 2, 3], [30, 20, 10]) == pytest.approx(-1.0)
    assert spearman([1, 2, 2, 3], [1, 5, 5, 9]) == pytest.approx(1.0)
    assert spearman([1.0, 1.0, 1.0], [1, 2, 3]) == 0.0  # constant
    assert spearman([2.0], [3.0]) == 0.0                # too short
    # Ties on one side only still rank-correlate partially.
    rho = spearman([1, 2, 2, 3], [1, 2, 3, 4])
    assert 0.9 < rho < 1.0
    with pytest.raises(ValueError):
        spearman([1, 2], [1, 2, 3])


# ---------------------------------------------------------------------------
# drift detection
# ---------------------------------------------------------------------------


def test_drift_detector_stable_then_shifted():
    """Stationary traffic never fires (PSI stays under threshold once
    the window fills); a mean shift fires within one window, re-fires
    at most once per window while it persists, and clears when the
    distribution recovers."""
    rng = np.random.default_rng(0)
    sink = _RecordingSink()
    det = DriftDetector("photometric", reference=32, window=8, bins=4,
                        threshold=1.0, registry=MetricRegistry(),
                        sink=sink)
    scores = [det.observe(float(rng.normal(0.5, 0.1)))
              for _ in range(32 + 40)]
    live = [s for s in scores if s is not None]
    assert len(live) == 40 - 7  # window fills 8 obs past the reference
    assert max(live) < det.threshold
    st = det.state()
    assert st["reference_frozen"] and st["events"] == 0
    assert not st["drifted"]

    for _ in range(16):  # mean shift: 2 windows of drifted traffic
        det.observe(float(rng.normal(5.0, 0.1)))
    st = det.state()
    assert st["drifted"] and st["score"] > det.threshold
    assert st["events"] == 2  # edge fire + one refire per window
    drift_events = sink.of("quality_drift")
    assert len(drift_events) == 2
    assert drift_events[0]["proxy"] == "photometric"
    assert drift_events[0]["score"] > det.threshold

    for _ in range(16):  # persisting drift: refire cadence holds
        det.observe(float(rng.normal(5.0, 0.1)))
    assert det.state()["events"] == 4

    for _ in range(12):  # recovery clears the latch, no new events
        det.observe(float(rng.normal(0.5, 0.1)))
    st = det.state()
    assert not st["drifted"] and st["events"] == 4


def test_drift_detector_validation():
    with pytest.raises(ValueError):
        DriftDetector("p", reference=4, bins=8)
    with pytest.raises(ValueError):
        DriftDetector("p", window=1)
    with pytest.raises(ValueError):
        DriftDetector("p", threshold=0.0)


# ---------------------------------------------------------------------------
# QualityMonitor (host-side unit)
# ---------------------------------------------------------------------------


def test_monitor_sampling_and_residual_sentinel():
    m0 = QualityMonitor(sample_rate=0.0)
    assert not any(m0.sample() for _ in range(50))
    m1 = QualityMonitor(sample_rate=1.0)
    assert all(m1.sample() for _ in range(50))
    # Seeded coin: replayable, and roughly calibrated.
    a = QualityMonitor(sample_rate=0.5, seed=3)
    b = QualityMonitor(sample_rate=0.5, seed=3)
    coins = [a.sample() for _ in range(200)]
    assert coins == [b.sample() for _ in range(200)]
    assert 60 < sum(coins) < 140
    # delta_max == -1 is "lane never iterated": no signal, not a value.
    m1.record_residual(-1.0)
    assert m1.snapshot()["residual"]["window_count"] == 0
    m1.record_residual(0.25, bucket="40x56")
    assert m1.snapshot()["residual"]["window_count"] == 1
    with pytest.raises(ValueError):
        QualityMonitor(sample_rate=1.5)


def test_monitor_scores_and_cycle_bookkeeping():
    """A scored retirement emits one ``quality_score`` event and
    returns trace attrs; a retirement recognized as a pending cycle
    backward pass folds into ``raft_quality_cycle`` instead of being
    scored as fresh traffic; the pending table is bounded."""
    rng = np.random.default_rng(7)
    im1, im2 = _shifted_pair(rng)
    sink = _RecordingSink()
    reg = MetricRegistry()
    m = QualityMonitor(registry=reg, sink=sink, sample_rate=1.0)

    fut = object()
    attrs = m.note_retirement(future=fut, image1=im1, image2=im2,
                              flow=_const_flow(2.0), bucket="40x56",
                              residual=0.2, converged=True, iters=2)
    assert attrs is not None
    assert attrs["quality_photometric"] >= 0.0
    assert attrs["quality_residual"] == pytest.approx(0.2)
    snap = m.snapshot()
    assert snap["scored_total"] == 1
    assert snap["residual"]["window_count"] == 1
    ev = sink.of("quality_score")
    assert len(ev) == 1 and ev[0]["bucket"] == "40x56"
    assert ev[0]["converged"] is True and ev[0]["iters"] == 2
    # Per-bucket gauge landed in the registry exposition.
    from raft_tpu.obs.exposition import render
    text = render(reg)
    assert "raft_quality_bucket_mean" in text and "40x56" in text

    # Cycle: the backward pass's retirement closes the measurement.
    bfut = object()
    m.begin_cycle(bfut, _const_flow(2.0), "40x56")
    out = m.note_retirement(future=bfut, image1=im2, image2=im1,
                            flow=_const_flow(-2.0), bucket="40x56",
                            residual=0.1)
    assert out is None  # not fresh traffic
    snap = m.snapshot()
    assert snap["scored_total"] == 1        # unchanged
    assert snap["cycle"]["window_count"] == 1
    assert snap["cycle"]["p50"] == pytest.approx(0.0, abs=1e-4)
    cyc_ev = [f for f in sink.of("quality_score")
              if f.get("proxy") == "cycle"]
    assert len(cyc_ev) == 1 and "occluded_frac" in cyc_ev[0]

    # Bounded pending table: the oldest entry is evicted, and its
    # retirement then scores as ordinary (fresh) traffic.
    futs = [object() for _ in range(3)]
    for f in futs:
        m.begin_cycle(f, _const_flow(2.0), None, limit=2)
    assert m.note_retirement(future=futs[0], image1=im1, image2=im2,
                             flow=_const_flow(2.0)) is not None
    assert m.snapshot()["scored_total"] == 2


# ---------------------------------------------------------------------------
# calibration: proxies vs ground truth (the acceptance gate)
# ---------------------------------------------------------------------------


class _GradedDataset:
    """Labeled fixture with monotone difficulty: sample ``d`` pans a
    smooth scene ``1 + 2d`` px (EPE against an untrained model grows
    with the motion), while contrast falls and sensor noise grows with
    ``d`` — the classic hard-flow regime (low-texture, noisy, large
    motion), which drives both the photometric warp error and the
    model's convergence residual."""

    def __init__(self, n=8, seed=3):
        rng = np.random.default_rng(seed)
        h, w = SHAPE
        pad = 2 + 2 * n
        self.samples = []
        for d in range(n):
            base = _smooth(rng, h, w, pad)
            gain = 0.9 - 0.09 * d
            shift = 1 + 2 * d
            im1 = base[pad:pad + h, pad:pad + w] * gain
            im2 = base[pad:pad + h,
                       pad - shift:pad - shift + w] * gain
            amp = 2.0 + 8.0 * d
            im1 = np.clip(im1 + rng.normal(0, amp, im1.shape), 0, 255)
            im2 = np.clip(im2 + rng.normal(0, amp, im2.shape), 0, 255)
            flow = np.zeros((h, w, 2), np.float32)
            flow[..., 0] = -shift
            self.samples.append({
                "image1": im1.astype(np.float32),
                "image2": im2.astype(np.float32),
                "flow": flow})

    def __len__(self):
        return len(self.samples)

    def load(self, i):
        return self.samples[i]


def test_quality_proxies_calibrated_against_epe(variables, monkeypatch):
    """THE receipt: on labeled data, the label-free proxies the serve
    path emits rank bad flow as bad — Spearman(proxy, EPE) >= 0.6 for
    BOTH the photometric and residual proxies (the bar
    ``evaluate.py --quality-proxies`` documents for a trustworthy
    drift/canary signal)."""
    from raft_tpu import evaluate

    monkeypatch.setitem(evaluate.EARLY_EXIT_DATASETS, "chairs",
                        lambda **kw: _GradedDataset())
    rec = evaluate.evaluate_quality_proxies(
        variables, CFG, dataset="chairs", iters=4, batch_size=4,
        bucket=False, cycle=True)
    assert rec["dataset"] == "chairs" and rec["n"] == 8
    assert rec["epe_mean"] > 0
    assert set(rec["spearman"]) == {"photometric", "residual", "cycle"}
    assert rec["spearman"]["photometric"] >= 0.6, rec["spearman"]
    assert rec["spearman"]["residual"] >= 0.6, rec["spearman"]
    assert -1.0 <= rec["spearman"]["cycle"] <= 1.0
    for v in rec["proxy_means"].values():
        assert np.isfinite(v)
    with pytest.raises(ValueError):
        evaluate.evaluate_quality_proxies(variables, CFG,
                                          dataset="nope")


def test_cli_quality_proxies_flags():
    from raft_tpu.cli import evaluate as cli

    args = cli.parse_args(["--model", "m", "--dataset", "chairs",
                           "--quality-proxies", "--quality-cycle"])
    assert args.quality_proxies and args.quality_cycle
    args = cli.parse_args(["--model", "m", "--dataset", "chairs"])
    assert not args.quality_proxies and not args.quality_cycle


# ---------------------------------------------------------------------------
# serve-engine integration
# ---------------------------------------------------------------------------


def test_engine_slot_sampled_scoring(variables, aot_dir):
    """Slot-mode engine at sample_rate=1 with cycle scoring: every
    retirement is scored (residual + photometric), each scored request
    triggers one backward pass that folds into the cycle histogram,
    and ``/v1/stats["quality"]`` carries the whole picture."""
    rng = np.random.default_rng(4)
    sink = _RecordingSink()
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, batching="slot", slots=2, max_wait_ms=5,
        aot_dir=aot_dir, quality_sample_rate=1.0, quality_cycle=True),
        sink=sink)
    n = 4
    with eng:
        futs = [eng.submit(*_shifted_pair(rng)) for _ in range(n)]
        for f in futs:
            assert f.result(timeout=120).shape == SHAPE + (2,)
        # Retirement accounting trails future resolution by one hook
        # call; the backward cycle passes retire asynchronously.
        _wait_for(lambda: eng.stats()["quality"]["cycle"]
                  ["window_count"] >= n, 30, "cycle passes to retire")
        q = eng.stats()["quality"]
    assert q["enabled"] and q["sample_rate"] == 1.0 and q["cycle"]
    assert q["scored_total"] == n  # backward passes are NOT re-scored
    assert q["photometric"]["window_count"] == n
    assert q["residual"]["window_count"] == n
    assert q["cycle"]["window_count"] == n
    for proxy in ("photometric", "residual", "cycle"):
        assert q[proxy]["p95"] >= q[proxy]["p50"] >= 0.0
    drift = q["drift"]
    assert drift["photometric"]["observed"] == n
    assert drift["residual"]["observed"] == n
    assert not drift["photometric"]["reference_frozen"]
    scored = [f for f in sink.of("quality_score")
              if "photometric" in f]
    cycles = [f for f in sink.of("quality_score")
              if f.get("proxy") == "cycle"]
    assert len(scored) == n and len(cycles) == n
    for f in scored:
        assert f["bucket"] == "40x56" and f["residual"] >= 0.0
        assert "canary" in f and "valid_frac" in f


def test_engine_rate_zero_is_zero_overhead(variables, aot_dir):
    """The default (rate 0): no monitor object, no compiles beyond the
    imported AOT artifacts, no quality telemetry — the hot path is the
    pre-quality hot path."""
    rng = np.random.default_rng(4)
    sink = _RecordingSink()
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, batching="slot", slots=2, max_wait_ms=5,
        aot_dir=aot_dir), sink=sink)
    assert eng.aot_info["ok"] is True
    with eng:
        for _ in range(2):
            flow = eng.infer(*_shifted_pair(rng), timeout=120)
            assert flow.shape == SHAPE + (2,)
        assert eng.compile_counter.counts() == {}
        assert eng._quality is None
        assert eng.quality_drift() is None
        stats = eng.stats()
    assert stats["quality"] == {"enabled": False}
    assert sink.of("quality_score") == []
    assert sink.of("quality_drift") == []


def test_serve_config_quality_validation():
    with pytest.raises(ValueError):
        ServeConfig(quality_sample_rate=1.5)
    with pytest.raises(ValueError):
        ServeConfig(quality_sample_rate=-0.1)
    with pytest.raises(ValueError):
        ServeConfig(quality_sample_rate=0.5, quality_drift_window=1)
    with pytest.raises(ValueError):
        ServeConfig(quality_sample_rate=0.5,
                    quality_drift_threshold=0.0)


# ---------------------------------------------------------------------------
# the end-to-end drill
# ---------------------------------------------------------------------------


def test_quality_smoke_drill_tiny(capsys):
    """The drill the PR promises: sampled scoring over healthy
    traffic, scrambled weights refused at the proxy canary, and the
    drift detector + fleet supervisor catching the same weights when
    hot-swapped past the gate.  The drill keeps its own AOT directory
    (its replica compiles and exports, the warming engine of the weight
    update imports): loading the MODULE fixture's blobs yet again in
    this process — the two engine tests above already hold them — trips
    jaxlib 0.9.0's XLA:CPU AOT loader ("NOT_FOUND ... Function
    iota_concatenate_fusion not found"), the loader whose own warning
    says its executables may not run.  A CPU-only hazard."""
    from raft_tpu.obs import reset_default_sink

    mod = _load_script("quality_smoke")
    try:
        rc = mod.main(["--tiny"])
    finally:
        # The drill binds the process-global telemetry sink to its
        # temp dir; restore the default for the rest of the session.
        os.environ.pop("RAFT_TELEMETRY_DIR", None)
        reset_default_sink()
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    assert rc == 0
    assert rec["metric"] == "quality_smoke" and rec["value"] == 1.0
    cfg = rec["config"]
    assert cfg["quality_drift_score"] > cfg["drift_threshold"]
    assert cfg["canary_proxy_delta_pct"] > 300.0  # way past the budget
    assert cfg["proxy_refusal"]["new"] > cfg["proxy_refusal"]["old"]
    # Healthy traffic sat below the drift threshold before the swap.
    for score in cfg["baseline"]["scores"].values():
        assert score < cfg["drift_threshold"]
