"""Fault-tolerance layer tests (tier-1): the chaos injection core, the
data-path quarantine, the checkpoint fallback chain + verify-ckpt CLI,
serve transient-error retry, and the chaos_smoke script.

The contracts pinned here are the PR-5 acceptance criteria: chaos
disabled = bit-identical batch stream (the test_prefetch determinism
contract still holds with the injection points compiled in); under
injected faults the train/serve paths COMPLETE with the expected
quarantine/fallback/retry telemetry; deterministic errors still fail
fast.

Everything but the one engine e2e test and the smoke runs without jit.
"""

import importlib.util
import json
import os
import os.path as osp
import threading
import time

import numpy as np
import pytest

from raft_tpu import chaos
from raft_tpu.chaos import (ChaosSpecError, FaultPlan,
                            InjectedDeviceError, InjectedProducerCrash,
                            is_transient_error)
from raft_tpu.data.datasets import (FlowDataset, SampleReadError,
                                    ShardedLoader)
from raft_tpu.data.prefetch import DevicePipeline
from raft_tpu.obs import EventSink, MetricRegistry

from loader_datasets import SynthDataset, ledger

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, osp.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Chaos is process-global state: never leak a plan across tests."""
    chaos.uninstall()
    yield
    chaos.uninstall()


def _events(path):
    out = []
    for fname in sorted(os.listdir(path)):
        if fname.endswith(".jsonl"):
            with open(osp.join(path, fname)) as f:
                out += [json.loads(l) for l in f if l.strip()]
    return out


# ---------------------------------------------------------------------
# FaultPlan: grammar + deterministic firing
# ---------------------------------------------------------------------

def test_fault_plan_parse_grammar_and_errors():
    plan = FaultPlan.parse(
        "corrupt_image@step=7,p=0.5;torn_ckpt@step=50;"
        "device_err@batch=3,times=2", seed=4)
    assert set(plan.counts()) == {"corrupt_image", "torn_ckpt",
                                  "device_err"}
    for bad in ("corrupt_image", "x@", "x@foo=1", "x@p=1.5", "x@p=0",
                "x@step=a", "x@times=0", "x@times=1", ";", "",
                "BadName@step=1"):
        with pytest.raises(ChaosSpecError):
            FaultPlan.parse(bad)
    # 'x@times=1' above: a times-only rule has no trigger


def test_fault_plan_step_and_ordinal_triggers():
    plan = FaultPlan.parse("device_err@batch=3")
    # with a step context: fires exactly at step 3, once
    assert [plan.fires("device_err", step=s)
            for s in (1, 2, 3, 3, 4)] == [False, False, True, False,
                                          False]
    # without a context the rule's own check ordinal is the trigger
    plan2 = FaultPlan.parse("corrupt_image@call=2")
    assert [plan2.fires("corrupt_image")
            for _ in range(5)] == [False, False, True, False, False]
    # unknown faults never fire and cost nothing
    assert not plan2.fires("torn_ckpt")


def test_fault_plan_p_rule_seeded_reproducible():
    a = FaultPlan.parse("e@p=0.3", seed=9)
    b = FaultPlan.parse("e@p=0.3", seed=9)
    fa = [a.fires("e") for _ in range(50)]
    fb = [b.fires("e") for _ in range(50)]
    assert fa == fb and 0 < sum(fa) < 50
    # default times for a pure p-rule is unlimited
    assert sum(fa) > 1
    # times bounds a p-rule
    c = FaultPlan.parse("e@p=1.0,times=2", seed=0)
    assert [c.fires("e") for _ in range(4)] == [True, True, False, False]


def test_install_from_env_and_should_inject(monkeypatch, tmp_path):
    monkeypatch.delenv(chaos.ENV_SPEC, raising=False)
    assert chaos.install_from_env() is None and not chaos.enabled()
    monkeypatch.setenv(chaos.ENV_SPEC, "device_err@batch=1")
    monkeypatch.setenv(chaos.ENV_SEED, "3")
    plan = chaos.install_from_env()
    assert chaos.enabled() and plan.seed == 3
    assert not chaos.should_inject("device_err", step=2)
    assert chaos.should_inject("device_err", step=1)
    assert plan.counts()["device_err"] == 1
    chaos.uninstall()
    assert not chaos.should_inject("device_err", step=1)


# ---------------------------------------------------------------------
# data path: context + quarantine
# ---------------------------------------------------------------------

def _write_png(path, hw=(8, 10)):
    from PIL import Image

    Image.fromarray(np.zeros(hw + (3,), np.uint8)).save(path)


def test_sample_read_error_carries_dataset_context(tmp_path):
    """Satellite: a truncated .flo no longer raises a bare ValueError —
    the error names the dataset, split, sample index and file path."""
    p1, p2 = str(tmp_path / "a.png"), str(tmp_path / "b.png")
    _write_png(p1), _write_png(p2)
    bad_flo = str(tmp_path / "bad.flo")
    with open(bad_flo, "wb") as f:
        f.write(b"garbage")
    ds = FlowDataset()
    ds.split = "training"
    ds.image_list = [(p1, p2)]
    ds.flow_list = [bad_flo]
    with pytest.raises(SampleReadError) as ei:
        ds.load(0)
    e = ei.value
    assert isinstance(e, ValueError)  # existing handlers keep working
    assert e.path == bad_flo and e.index == 0
    assert e.dataset_name == "FlowDataset" and e.split == "training"
    for frag in (bad_flo, "FlowDataset", "training", "sample=0"):
        assert frag in str(e), str(e)
    assert isinstance(e.__cause__, ValueError)  # original kept chained


def test_quarantine_skips_bad_sample_and_keeps_shapes(tmp_path):
    """A corrupt sample is retried, quarantined (event + counter), and
    deterministically replaced — batches keep their shape and the run
    keeps going.  The sample fails inside a worker process; the event,
    the counters and ``quarantined_total`` are the parent's."""
    reg = MetricRegistry()
    sink = EventSink(str(tmp_path))
    calls = str(tmp_path / "calls")
    ds = SynthDataset(n=13, poison={5}, ledger=calls)
    loader = ShardedLoader(ds, batch_size=2, seed=7, num_workers=2,
                           sample_retries=1, sink=sink, registry=reg)
    it = loader.batches()
    batches = [next(it) for _ in range(6)]  # the full epoch
    it.close()
    sink.close()
    for b in batches:
        assert b["image1"].shape == (2, 8, 10, 3)
    assert loader.quarantined_total == 1
    assert reg.counter("raft_data_quarantined_total").value() == 1
    # every delivered sample was booked in the parent from its header
    assert reg.counter("raft_data_samples_total").value() >= 12
    # the same poisoned file was retried sample_retries+1 times
    assert ledger(calls).count(5) == 2
    (ev,) = [e for e in _events(str(tmp_path))
             if e["event"] == "sample_quarantine"]
    assert ev["dataset"] == "SynthDataset"
    assert ev["split"] == "synthetic"
    assert ev["path"] == "synth://5/a"
    assert ev["index"] == 5 and ev["original_index"] == 5
    assert "synthetic corruption" in ev["error"]


def test_quarantine_replacement_is_deterministic():
    """Two loaders over identically-poisoned data produce bit-identical
    streams — the replacement draw is keyed on (seed, epoch, index),
    not on scheduling or wall clock."""
    def stream():
        loader = ShardedLoader(SynthDataset(n=13, poison={5}),
                               batch_size=2, seed=7, num_workers=2,
                               sink=EventSink(None))
        it = loader.batches()
        out = [next(it) for _ in range(6)]
        it.close()
        return out

    a, b = stream(), stream()
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])


def test_chaos_disabled_stream_bit_identical():
    """The acceptance criterion: with chaos disabled — no plan, or an
    installed plan whose rules never fire — the batch stream through
    loader + DevicePipeline is bit-identical to the plain path (the
    injection points add no RNG draws, no reordering, nothing)."""
    def stream(depth):
        loader = ShardedLoader(SynthDataset(n=13), batch_size=2,
                               seed=7, num_workers=1,
                               sink=EventSink(None))
        pipe = DevicePipeline(loader.batches(), depth=depth)
        try:
            return [next(pipe) for _ in range(6)]
        finally:
            pipe.close()

    baseline = stream(0)
    chaos.install(FaultPlan.parse(
        "corrupt_image@step=9999;producer_err@step=9999"))  # inert
    armed = stream(0)
    overlapped = stream(3)
    chaos.uninstall()
    for other in (armed, overlapped):
        for x, y in zip(baseline, other):
            for k in x:
                np.testing.assert_array_equal(x[k], y[k])


def test_quarantine_gives_up_when_everything_is_rotten(tmp_path):
    calls = str(tmp_path / "calls")
    ds = SynthDataset(n=5, poison=set(range(5)), ledger=calls)
    loader = ShardedLoader(ds, batch_size=2, seed=7, num_workers=1,
                           sample_retries=0, sample_resamples=3,
                           sink=EventSink(None))
    with pytest.raises(RuntimeError, match="replacement"):
        loader._load_one(0, 1)
    # 1 original + 3 replacements, each tried once
    assert len(ledger(calls)) == 4
    assert loader.quarantined_total == 4


def test_worker_err_injection_propagates_not_quarantines():
    """`worker_err` is a loader BUG model, not a decode error: it must
    kill the run, never be absorbed by quarantine."""
    from raft_tpu.chaos import InjectedWorkerCrash

    chaos.install(FaultPlan.parse("worker_err@call=0"))
    loader = ShardedLoader(SynthDataset(n=5), batch_size=2, seed=7,
                           num_workers=1, sink=EventSink(None))
    with pytest.raises(InjectedWorkerCrash):
        loader._load_one(0, 1)
    assert loader.quarantined_total == 0


def _worker_pids():
    """This process's loader workers: children running
    ``raft_tpu.data.workers``."""
    pids = []
    for d in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{d}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            with open(f"/proc/{d}/cmdline", "rb") as f:
                cmd = f.read()
        except OSError:
            continue
        if ppid == os.getpid() and b"raft_tpu.data.workers" in cmd:
            pids.append(int(d))
    return pids


@pytest.mark.parametrize("how", ["worker_err", "killed"])
def test_a_failed_worker_fails_next_within_a_time_limit(how):
    """`worker_err` (drawn in the parent at submit, raised in the worker)
    and a worker process that dies both end the stream with an error out
    of ``next()``, never a hang, and no worker outlives the stream."""
    import signal

    from raft_tpu.chaos import InjectedWorkerCrash

    if how == "worker_err":
        chaos.install(FaultPlan.parse("worker_err@call=6"))
    it = ShardedLoader(SynthDataset(n=13), batch_size=2, seed=7,
                       num_workers=2, sink=EventSink(None)).batches()
    next(it)
    if how == "killed":
        victim, _ = _worker_pids()
        os.kill(victim, signal.SIGKILL)
        # Gone once a zombie (its sockets closed): the very next wait on
        # the workers sees it, however fast the tiny samples come.
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            with open(f"/proc/{victim}/stat") as f:
                if f.read().rsplit(")", 1)[1].split()[0] == "Z":
                    break
            time.sleep(0.01)
    t0 = time.monotonic()
    with pytest.raises(InjectedWorkerCrash if how == "worker_err"
                       else RuntimeError,
                       match="crash" if how == "worker_err"
                       else "killed by signal 9"):
        for _ in range(12):
            next(it)
    assert time.monotonic() - t0 < 30.0
    assert not _worker_pids()


def test_corrupt_image_injection_fires_at_sample_read(tmp_path):
    """The data.sample_read seam: the injected corruption takes the
    exact real-corruption path (SampleReadError -> quarantine)."""
    chaos.install(FaultPlan.parse("corrupt_image@call=2"))
    sink = EventSink(str(tmp_path))
    p1, p2 = str(tmp_path / "a.png"), str(tmp_path / "b.png")
    _write_png(p1), _write_png(p2)
    flo = str(tmp_path / "ok.flo")
    from raft_tpu.data.frame_utils import write_flo

    write_flo(flo, np.zeros((8, 10, 2), np.float32))
    ds = FlowDataset()
    ds.image_list, ds.flow_list = [(p1, p2)] * 4, [flo] * 4
    loader = ShardedLoader(ds, batch_size=2, seed=1, num_workers=1,
                           sample_retries=0, sink=sink)
    it = loader.batches()
    next(it), next(it)  # the third sample's header reaches the parent
    it.close()
    sink.close()
    evs = [e["event"] for e in _events(str(tmp_path))]
    assert evs.count("sample_quarantine") == 1


# ---------------------------------------------------------------------
# pipeline producer seam
# ---------------------------------------------------------------------

def test_producer_err_injection_propagates_both_depths():
    for depth in (0, 2):
        chaos.install(FaultPlan.parse("producer_err@step=1"))

        def src():
            while True:
                yield {"x": np.zeros((4,), np.float32)}

        pipe = DevicePipeline(src(), depth=depth)
        next(pipe)  # pull ordinal 0 is clean
        with pytest.raises(InjectedProducerCrash):
            for _ in range(3):
                next(pipe)
        pipe.close()
        chaos.uninstall()


# ---------------------------------------------------------------------
# checkpoint fallback + verify
# ---------------------------------------------------------------------

def _tiny_state(step=0):
    import jax.numpy as jnp
    import optax

    from raft_tpu.train.state import TrainState

    params = {"w": jnp.full((2, 2), float(step), jnp.float32)}
    tx = optax.sgd(1e-2)
    return TrainState(step=jnp.asarray(step, jnp.int32), params=params,
                      batch_stats={}, opt_state=tx.init(params),
                      nonfinite_steps=jnp.zeros((), jnp.int32))


def _mgr(path, sink=None):
    from raft_tpu.train.checkpoint import CheckpointManager

    return CheckpointManager(str(path), async_save=False, sink=sink)


def test_restore_latest_falls_back_past_torn_step(tmp_path):
    from raft_tpu.train.checkpoint import CheckpointRestoreError

    tdir = tmp_path / "telemetry"
    sink = EventSink(str(tdir))
    mgr = _mgr(tmp_path / "ck", sink=sink)
    for s in (1, 2, 3):
        mgr.save(s, _tiny_state(s))
    mgr.wait()
    chaos.tear_files(str(tmp_path / "ck" / "3"))

    st = mgr.restore_latest(_tiny_state(0))
    assert int(st.step) == 2  # newest VALID, not newest
    evs = [e for e in _events(str(tdir)) if e["event"] == "ckpt_fallback"]
    assert len(evs) == 1 and evs[0]["step"] == 3
    assert evs[0]["remaining_steps"] == 2

    # verify() reports the same picture without model code
    reports = mgr.verify_all()
    assert [(r["step"], r["ok"]) for r in reports] == [
        (1, True), (2, True), (3, False)]
    assert "error" in reports[2]

    # everything torn -> loud failure, never a silent fresh start
    chaos.tear_files(str(tmp_path / "ck" / "1"))
    chaos.tear_files(str(tmp_path / "ck" / "2"))
    with pytest.raises(CheckpointRestoreError, match="no restorable"):
        mgr.restore_latest(_tiny_state(0))
    mgr.close()
    sink.close()


def test_restore_err_injection_walks_fallback(tmp_path):
    mgr = _mgr(tmp_path / "ck", sink=EventSink(None))
    for s in (1, 2):
        mgr.save(s, _tiny_state(s))
    mgr.wait()
    chaos.install(FaultPlan.parse("restore_err@step=2"))
    st = mgr.restore_latest(_tiny_state(0))
    assert int(st.step) == 1
    mgr.close()


def test_torn_ckpt_injection_tears_after_commit(tmp_path):
    chaos.install(FaultPlan.parse("torn_ckpt@step=2"))
    mgr = _mgr(tmp_path / "ck", sink=EventSink(None))
    for s in (1, 2):
        mgr.save(s, _tiny_state(s))
    mgr.wait()
    assert mgr.all_steps() == [1, 2]  # torn step stays listed...
    assert [r["ok"] for r in mgr.verify_all()] == [True, False]  # ...torn
    st = mgr.restore_latest(_tiny_state(0))
    assert int(st.step) == 1
    mgr.close()


def test_structure_mismatch_narrowing():
    """Satellite: only structure-mismatch errors qualify for the
    legacy-template retry; corruption classes never do."""
    from raft_tpu.train.checkpoint import _is_structure_mismatch

    yes = [ValueError("User-provided restore item and on-disk value "
                      "metadata tree structures do not match"),
           ValueError("Tree structure mismatch at key nonfinite_steps"),
           KeyError("nonfinite_steps"),
           # "missing" + the legacy-counter signature stays a mismatch:
           # the nonfinite_steps wording always wins over the veto
           ValueError("restore template missing key nonfinite_steps")]
    no = [json.JSONDecodeError("Unterminated string", "x", 0),
          OSError("read failed"),
          RuntimeError("structure"),  # wrong class, right word
          ValueError("bad .flo magic"),
          # Regression (PR 7): torn-file IO errors phrased with
          # "missing" — tensorstore/orbax wording for truncated or
          # absent chunk files — must classify as CORRUPTION, never as
          # a structure mismatch (the legacy-template retry would bury
          # the real traceback).
          ValueError('NOT_FOUND: Error opening "zarr" driver: '
                     'Metadata at "params/w/.zarray" does not exist'),
          ValueError('Error opening "zarr" driver: missing chunk 0.0 '
                     'for "opt_state/mu/w"'),
          ValueError("missing metadata file for array params/b"),
          KeyError("manifest.ocdbt truncated: missing data"),
          TypeError("CHECKSUM mismatch decoding params/w: missing "
                    "trailing bytes")]
    assert all(_is_structure_mismatch(e) for e in yes)
    assert not any(_is_structure_mismatch(e) for e in no)


def test_verify_ckpt_cli(tmp_path, capsys):
    from raft_tpu.cli.verify_ckpt import main as verify_main

    mgr = _mgr(tmp_path / "ck", sink=EventSink(None))
    for s in (1, 2, 3):
        mgr.save(s, _tiny_state(s))
    mgr.wait()
    mgr.close()

    assert verify_main([str(tmp_path / "ck")]) == 0
    capsys.readouterr()

    chaos.tear_files(str(tmp_path / "ck" / "3"))
    assert verify_main([str(tmp_path / "ck"), "--json"]) == 1
    rep = json.loads(capsys.readouterr().out.strip())
    assert rep["latest_valid"] == 2 and rep["ok"] is False
    assert [(s["step"], s["ok"]) for s in rep["steps"]] == [
        (1, True), (2, True), (3, False)]

    chaos.tear_files(str(tmp_path / "ck" / "1"))
    chaos.tear_files(str(tmp_path / "ck" / "2"))
    assert verify_main([str(tmp_path / "ck")]) == 2
    assert verify_main([str(tmp_path / "empty")]) == 2


# ---------------------------------------------------------------------
# serve: transient classification + retry
# ---------------------------------------------------------------------

def test_is_transient_error_classification():
    XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
    assert is_transient_error(InjectedDeviceError("x"))
    assert is_transient_error(XlaRuntimeError("UNAVAILABLE: socket "
                                              "closed"))
    assert is_transient_error(XlaRuntimeError("DEADLINE_EXCEEDED: "
                                              "program launch"))
    assert not is_transient_error(XlaRuntimeError(
        "INVALID_ARGUMENT: shape mismatch"))
    assert not is_transient_error(ValueError("UNAVAILABLE"))  # not a
    # runtime-error type: a value error naming the word is still a bug
    assert not is_transient_error(RuntimeError("UNAVAILABLE"))

    class Flagged(RuntimeError):
        transient = False

    assert not is_transient_error(Flagged("UNAVAILABLE"))  # explicit
    # flag wins over message sniffing


def _engine_shell(tmp_path=None, **cfg_kw):
    """An InferenceEngine WITHOUT start(): cheap (no compile), enough
    to unit-test the device-call retry policy."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.serve import InferenceEngine, ServeConfig

    sink = EventSink(str(tmp_path) if tmp_path else None)
    return InferenceEngine(
        {"params": {}}, RAFTConfig.small_model(),
        ServeConfig(retry_backoff_s=0.0, **cfg_kw), sink=sink)


def test_call_device_retries_transient_once(tmp_path):
    eng = _engine_shell(tmp_path, device_retries=1)
    calls = []

    def flaky(variables, a1, a2):
        calls.append(1)
        if len(calls) == 1:
            raise InjectedDeviceError("transient flake")
        return None, np.zeros((1, 8, 8, 2), np.float32)

    out = eng._call_device(flaky, None, None, (8, 8), seq=1)
    assert out.shape == (1, 8, 8, 2) and len(calls) == 2
    assert eng.stats()["retries"] == 1
    evs = [e for e in _events(str(tmp_path))
           if e["event"] == "serve_retry"]
    assert len(evs) == 1 and evs[0]["attempt"] == 1


def test_call_device_fails_fast_on_deterministic_error():
    eng = _engine_shell(device_retries=3)
    calls = []

    def broken(variables, a1, a2):
        calls.append(1)
        raise ValueError("shape mismatch")

    with pytest.raises(ValueError, match="shape mismatch"):
        eng._call_device(broken, None, None, (8, 8), seq=1)
    assert len(calls) == 1  # deterministic: exactly one attempt
    assert eng.stats()["retries"] == 0


def test_call_device_retry_budget_exhausts():
    eng = _engine_shell(device_retries=2)
    calls = []

    def always_flaky(variables, a1, a2):
        calls.append(1)
        raise InjectedDeviceError("still down")

    with pytest.raises(InjectedDeviceError):
        eng._call_device(always_flaky, None, None, (8, 8), seq=1)
    assert len(calls) == 3  # 1 + 2 retries
    assert eng.stats()["retries"] == 2


@pytest.mark.parametrize("transient", [True, False])
def test_drain_error_of_batch_n_leaves_batch_n_plus_1_alone(tmp_path,
                                                            transient):
    """The error of batch n surfaces at its drain, with batch n+1
    already uploaded and launched behind it.  A transient one re-runs n
    whole, once, on the completing side (one ``serve_retry`` event,
    retries counted once) and both are answered, in order; a
    deterministic one fails n only."""
    from tests.fake_device import FakeDevice

    eng = _engine_shell(tmp_path, device_retries=2, max_batch=1,
                        batch_sizes=(1,), max_wait_ms=1)
    dev = FakeDevice(gated=True).install(eng)
    dev.drain_errors[0] = [InjectedDeviceError("flake at the drain")
                           if transient else ValueError("bad program")]
    eng.start()
    try:
        im = np.zeros((36, 52, 3), np.float32)
        f0 = eng.submit(im, im)
        assert dev.wait_launched(1)
        f1 = eng.submit(im, im)
        assert dev.wait_launched(2)        # n+1 issued, n not yet read
        assert dev.drained == []
        dev.gate.set()
        # answers name their launch: batch 1 is launch 1, and the
        # re-run of batch 0 is the third launch
        assert f1.result(timeout=30)[0, 0, 0] == 1.0
        if transient:
            assert f0.result(timeout=30)[0, 0, 0] == 2.0
        else:
            with pytest.raises(ValueError, match="bad program"):
                f0.result(timeout=30)
        assert [k for k, _ in dev.drained] == ([2, 1] if transient
                                               else [1])
    finally:
        dev.gate.set()
        eng.stop()
    stats = eng.stats()
    assert stats["retries"] == int(transient)
    assert stats["errors"] == int(not transient)
    assert stats["completed"] == 1 + int(transient)
    evs = [e for e in _events(str(tmp_path))
           if e["event"] == "serve_retry"]
    assert len(evs) == int(transient)
    from raft_tpu.obs import stages

    r0, r1 = stages.recent("serve")[-2:]
    assert (r0["retries"], r1["retries"]) == (int(transient), 0)
    assert (r0["calls"], r1["calls"]) == (0, 0)   # the fake issues none
    assert r0["error"] == (None if transient else "ValueError")
    assert r1["error"] is None and r1["ahead"] == 1


# ---------------------------------------------------------------------
# chaos_smoke: the end-to-end acceptance criterion (train completes
# under corrupt sample + torn ckpt + resume; serve survives a
# transient device error)
# ---------------------------------------------------------------------

def test_chaos_smoke_tiny(capsys):
    mod = _load_script("chaos_smoke")
    rc = mod.main(["--tiny"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, rec
    assert rec["metric"] == "chaos_smoke" and rec["value"] == 1.0
    assert rec["config"]["events"] == {
        "sample_quarantine": 1, "ckpt_fallback": 1,
        "serve_retry": 1, "chaos_inject": 3}
    assert rec["config"]["summary_gates"] == {
        "quarantined_total": 1, "ckpt_fallback_total": 1}
    assert not chaos.enabled()  # the script cleans up after itself
