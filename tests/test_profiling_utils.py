"""Unit tests for the profiling utilities: HBM-limit artifact loader,
allocation-probe error classification, the persistent compile cache's
placement rule, and the serve engine's compile-count ledger (no device work)."""

import json
import os
import os.path as osp
import stat

import pytest

from raft_tpu.utils.profiling import (
    CompileCounter,
    enable_persistent_compile_cache,
    load_hbm_limit,
    probe_error_is_oom,
)


def test_load_hbm_limit_valid(tmp_path):
    p = tmp_path / "HBM_LIMIT.json"
    p.write_text(json.dumps(
        {"hbm_limit_gb": 15.48, "source": "allocation probe"}))
    assert load_hbm_limit(16.0, path=str(p)) == (15.48, "allocation probe")


def test_load_hbm_limit_missing(tmp_path):
    limit, src = load_hbm_limit(16.0, path=str(tmp_path / "nope.json"))
    assert limit == 16.0 and "no (valid)" in src


def test_load_hbm_limit_corrupt_and_degenerate(tmp_path):
    p = tmp_path / "HBM_LIMIT.json"
    p.write_text('{"hbm_limit_gb": 15.')           # truncated write
    assert load_hbm_limit(16.0, path=str(p)) \
        == (16.0, "corrupt HBM_LIMIT.json")
    p.write_text("[15.48]")                        # valid JSON, not a dict
    assert load_hbm_limit(16.0, path=str(p)) \
        == (16.0, "corrupt HBM_LIMIT.json")
    # "unavailable" marker (probe refused) is not a number -> fallback.
    p.write_text(json.dumps({"hbm_limit_gb": "unavailable"}))
    limit, _ = load_hbm_limit(None, path=str(p))
    assert limit is None
    # sub-GB degenerate value -> fallback (probe guard mirrored here).
    p.write_text(json.dumps({"hbm_limit_gb": 0.25}))
    assert load_hbm_limit(16.0, path=str(p))[0] == 16.0


def test_probe_error_classification():
    """Only OOM-shaped failures may terminate the allocation probe as a
    measurement; transport/backend errors are a broken probe."""
    assert probe_error_is_oom(
        RuntimeError("RESOURCE_EXHAUSTED: attempting to allocate ..."))
    assert probe_error_is_oom(
        RuntimeError("Resource exhausted: Out of memory while trying"))
    assert probe_error_is_oom(ValueError("TPU OOM allocating 256 MiB"))
    assert not probe_error_is_oom(
        RuntimeError("DEADLINE_EXCEEDED: socket closed"))
    assert not probe_error_is_oom(
        ConnectionError("connection reset by peer"))
    assert not probe_error_is_oom(RuntimeError("INTERNAL: mesh barrier"))


REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Snapshot/restore the two jax.config values the cache function
    may touch, so a test can never leave a cache enabled for the rest
    of the CPU suite."""
    import jax

    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield old_dir
    jax.config.update("jax_compilation_cache_dir", old_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      old_min)


def test_cache_env_set_code_sets_no_directory(tmp_path, monkeypatch,
                                              cache_config):
    """JAX_COMPILATION_CACHE_DIR set: JAX honours it by itself, so the
    function names that directory and neither creates nor configures
    one of its own."""
    import jax

    target = tmp_path / "placed-from-outside"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    assert enable_persistent_compile_cache(force=True) == str(target)
    assert jax.config.jax_compilation_cache_dir == cache_config
    assert not target.exists()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_cache_env_unset_fixed_path_in_checkout(monkeypatch,
                                                cache_config):
    """No env var: one fixed, git-ignored path inside the checkout,
    mode 0700 — nothing of a tempdir, uid, user, pid or time in it, and
    the retired private override variable is ignored."""
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("RAFT_JAX_CACHE_DIR", "/somewhere/else")
    want = osp.join(REPO, ".jax_cache")
    # force=True: the suite runs on the CPU backend, where the
    # un-forced call refuses (next test).  No compile happens before
    # the fixture restores the config, so nothing is written.
    assert enable_persistent_compile_cache(force=True) == want
    assert jax.config.jax_compilation_cache_dir == want
    assert stat.S_IMODE(os.stat(want).st_mode) == 0o700
    with open(osp.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_refused_on_cpu_backend(tmp_path, monkeypatch,
                                      cache_config):
    import jax

    assert jax.default_backend() == "cpu"  # tests/conftest.py
    for env in (None, str(tmp_path / "jaxcache")):
        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert enable_persistent_compile_cache() == ""
        assert jax.config.jax_compilation_cache_dir == cache_config
    assert not (tmp_path / "jaxcache").exists()


def test_step_profiler_anchors_window_on_resume(monkeypatch):
    """A checkpoint-resumed run first observes step N != 0; the trace
    window must anchor to that FIRST OBSERVED step (so the compile
    steps are still skipped), not to absolute step numbers."""
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    from raft_tpu.utils.profiling import StepProfiler

    sp = StepProfiler(trace_dir="/tmp/x", start_step=2, num_steps=1)
    traced = []
    for step in range(1000, 1010):  # resumed at step 1000
        sp.maybe_start(step)
        if sp._running:
            traced.append(step)
        sp.maybe_stop(step, sync_on=None)
    assert traced == [1002]  # 1000 + start_step, exactly num_steps long
    assert [c[0] for c in calls] == ["start", "stop"]
    assert sp._done
    sp.close()

    # disabled profiler: no anchoring, no trace calls
    calls.clear()
    off = StepProfiler(trace_dir=None)
    off.maybe_start(0)
    off.maybe_stop(0)
    assert calls == [] and off._first_step is None


def test_compile_counter_registry_mirror():
    """With a registry attached, compile events also land on a labeled
    telemetry counter (the serving engine's /metrics wiring)."""
    from raft_tpu.obs import MetricRegistry

    reg = MetricRegistry()
    c = CompileCounter(
        registry=reg, metric="raft_serve_compiles_total",
        labeler=lambda key: {"bucket": f"{key[0][0]}x{key[0][1]}",
                             "batch": str(key[1])})
    c.record(((440, 1024), 8))
    c.record(((440, 1024), 8))
    c.record(((368, 496), 4))
    m = reg.counter("raft_serve_compiles_total")
    assert m.value(bucket="440x1024", batch="8") == 2
    assert m.value(bucket="368x496", batch="4") == 1
    # ledger unchanged
    assert c.total() == 3

    # default labeler: one key=str(key) label
    reg2 = MetricRegistry()
    c2 = CompileCounter(registry=reg2)
    c2.record("step")
    assert reg2.counter("raft_compiles_total").value(key="step") == 1


def test_compile_counter():
    c = CompileCounter()
    key = ((440, 1024), 8)
    assert c.count(key) == 0 and c.total() == 0
    c.record(key)
    c.record(((376, 1248), 4))
    c.record(key)
    assert c.count(key) == 2
    assert c.counts() == {key: 2, ((376, 1248), 4): 1}
    assert c.total() == 3
    c.reset()
    assert c.counts() == {}
