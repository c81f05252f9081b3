"""Tuning-registry tests: round-trip, nearest-bucket fallback, override
precedence, provenance stamps — and the `scripts/autotune.py --tiny`
smoke (sweep -> persist -> cache hit -> consumption by a default-knobs
train step), the tier-1 wiring of the autotune loop."""

import importlib.util
import json
import os
import os.path as osp

import pytest

from raft_tpu import tuning
from raft_tpu.config import RAFTConfig

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, osp.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def reg(tmp_path):
    return str(tmp_path / "tuning.json")


def _save(reg, kind="train", hw=(368, 496), batch=16, knobs=None,
          device=None, prov=None):
    return tuning.save_entry(kind, hw, batch,
                             knobs or {"scan_unroll": 6, "remat": False},
                             provenance=prov, path=reg, device=device)


def test_round_trip_exact_hit(reg):
    key = _save(reg)
    hit = tuning.lookup("train", (368, 496), 16, path=reg)
    assert hit is not None
    got_key, entry, exact = hit
    assert got_key == key and exact
    assert entry["knobs"] == {"scan_unroll": 6, "remat": False}
    assert entry["provenance"]["host"]  # provenance always stamped
    assert entry["provenance"]["updated"] > 0


def test_save_rejects_unknown_knobs(reg):
    with pytest.raises(ValueError, match="unknown tunable knob"):
        _save(reg, knobs={"scan_unroll": 6, "warp_factor": 9})


def test_nearest_bucket_fallback(reg):
    _save(reg, hw=(368, 496), batch=16,
          knobs={"scan_unroll": 6})
    _save(reg, hw=(288, 960), batch=16,
          knobs={"scan_unroll": 1})
    # a chairs-like query snaps to the chairs-crop entry ...
    key, entry, exact = tuning.lookup("train", (380, 520), 16, path=reg)
    assert not exact
    assert entry["bucket_hw"] == [368, 496]
    # ... a panoramic kitti-like query to the kitti-crop entry
    key, entry, exact = tuning.lookup("train", (300, 940), 16, path=reg)
    assert not exact
    assert entry["bucket_hw"] == [288, 960]
    # batch distance is a tie-breaker within the same bucket
    _save(reg, hw=(368, 496), batch=4, knobs={"scan_unroll": 2})
    key, entry, exact = tuning.lookup("train", (368, 496), 5, path=reg)
    assert not exact
    assert entry["batch"] == 4


def test_no_cross_device_or_cross_kind_fallback(reg):
    _save(reg, device="TPU v5e")
    assert tuning.lookup("train", (368, 496), 16, device="cpu",
                         path=reg) is None
    _save(reg, kind="train", device="cpu")
    assert tuning.lookup("eval", (368, 496), 16, device="cpu",
                         path=reg) is None


def test_kind_preference_order(reg):
    _save(reg, kind="eval", knobs={"corr_dtype": "float32"})
    # serve falls back to eval ...
    key, entry, _ = tuning.lookup(("serve", "eval"), (368, 496), 16,
                                  path=reg)
    assert entry["kind"] == "eval"
    # ... until a serve entry exists
    _save(reg, kind="serve", knobs={"corr_dtype": "bfloat16"})
    key, entry, _ = tuning.lookup(("serve", "eval"), (368, 496), 16,
                                  path=reg)
    assert entry["kind"] == "serve"


def test_resolve_applies_only_defaults_and_is_idempotent(reg):
    _save(reg, knobs={"scan_unroll": 6, "remat": False,
                      "fuse_upsample_in_scan": True})
    cfg = RAFTConfig.full()
    tuned, info = tuning.resolve_config(cfg, "train", (368, 496), 16,
                                        path=reg)
    assert info.tuned and info.exact
    assert tuned.scan_unroll == 6 and tuned.remat is False
    assert tuned.fuse_upsample_in_scan is True
    assert set(info.applied) == {"scan_unroll", "remat",
                                 "fuse_upsample_in_scan"}
    # second resolve: nothing left to change, config unchanged
    tuned2, info2 = tuning.resolve_config(tuned, "train", (368, 496), 16,
                                          path=reg)
    assert tuned2 == tuned and info2.applied == {}


def test_user_pinned_knob_beats_registry(reg):
    _save(reg, knobs={"scan_unroll": 6, "remat": False})
    cfg = RAFTConfig.full(scan_unroll=3)   # != class default -> pinned
    tuned, info = tuning.resolve_config(cfg, "train", (368, 496), 16,
                                        path=reg)
    assert tuned.scan_unroll == 3
    assert info.pinned == {"scan_unroll": 3}
    assert info.applied == {"remat": False}


def test_env_disable(reg, monkeypatch):
    _save(reg)
    monkeypatch.setenv(tuning.ENV_DISABLE, "0")
    cfg = RAFTConfig.full()
    tuned, info = tuning.resolve_config(cfg, "train", (368, 496), 16,
                                        path=reg)
    assert not info.tuned and tuned == cfg


def test_corrupt_registry_tolerated(reg):
    with open(reg, "w") as f:
        f.write("{not json")
    with pytest.warns(UserWarning, match="unreadable"):
        assert tuning.lookup("train", (368, 496), 16, path=reg) is None
    # and the next save heals the file
    _save(reg)
    assert tuning.lookup("train", (368, 496), 16, path=reg) is not None


def test_stamp_fields(reg):
    _save(reg)
    _, info = tuning.resolve_config(RAFTConfig.full(), "train",
                                    (368, 496), 16, path=reg)
    stamp = info.stamp()
    assert stamp["tuned"] is True
    assert stamp["tuning_key"] == "train|cpu|368x496|b16"
    assert stamp["tuning_registry_hash"] == tuning.registry_file_hash(reg)
    # nearest-bucket lookups say so
    _, info2 = tuning.resolve_config(RAFTConfig.full(), "train",
                                     (400, 720), 8, path=reg)
    assert info2.stamp()["tuning_fallback"] == "nearest-bucket"
    # and no-registry runs stamp untuned
    assert tuning.TuningInfo(tuned=False).stamp() == {"tuned": False}


def test_run_config_carries_tuning_stamp(tmp_path):
    """The telemetry run_config event carries the stamp, and
    telemetry_summary folds it into its config block (bench-series
    attribution for real runs)."""
    from raft_tpu.obs.train import TrainTelemetry

    telem = TrainTelemetry(str(tmp_path), batch_size=4, num_devices=1,
                           image_size=(368, 496),
                           tuning_stamp={"tuned": True,
                                         "tuning_key": "train|cpu|x|b4",
                                         "tuning_registry_hash": "abc"})
    telem.start(start_step=0, num_steps=10)
    telem.record_step({"step": 1, "t_start": 0.0, "t_end": 0.5,
                       "stages": {"input_wait": 0.0}})
    telem.sink.close()
    ts = _load_script("telemetry_summary")
    (run_cfg, steps, health, faults, spans, costs, quality,
     retires, incidents, fabric) = ts.last_run(
        ts.iter_records(str(tmp_path)))
    assert run_cfg["tuned"] is True
    out = ts.summarize(run_cfg, steps, health, faults, spans, costs,
                       quality, retires, skip=0)
    assert out["config"]["tuned"] is True
    assert out["config"]["tuning_key"] == "train|cpu|x|b4"
    assert out["config"]["tuning_registry_hash"] == "abc"


def test_require_tuned_gate():
    cr = _load_script("check_regression")
    rec = {"metric": "m", "value": 30.0, "config": {"tuned": True}}
    failures, _ = cr.check({"m": [rec]}, require_tuned=True)
    assert not failures
    rec2 = {"metric": "m", "value": 30.0, "config": {}}
    failures, _ = cr.check({"m": [rec2]}, require_tuned=True)
    assert failures and "tuned" in failures[0]


# ---------------------------------------------------------------------
# The end-to-end autotune loop (tier-1 acceptance wiring): 2-point
# sweep -> registry write -> second invocation cache hit -> a tiny
# default-knobs train step CONSUMES the entry.
# ---------------------------------------------------------------------

def test_autotune_tiny_smoke(tmp_path, capsys):
    mod = _load_script("autotune")
    rc = mod.main(["--tiny", "--out", str(tmp_path / "tuning.json")])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, rec
    assert rec["metric"] == "autotune_tiny" and rec["value"] == 1.0
    cfg = rec["config"]
    assert cfg["first_cache_hit"] is False
    assert cfg["second_cache_hit"] is True
    assert cfg["consumed_by_train_step"] is True
    assert cfg["tiny_step_loss_finite"] is True
    assert cfg["registry_hash"]
    # the registry file itself is sane and exact-keyed
    hit = tuning.lookup("train", (48, 64), 2,
                        path=str(tmp_path / "tuning.json"))
    assert hit is not None and hit[2]
    assert hit[1]["provenance"]["tool"] == "scripts/autotune.py"
    assert os.environ.get(tuning.ENV_DISABLE) is None  # cleaned up


def test_autotune_seed_known(tmp_path, capsys):
    """--seed-known installs the measured r03 winners, labeled as
    seeded (no sweep_id: a later real sweep re-measures, never
    cache-hits)."""
    mod = _load_script("autotune")
    out = str(tmp_path / "tuning.json")
    rc = mod.main(["--seed-known", "--out", out])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and rec["metric"] == "autotune_seed_known"
    hit = tuning.lookup("train", (368, 496), 16, path=out)
    assert hit is not None and hit[2]
    assert hit[1]["knobs"]["scan_unroll"] == 12
    assert hit[1]["knobs"]["corr_impl"] == "allpairs_pallas"
    assert hit[1]["provenance"]["mode"] == "seed-known"
    assert "sweep_id" not in hit[1]["provenance"]


def test_fallback_distance_cutoff(reg):
    """Nearest-bucket transfer is bounded: the chairs winners must NOT
    leak to beyond-HBM shapes (unroll-12 crashed the 1440x2560 compile,
    round 4) or to toy shapes — past the cutoff the config defaults are
    the safer guess."""
    _save(reg, hw=(368, 496), batch=16, knobs={"scan_unroll": 12})
    assert tuning.lookup("train", (1440, 2560), 1, path=reg) is None
    assert tuning.lookup("train", (48, 64), 2, path=reg) is None
    # things crop stays within reach
    hit = tuning.lookup("train", (400, 720), 8, path=reg)
    assert hit is not None and not hit[2]


# ---------------------------------------------------------------------
# Serve-knob tuning (scripts/autotune.py --kind serve): the serve-only
# knob surface (batching/slots/early_exit_threshold) persists under
# kind="serve" and resolves onto ServeConfig with the same precedence
# rules as model knobs.
# ---------------------------------------------------------------------

def test_save_serve_knobs_gated_by_kind(reg):
    key = _save(reg, kind="serve",
                knobs={"batching": "slot", "slots": 16,
                       "early_exit_threshold": 0.05})
    assert key
    # serve-only knobs are rejected for every other kind
    with pytest.raises(ValueError, match="unknown tunable knob"):
        _save(reg, kind="train", knobs={"slots": 16})
    with pytest.raises(ValueError, match="unknown tunable knob"):
        _save(reg, kind="eval", knobs={"batching": "slot"})


def test_resolve_serve_config_applies_and_pins(reg):
    from raft_tpu.serve import ServeConfig

    _save(reg, kind="serve",
          knobs={"batching": "slot", "slots": 16,
                 "early_exit_threshold": 0.05})
    tuned, info = tuning.resolve_serve_config(ServeConfig(), path=reg)
    assert info.tuned
    assert tuned.batching == "slot" and tuned.slots == 16
    assert tuned.early_exit_threshold == 0.05
    assert set(info.applied) == {"batching", "slots",
                                 "early_exit_threshold"}
    # explicit user knobs beat the registry (pinned, not overwritten)
    tuned2, info2 = tuning.resolve_serve_config(
        ServeConfig(slots=4), path=reg)
    assert tuned2.slots == 4 and info2.pinned == {"slots": 4}
    assert "slots" not in info2.applied
    # no registry entry -> untouched config
    tuned3, info3 = tuning.resolve_serve_config(
        ServeConfig(), path=reg.replace("tuning", "absent"))
    assert not info3.tuned and tuned3 == ServeConfig()


def test_resolve_serve_config_env_disable(reg, monkeypatch):
    from raft_tpu.serve import ServeConfig

    _save(reg, kind="serve", knobs={"slots": 16})
    monkeypatch.setenv(tuning.ENV_DISABLE, "0")
    tuned, info = tuning.resolve_serve_config(ServeConfig(), path=reg)
    assert not info.tuned and tuned == ServeConfig()


def test_early_exit_gate():
    cr = _load_script("check_regression")
    rec = {"metric": "m", "value": 30.0,
           "config": {"early_exit_epe_delta": 0.02}}
    failures, _ = cr.check({"m": [rec]}, max_early_exit_epe_delta=0.05)
    assert not failures
    rec2 = {"metric": "m", "value": 30.0,
            "config": {"early_exit_epe_delta": 0.2}}
    failures, _ = cr.check({"m": [rec2]}, max_early_exit_epe_delta=0.05)
    assert failures and "early-exit" in failures[0]
    # the gate refuses to pass vacuously
    failures, _ = cr.check({"m": [{"metric": "m", "value": 1.0}]},
                           max_early_exit_epe_delta=0.05)
    assert failures and "did not run" in failures[0]
