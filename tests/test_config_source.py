"""The configuration a caller passes is the configuration that runs.

Until PR 30 a file in the home directory (``~/.cache/raft_tpu/tuning.json``,
or wherever an environment variable pointed) overrode every ``RAFTConfig``
knob still at its class default, and ``batching``/``slots``/``iters``/
``early_exit_threshold`` of a ``ServeConfig``, on every entry point.  The
registry is gone; this holds each former reader to what it is handed, with
such a file lying where the old code looked for it.
"""

import json

import jax
import jax.numpy as jnp
import pytest

from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.models.raft import RAFT

HW = (48, 64)
MODEL_KNOBS = {"corr_impl": "chunked", "scan_unroll": 1, "remat": False}
SERVE_KNOBS = dict(MODEL_KNOBS, iters=4, batching="slot")


@pytest.fixture
def legacy_registry(tmp_path, monkeypatch):
    """A registry file in the old format, one entry a kind, for this
    device, at both places the old code read."""
    device = jax.devices()[0].device_kind
    entries = {}
    for kind, knobs in (("train", MODEL_KNOBS), ("eval", MODEL_KNOBS),
                        ("serve", SERVE_KNOBS)):
        entries[f"{kind}|{device}|{HW[0]}x{HW[1]}|b2"] = {
            "kind": kind, "device_kind": device, "bucket_hw": list(HW),
            "batch": 2, "knobs": knobs,
            "provenance": {"tool": "by hand", "updated": 1.0}}
    path = tmp_path / ".cache" / "raft_tpu" / "tuning.json"
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps({"version": 1, "entries": entries}))
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv("RAFT_TUNING_REGISTRY", str(path))
    return path


def _stepped_config(model_cfg, monkeypatch):
    """The configuration of the model that ``make_train_step``'s step
    applies, read while the step traces (nothing is compiled)."""
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import init_state, make_train_step

    cfg = TrainConfig(num_steps=10, batch_size=2, image_size=HW, iters=2)
    model = RAFT(model_cfg)
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    state = jax.eval_shape(
        lambda: init_state(model, tx, jax.random.PRNGKey(0), HW))
    step = make_train_step(model, tx, cfg, donate=False)
    seen = []
    apply = RAFT.apply

    def spy(self, *args, **kwargs):
        seen.append(self.config)
        return apply(self, *args, **kwargs)

    monkeypatch.setattr(RAFT, "apply", spy)
    image = jax.ShapeDtypeStruct((2,) + HW + (3,), jnp.float32)
    batch = {"image1": image, "image2": image,
             "flow": jax.ShapeDtypeStruct((2,) + HW + (2,), jnp.float32),
             "valid": jax.ShapeDtypeStruct((2,) + HW, jnp.float32)}
    jax.eval_shape(step, state, batch, jax.random.PRNGKey(1))
    assert seen
    return seen


def _engine(model_cfg, serve_cfg):
    from raft_tpu.serve import InferenceEngine

    # the constructor only places the tree on the device: no program is
    # traced before a request or a warm-up
    return InferenceEngine({"params": {}}, model_cfg, serve_cfg)


@pytest.mark.parametrize("reader", ["make_train_step",
                                    "make_inference_model",
                                    "engine_model", "engine_serve_config"])
def test_a_legacy_registry_file_is_ignored(reader, legacy_registry,
                                           monkeypatch):
    from raft_tpu.evaluate import make_inference_model
    from raft_tpu.serve import ServeConfig

    assert legacy_registry.exists()
    model_cfg = RAFTConfig.small_model()
    # what the file would have changed sits at its default in what we pass
    assert (model_cfg.corr_impl, model_cfg.scan_unroll, model_cfg.remat) \
        == ("allpairs", 12, True)
    serve_cfg = ServeConfig(batch_sizes=(1,), max_batch=1)
    assert (serve_cfg.iters, serve_cfg.batching) == (32, "request")
    if reader == "make_train_step":
        assert set(_stepped_config(model_cfg, monkeypatch)) == {model_cfg}
    elif reader == "make_inference_model":
        # scan_unroll=1 is the function's own doing, whatever the file says
        assert make_inference_model(model_cfg).config \
            == model_cfg.replace(scan_unroll=1)
    elif reader == "engine_model":
        assert _engine(model_cfg, serve_cfg)._model_cfg \
            == model_cfg.replace(scan_unroll=1)
    else:
        assert _engine(model_cfg, serve_cfg).cfg == serve_cfg
