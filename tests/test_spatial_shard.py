"""Spatial (image-height) activation sharding over the 2-D mesh — the
long-context analog (SURVEY.md §5): GSPMD splits activations and the
correlation volume's query rows across chips and inserts conv halo
exchanges automatically.  Verified on the 8-virtual-device CPU mesh
against the purely data-parallel result.

The matrix covers every correlation implementation actual training can
select — ``allpairs`` (XLA einsums), ``allpairs_pallas`` (the TPU
training default, fused Pallas pyramid lookup) and ``pallas`` (the
on-demand beyond-HBM path) — with the FULL model.  Spatial sharding is an
XLA-path feature: GSPMD cannot partition a Mosaic kernel (interpret mode
on this CPU mesh lowers it to ordinary HLO and would hide that).  A
materialized pyramid has an XLA lookup to run instead, and the selection
(``models.raft.corr_impl_at``, told ``rows_split``) picks it under either
of its names (PR 27); the on-demand kernel has none, so
``make_train_step`` REFUSES ``shard_spatial=True`` with it (PR 22) and
these tests pin the refusal.  Under pure data parallelism the
Pallas kernels run per batch shard (``ops/pallas_util.per_data_shard``),
matching the reference's guarantee that DataParallel wraps the whole
model including the CUDA kernel (reference train.py:138,
core/corr.py:86).
"""

import jax
import numpy as np
import pytest

from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.models.raft import RAFT
from raft_tpu.parallel.mesh import make_mesh, shard_batch
from raft_tpu.train.optim import make_optimizer
from raft_tpu.train.step import init_state, make_train_step

pytestmark = pytest.mark.slow

H, W, B = 48, 64, 4


def _batch(rng, h=H, w=W, b=B):
    return {
        "image1": rng.uniform(0, 255, (b, h, w, 3)).astype(np.float32),
        "image2": rng.uniform(0, 255, (b, h, w, 3)).astype(np.float32),
        "flow": rng.standard_normal((b, h, w, 2)).astype(np.float32),
        "valid": np.ones((b, h, w), np.float32),
    }


@pytest.mark.parametrize("corr_impl",
                         ["allpairs", "allpairs_pallas", "pallas"])
def test_spatial_sharded_step_matches_dp(corr_impl):
    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices")
    model_cfg = RAFTConfig.full(corr_impl=corr_impl,
                                pallas_offtpu="interpret")
    cfg = TrainConfig(num_steps=10, batch_size=B, image_size=(H, W),
                      iters=2)
    model = RAFT(model_cfg)
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    rng = np.random.default_rng(0)
    batch = _batch(rng)
    key = jax.random.PRNGKey(1)

    mesh_dp = make_mesh(num_data=4, num_spatial=1,
                        devices=jax.devices()[:4])
    state = init_state(model, tx, jax.random.PRNGKey(0), (H, W))
    step_dp = make_train_step(model, tx, cfg, mesh_dp, donate=False)
    _, m_dp = step_dp(state, shard_batch(batch, mesh_dp), key)

    mesh_sp = make_mesh(num_data=4, num_spatial=2)
    if corr_impl == "pallas":
        with pytest.raises(ValueError, match="shard_spatial=True cannot"):
            make_train_step(model, tx, cfg, mesh_sp, donate=False,
                            shard_spatial=True)
        assert np.isfinite(float(m_dp["loss"]))  # DP ran the kernels
        return
    step_sp = make_train_step(model, tx, cfg, mesh_sp, donate=False,
                              shard_spatial=True)
    _, m_sp = step_sp(state, shard_batch(batch, mesh_sp, spatial=True),
                      key)

    np.testing.assert_allclose(float(m_dp["loss"]), float(m_sp["loss"]),
                               rtol=2e-4)
    np.testing.assert_allclose(float(m_dp["epe"]), float(m_sp["epe"]),
                               rtol=2e-4)


@pytest.mark.parametrize("corr_impl", ["allpairs_pallas", "pallas"])
def test_flagship_bf16_spatial_step_wide_aspect(corr_impl):
    """The SHIPPED bf16 training config (what cli/train.py resolves on
    TPU) on a realistic wide aspect ratio (96x256 ~ KITTI's 1:3.3):
    one data-parallel SPMD step must run the kernels per shard and
    produce a finite loss, and the spatially sharded form must be
    refused for the on-demand kernel and keep the XLA lookup (no
    ``pallas_call`` in the traced step) for the materialized pyramid.
    This pins the flagship Pallas configs' partitioning
    behavior so a regression can't ship silently (VERDICT r2, missing
    #2)."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices")
    h, w = 96, 256
    model_cfg = RAFTConfig.full(compute_dtype="bfloat16",
                                corr_impl=corr_impl,
                                pallas_offtpu="interpret")
    cfg = TrainConfig(num_steps=10, batch_size=B, image_size=(h, w),
                      iters=2)
    assert cfg.fused_loss
    model = RAFT(model_cfg)
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    state = init_state(model, tx, jax.random.PRNGKey(0), (h, w))
    batch = _batch(np.random.default_rng(0), h=h, w=w)
    mesh_sp = make_mesh(num_data=4, num_spatial=2)
    if corr_impl == "pallas":
        with pytest.raises(ValueError, match="shard_spatial=True cannot"):
            make_train_step(model, tx, cfg, mesh_sp, donate=False,
                            shard_spatial=True)
    else:
        step_sp = make_train_step(model, tx, cfg, mesh_sp, donate=False,
                                  shard_spatial=True)
        traced = jax.make_jaxpr(step_sp)(
            state, shard_batch(batch, mesh_sp, spatial=True),
            jax.random.PRNGKey(1))
        assert "pallas_call" not in str(traced)
    mesh = make_mesh(num_data=4, num_spatial=1,
                     devices=jax.devices()[:4])
    step = make_train_step(model, tx, cfg, mesh, donate=False)
    _, m = step(state, shard_batch(batch, mesh), jax.random.PRNGKey(1))
    assert np.isfinite(float(m["loss"])), float(m["loss"])
    assert np.isfinite(float(m["epe"])), float(m["epe"])
