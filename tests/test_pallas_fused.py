"""Fused Pallas kernels (PR 13): parity vs the unfused paths.

Kernel (a) ``pallas_pyramid_lookup_encode`` (pyramid lookup + motion
encoder convc1 + relu in one kernel) and kernel (b) the
``gru_gate_rh``/``gru_gate_blend`` ConvGRU gate chains must match the
unfused compositions they replace — forward AND gradients — across the
supported corr dtypes, with the quantized stop-gradient contract
(fnet gets zero grad through an int8 volume) re-pinned on the fused
path.  Runs in pallas interpreter mode on the CPU test backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.ops.corr import build_corr_pyramid_flat
from raft_tpu.ops.pallas_corr import (pallas_pyramid_lookup,
                                      pallas_pyramid_lookup_encode,
                                      pallas_pyramid_lookup_quantized)
from raft_tpu.ops.pallas_gru import gru_gate_blend, gru_gate_rh
from raft_tpu.ops.sampler import coords_grid

pytestmark = pytest.mark.slow

B, H, W, C = 2, 12, 16, 32
LEVELS, RADIUS = 3, 3
KK = LEVELS * (2 * RADIUS + 1) ** 2
F = 24  # convc1 out features (deliberately not a lane multiple)


def _setup(seed=0):
    rng = np.random.default_rng(seed)
    f1 = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    f2 = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    coords = coords_grid(B, H, W) + jnp.asarray(
        rng.uniform(-2, 2, (B, H, W, 2)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((KK, F)) * KK ** -0.5,
                    jnp.float32)
    b = jnp.asarray(rng.standard_normal((F,)) * 0.1, jnp.float32)
    return f1, f2, coords, w, b


def _unfused_encode(pyr, coords, w, b, quantized):
    lookup = (pallas_pyramid_lookup_quantized if quantized
              else pallas_pyramid_lookup)
    taps = lookup(pyr, coords, RADIUS, 128, True)
    return jax.nn.relu(jnp.einsum("bhwk,kf->bhwf", taps, w) + b)


# ---------------------------------------------------------------------
# kernel (a): lookup + convc1 + relu
# ---------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_lookup_encode_forward_matches_unfused(dtype):
    f1, f2, coords, w, b = _setup(0)
    pyr = build_corr_pyramid_flat(f1, f2, LEVELS, pad_q=128,
                                  out_dtype=dtype)
    want = np.asarray(
        _unfused_encode(pyr, coords, w, b, dtype == "int8"))
    got = np.asarray(pallas_pyramid_lookup_encode(
        pyr, coords, w, b, RADIUS, 128, True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_lookup_encode_grads_match_unfused(dtype):
    """Weight/bias/pyramid cotangents track the unfused composition
    (the fused backward delegates pyramid grads to the unfused
    lookup's vjp — same semantics by construction, pinned here)."""
    f1, f2, coords, w, b = _setup(1)
    pyr = build_corr_pyramid_flat(f1, f2, LEVELS, pad_q=128,
                                  out_dtype=dtype)

    def loss_fused(w_, b_, pyr_):
        out = pallas_pyramid_lookup_encode(pyr_, coords, w_, b_,
                                           RADIUS, 128, True)
        return jnp.sum(jnp.sin(out))

    def loss_unfused(w_, b_, pyr_):
        return jnp.sum(jnp.sin(_unfused_encode(pyr_, coords, w_, b_,
                                               False)))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(w, b, pyr)
    gu = jax.grad(loss_unfused, argnums=(0, 1, 2))(w, b, pyr)
    for a, want in zip(jax.tree.leaves(gf), jax.tree.leaves(gu)):
        a, want = np.asarray(a, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, want, rtol=1e-4, atol=1e-4)


def test_lookup_encode_int8_stop_gradient_repinned():
    """The quantized stop-gradient contract survives the fusion: conv
    weight/bias still learn (non-zero grads matching unfused), while
    the int8 codes and scales — and through them fnet — get exactly
    zero, and coords are detached."""
    f1, f2, coords, w, b = _setup(2)

    def loss(w_, b_, f1_, f2_, c_):
        pyr = build_corr_pyramid_flat(f1_, f2_, LEVELS, pad_q=128,
                                      out_dtype="int8")
        out = pallas_pyramid_lookup_encode(pyr, c_, w_, b_, RADIUS,
                                           128, True)
        return jnp.sum(out ** 2)

    gw, gb, g1, g2, gc = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
        w, b, f1, f2, coords)
    assert np.abs(np.asarray(gw)).max() > 0
    assert np.abs(np.asarray(gb)).max() > 0
    for g in (g1, g2, gc):
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).sum() == 0.0

    def loss_unfused(w_, b_):
        pyr = build_corr_pyramid_flat(f1, f2, LEVELS, pad_q=128,
                                      out_dtype="int8")
        return jnp.sum(_unfused_encode(pyr, coords, w_, b_, True) ** 2)

    uw, ub = jax.grad(loss_unfused, argnums=(0, 1))(w, b)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(uw),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(ub),
                               rtol=1e-4, atol=1e-4)


def test_lookup_encode_ragged_queries():
    """N = 192 with block_q 128 forces a ragged (padded) final block;
    padded queries must not leak into real outputs."""
    f1, f2, coords, w, b = _setup(3)
    pyr = build_corr_pyramid_flat(f1, f2, LEVELS, pad_q=128)
    a = np.asarray(pallas_pyramid_lookup_encode(pyr, coords, w, b,
                                                RADIUS, 128, True))
    pyr64 = build_corr_pyramid_flat(f1, f2, LEVELS, pad_q=64)
    bq64 = np.asarray(pallas_pyramid_lookup_encode(pyr64, coords, w, b,
                                                   RADIUS, 64, True))
    np.testing.assert_allclose(a, bq64, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------
# kernel (b): GRU gate chains
# ---------------------------------------------------------------------

def _gru_operands(seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    shape = (B, 6, 10, 48)
    mk = lambda: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    return mk(), mk(), mk()  # z_raw/r_raw, q_raw, h


def test_gru_gates_forward_match_unfused():
    r_raw, q_raw, h = _gru_operands(0)
    z_raw = q_raw  # any tensor of the right shape
    want_rh = np.asarray(jax.nn.sigmoid(r_raw) * h)
    got_rh = np.asarray(gru_gate_rh(r_raw, h, interpret=True))
    np.testing.assert_allclose(got_rh, want_rh, rtol=1e-6, atol=1e-6)
    sz = jax.nn.sigmoid(z_raw)
    want_bl = np.asarray((1 - sz) * h + sz * jnp.tanh(q_raw))
    got_bl = np.asarray(gru_gate_blend(z_raw, q_raw, h, interpret=True))
    np.testing.assert_allclose(got_bl, want_bl, rtol=1e-6, atol=1e-6)


def test_gru_gates_grads_match_unfused():
    z_raw, q_raw, h = _gru_operands(1)

    def loss_fused(z_, q_, h_):
        rh = gru_gate_rh(z_, h_, interpret=True)
        out = gru_gate_blend(z_, q_ + jnp.mean(rh), h_, interpret=True)
        return jnp.sum(jnp.sin(out))

    def loss_unfused(z_, q_, h_):
        rh = jax.nn.sigmoid(z_) * h_
        sz = jax.nn.sigmoid(z_)
        q2 = q_ + jnp.mean(rh)
        out = (1 - sz) * h_ + sz * jnp.tanh(q2)
        return jnp.sum(jnp.sin(out))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(z_raw, q_raw, h)
    gu = jax.grad(loss_unfused, argnums=(0, 1, 2))(z_raw, q_raw, h)
    for a, want in zip(gf, gu):
        a = np.asarray(a)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_gru_gates_bf16_storage():
    """bf16 operands: fp32 compute in VMEM, output cast follows h."""
    z_raw, q_raw, h = _gru_operands(2, jnp.bfloat16)
    got = gru_gate_blend(z_raw, q_raw, h, interpret=True)
    assert got.dtype == jnp.bfloat16
    sz = jax.nn.sigmoid(z_raw.astype(jnp.float32))
    want = ((1 - sz) * h.astype(jnp.float32)
            + sz * jnp.tanh(q_raw.astype(jnp.float32)))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------
# model level: both knobs on == both knobs off (same params)
# ---------------------------------------------------------------------

def _model_pair():
    from raft_tpu.config import RAFTConfig

    base = RAFTConfig.small_model(corr_impl="allpairs_pallas",
                                  pallas_offtpu="interpret")
    fused = base.replace(fused_lookup_encoder=True, fused_gru=True)
    from raft_tpu.models.raft import corr_impl_at

    # the fused lookup+encoder engages where the map runs the kernel
    assert corr_impl_at(fused, 8, 12) == "allpairs_pallas"
    assert fused.resolved_fused_gru is True
    return base, fused


def test_model_fused_knobs_share_param_tree_and_match_eval():
    """One param set drives both configs: identical trees, and the
    test-mode forward agrees (the registry may flip the knobs on a
    compiled replica without a re-init or checkpoint surgery)."""
    from raft_tpu.models.raft import RAFT

    base, fused = _model_pair()
    rng = jax.random.PRNGKey(0)
    img1 = jnp.asarray(
        np.random.default_rng(3).uniform(0, 255, (1, 48, 64, 3)),
        jnp.float32)
    img2 = jnp.asarray(
        np.random.default_rng(4).uniform(0, 255, (1, 48, 64, 3)),
        jnp.float32)
    vb = RAFT(base).init({"params": rng, "dropout": rng}, img1, img2,
                         iters=1)
    vf = RAFT(fused).init({"params": rng, "dropout": rng}, img1, img2,
                          iters=1)
    assert (jax.tree_util.tree_structure(vb)
            == jax.tree_util.tree_structure(vf))
    out_b = RAFT(base).apply(vb, img1, img2, iters=2, test_mode=True)
    out_f = RAFT(fused).apply(vb, img1, img2, iters=2, test_mode=True)
    np.testing.assert_allclose(np.asarray(out_f[1]),
                               np.asarray(out_b[1]),
                               rtol=1e-4, atol=1e-4)


def test_model_fused_train_grads_match_unfused():
    """Train-mode gradients through BOTH fused kernels are finite and
    match the unfused model within tolerance."""
    from raft_tpu.models.raft import RAFT

    base, fused = _model_pair()
    rng = jax.random.PRNGKey(0)
    img1 = jnp.asarray(
        np.random.default_rng(5).uniform(0, 255, (1, 48, 64, 3)),
        jnp.float32)
    img2 = jnp.asarray(
        np.random.default_rng(6).uniform(0, 255, (1, 48, 64, 3)),
        jnp.float32)
    variables = RAFT(base).init({"params": rng, "dropout": rng},
                                img1, img2, iters=1)

    def loss(params, cfg):
        flows = RAFT(cfg).apply({"params": params}, img1, img2, iters=2,
                                rngs={"dropout": rng})
        return jnp.mean(jnp.abs(jnp.stack(flows)))

    gb = jax.grad(loss)(variables["params"], base)
    gf = jax.grad(loss)(variables["params"], fused)
    for a, want in zip(jax.tree.leaves(gf), jax.tree.leaves(gb)):
        a = np.asarray(a)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, np.asarray(want),
                                   rtol=2e-3, atol=2e-4)
