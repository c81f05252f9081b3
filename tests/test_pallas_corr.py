"""Pallas correlation kernel: equivalence vs the materialized XLA path and
real gradients (the reference never tests that its two corr paths agree,
SURVEY.md §4; its CUDA backward is unwired, C6 — ours must be correct).

Runs in pallas interpreter mode on the CPU test backend.  The window
backward's tests are tier-1; every other test here is in the slow tier.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.ops.corr import (build_corr_pyramid, chunked_corr_lookup,
                               corr_lookup, pool_fmap_pyramid)
from raft_tpu.ops.pallas_corr import pallas_corr_lookup
from raft_tpu.ops.sampler import coords_grid


B, H, W, C = 2, 12, 16, 32
LEVELS, RADIUS = 3, 3


def _setup(seed=0):
    rng = np.random.default_rng(seed)
    f1 = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    f2 = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    coords = coords_grid(B, H, W) + jnp.asarray(
        rng.uniform(-2, 2, (B, H, W, 2)), jnp.float32)
    return f1, f2, coords


@pytest.mark.slow
def test_matches_materialized_lookup():
    f1, f2, coords = _setup()
    pyr = build_corr_pyramid(f1, f2, LEVELS)
    want = np.asarray(corr_lookup(pyr, coords, RADIUS))
    f2_pyr = tuple(pool_fmap_pyramid(f2, LEVELS))
    got = np.asarray(pallas_corr_lookup(f1, f2_pyr, coords, RADIUS, 64))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_matches_chunked_lookup():
    f1, f2, coords = _setup(1)
    f2_pyr = tuple(pool_fmap_pyramid(f2, LEVELS))
    want = np.asarray(chunked_corr_lookup(f1, f2_pyr, coords, RADIUS,
                                          block_size=32))
    got = np.asarray(pallas_corr_lookup(f1, f2_pyr, coords, RADIUS, 64))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_padding_and_block_sizes():
    # N = 12*16 = 192; block 128 forces a ragged final block.
    f1, f2, coords = _setup(2)
    f2_pyr = tuple(pool_fmap_pyramid(f2, LEVELS))
    a = np.asarray(pallas_corr_lookup(f1, f2_pyr, coords, RADIUS, 128))
    b = np.asarray(pallas_corr_lookup(f1, f2_pyr, coords, RADIUS, 64))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.slow
def test_gradients_match_xla_path():
    f1, f2, coords = _setup(3)

    def loss_pallas(f1_, f2_):
        pyr = tuple(pool_fmap_pyramid(f2_, LEVELS))
        out = pallas_corr_lookup(f1_, pyr, coords, RADIUS, 64)
        return jnp.sum(jnp.sin(out))

    def loss_xla(f1_, f2_):
        pyr = build_corr_pyramid(f1_, f2_, LEVELS)
        out = corr_lookup(pyr, coords, RADIUS)
        return jnp.sum(jnp.sin(out))

    gp = jax.grad(loss_pallas, argnums=(0, 1))(f1, f2)
    gx = jax.grad(loss_xla, argnums=(0, 1))(f1, f2)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def _grad_pair(coords, f1, f2):
    def loss_pallas(f1_, f2_):
        pyr = tuple(pool_fmap_pyramid(f2_, LEVELS))
        out = pallas_corr_lookup(f1_, pyr, coords, RADIUS, 64)
        return jnp.sum(jnp.sin(out))

    def loss_xla(f1_, f2_):
        pyr = build_corr_pyramid(f1_, f2_, LEVELS)
        out = corr_lookup(pyr, coords, RADIUS)
        return jnp.sum(jnp.sin(out))

    return (jax.grad(loss_pallas, argnums=(0, 1))(f1, f2),
            jax.grad(loss_xla, argnums=(0, 1))(f1, f2))


@pytest.mark.slow
def test_blocked_bwd_all_levels_match_xla(monkeypatch):
    """Force EVERY level onto the blocked backward pair (the beyond-HBM
    tiling, round-4): gradients must still match the XLA path."""
    from raft_tpu.ops import pallas_corr as pc

    monkeypatch.setattr(pc, "_FUSED_BWD_BUDGET", 0)
    monkeypatch.setattr(pc, "_BWD_BLOCK_Q", 64)
    f1, f2, coords = _setup(7)
    gp, gx = _grad_pair(coords, f1, f2)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_blocked_bwd_mixed_partition_matches_xla(monkeypatch):
    """Budget admits only the SMALL levels into the fused kernel, so
    level 0 runs blocked while levels 1.. stay fused — the partition the
    auto heuristic picks at 1088x1920+."""
    from raft_tpu.ops import pallas_corr as pc
    from raft_tpu.ops.corr import pool_fmap_pyramid as pool

    f1, f2, coords = _setup(8)
    nonempty = [(lvl, x) for lvl, x in enumerate(pool(f2, LEVELS))]
    k = 2 * RADIUS + 1
    small_est = pc._fused_bwd_est(nonempty[1:], 64, k)
    full_est = pc._fused_bwd_est(nonempty, 64, k)
    assert small_est < full_est
    monkeypatch.setattr(pc, "_FUSED_BWD_BUDGET", small_est + 1)
    monkeypatch.setattr(pc, "_BWD_BLOCK_Q", 64)
    gp, gx = _grad_pair(coords, f1, f2)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_blocked_bwd_large_flow_offsets(monkeypatch):
    """Blocked backward with coords far from the raster grid (windows in
    arbitrary tiles, some fully out of range) — exercises the
    _tile_overlaps skip logic for both hit and miss tiles."""
    from raft_tpu.ops import pallas_corr as pc

    monkeypatch.setattr(pc, "_FUSED_BWD_BUDGET", 0)
    monkeypatch.setattr(pc, "_BWD_BLOCK_Q", 64)
    rng = np.random.default_rng(9)
    f1 = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    f2 = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    coords = coords_grid(B, H, W) + jnp.asarray(
        rng.uniform(-20, 20, (B, H, W, 2)), jnp.float32)
    gp, gx = _grad_pair(coords, f1, f2)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_coords_gradient_is_zero():
    f1, f2, coords = _setup(4)
    f2_pyr = tuple(pool_fmap_pyramid(f2, LEVELS))

    g = jax.grad(lambda c: jnp.sum(
        pallas_corr_lookup(f1, f2_pyr, c, RADIUS, 64)))(coords)
    assert np.all(np.asarray(g) == 0.0)


@pytest.mark.slow
def test_model_with_pallas_corr_runs():
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    cfg = RAFTConfig.small_model(corr_impl="pallas",
                                 pallas_offtpu="interpret")
    model = RAFT(cfg)
    rng = jax.random.PRNGKey(0)
    img = jax.random.uniform(rng, (1, 48, 64, 3)) * 255.0
    variables = model.init({"params": rng, "dropout": rng}, img, img,
                           iters=1)
    flows = model.apply(variables, img, img, iters=2)
    assert flows.shape == (2, 1, 48, 64, 2)
    assert np.isfinite(np.asarray(flows)).all()

    cfg_ref = RAFTConfig.small_model(corr_impl="allpairs")
    flows_ref = RAFT(cfg_ref).apply(variables, img, img, iters=2)
    np.testing.assert_allclose(np.asarray(flows), np.asarray(flows_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Fused materialized-pyramid lookup (allpairs_pallas path)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pyramid_lookup_matches_xla():
    from raft_tpu.ops.corr import build_corr_pyramid_flat
    from raft_tpu.ops.pallas_corr import pallas_pyramid_lookup

    f1, f2, coords = _setup(2)
    want = np.asarray(
        corr_lookup(build_corr_pyramid(f1, f2, LEVELS), coords, RADIUS))
    pyr = build_corr_pyramid_flat(f1, f2, LEVELS, pad_q=64)
    got = np.asarray(pallas_pyramid_lookup(pyr, coords, RADIUS, 64))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_pyramid_lookup_out_of_range_coords_zero():
    from raft_tpu.ops.corr import build_corr_pyramid_flat
    from raft_tpu.ops.pallas_corr import pallas_pyramid_lookup

    f1, f2, _ = _setup(3)
    coords = jnp.full((B, H, W, 2), -100.0)   # every window out of range
    pyr = build_corr_pyramid_flat(f1, f2, LEVELS, pad_q=64)
    got = np.asarray(pallas_pyramid_lookup(pyr, coords, RADIUS, 64))
    assert np.all(got == 0.0)


@pytest.mark.slow
def test_pyramid_lookup_grads_match_xla():
    from raft_tpu.ops.corr import build_corr_pyramid_flat
    from raft_tpu.ops.pallas_corr import pallas_pyramid_lookup

    f1, f2, coords = _setup(4)

    def loss_ref(f1, f2):
        p = build_corr_pyramid(f1, f2, LEVELS)
        return jnp.sum(jnp.sin(corr_lookup(p, coords, RADIUS)))

    def loss_new(f1, f2):
        p = build_corr_pyramid_flat(f1, f2, LEVELS, pad_q=64)
        return jnp.sum(jnp.sin(pallas_pyramid_lookup(p, coords, RADIUS,
                                                     64)))

    g_ref = jax.grad(loss_ref, argnums=(0, 1))(f1, f2)
    g_new = jax.grad(loss_new, argnums=(0, 1))(f1, f2)
    np.testing.assert_allclose(np.asarray(g_new[0]), np.asarray(g_ref[0]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g_new[1]), np.asarray(g_ref[1]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_pyramid_lookup_bf16_storage_close_and_grad_dtype():
    """corr_dtype='bfloat16' (bf16-stored pyramid, fp32 in-kernel
    accumulation): values track the fp32 path within bf16 rounding and
    the custom_vjp returns bf16 cotangents matching the primal dtype."""
    from raft_tpu.ops.corr import build_corr_pyramid_flat
    from raft_tpu.ops.pallas_corr import pallas_pyramid_lookup

    f1, f2, coords = _setup(6)
    want = np.asarray(
        corr_lookup(build_corr_pyramid(f1, f2, LEVELS), coords, RADIUS))
    pyr16 = build_corr_pyramid_flat(f1, f2, LEVELS, pad_q=64,
                                    out_dtype=jnp.bfloat16)
    assert all(p.dtype == jnp.bfloat16 for p in pyr16)
    got = np.asarray(pallas_pyramid_lookup(pyr16, coords, RADIUS, 64))
    # corr values are O(sqrt(C)); bf16 storage rounds at ~0.4% relative,
    # and each tap mixes <= 4 * levels stored values.
    np.testing.assert_allclose(got, want, rtol=0.02, atol=0.05)

    def loss(pyr):
        return jnp.sum(jnp.sin(pallas_pyramid_lookup(pyr, coords, RADIUS,
                                                     64)))

    dpyr = jax.grad(loss)(pyr16)
    assert all(d.dtype == jnp.bfloat16 for d in dpyr)
    assert all(bool(jnp.isfinite(d.astype(jnp.float32)).all())
               for d in dpyr)


@pytest.mark.slow
def test_model_allpairs_pallas_matches_allpairs():
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    rng = np.random.default_rng(5)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, 48, 64, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, 48, 64, 3)), jnp.float32)
    base = RAFTConfig.full(pallas_offtpu="interpret")
    v = RAFT(base).init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(0)},
                        img1, img2, iters=1)
    outs = {}
    for impl in ("allpairs", "allpairs_pallas"):
        model = RAFT(base.replace(corr_impl=impl))
        outs[impl] = np.asarray(
            model.apply(v, img1, img2, iters=2, test_mode=True)[1])
    np.testing.assert_allclose(outs["allpairs_pallas"], outs["allpairs"],
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Off-TPU fallback dispatch (pallas_offtpu='fallback', the default)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_offtpu_fallback_resolves_to_xla_impls():
    """Off-TPU, the default config must dispatch XLA equivalents instead
    of the (pathologically slow) Pallas interpreter; 'interpret' keeps
    the Pallas paths (VERDICT r4 weak #6)."""
    from raft_tpu.config import RAFTConfig

    assert jax.default_backend() != "tpu"  # conftest forces cpu
    cfg = RAFTConfig.full(corr_impl="allpairs_pallas",
                          upsample_loss_kernel="pallas")
    assert cfg.resolved_corr_impl == "allpairs"
    assert cfg.resolved_upsample_loss_kernel == "xla"
    assert RAFTConfig.full(corr_impl="pallas").resolved_corr_impl \
        == "chunked"
    keep = cfg.replace(pallas_offtpu="interpret")
    assert keep.resolved_corr_impl == "allpairs_pallas"
    assert keep.resolved_upsample_loss_kernel == "pallas"
    # XLA impls resolve to themselves either way.
    assert RAFTConfig.full().resolved_corr_impl == "allpairs"


@pytest.mark.slow
def test_offtpu_fallback_model_runs_without_pallas():
    """A model configured for the TPU pallas path must run off-TPU via
    the fallback (and match the XLA impl it falls back to)."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    rng = np.random.default_rng(7)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, 48, 64, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, 48, 64, 3)), jnp.float32)
    cfg_p = RAFTConfig.full(corr_impl="allpairs_pallas")
    v = RAFT(cfg_p).init({"params": jax.random.PRNGKey(0),
                          "dropout": jax.random.PRNGKey(0)},
                         img1, img2, iters=1)
    out_p = RAFT(cfg_p).apply(v, img1, img2, iters=2, test_mode=True)[1]
    out_x = RAFT(RAFTConfig.full()).apply(v, img1, img2, iters=2,
                                          test_mode=True)[1]
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_x))


# ---------------------------------------------------------------------
# Quantized (int8) materialized-pyramid lookup: the Pallas kernel path.
# ---------------------------------------------------------------------

@pytest.mark.slow
def test_quantized_pyramid_lookup_matches_fp32_oracle():
    """int8 storage through the fused Pallas kernel tracks the fp32 XLA
    oracle within the calibration-scale bound, and agrees with the XLA
    int8 path (same codes, same fused dequant) to float tolerance."""
    from raft_tpu.ops.corr import build_corr_pyramid, build_corr_pyramid_flat
    from raft_tpu.ops.pallas_corr import pallas_pyramid_lookup_quantized

    f1, f2, coords = _setup(5)
    want = np.asarray(
        corr_lookup(build_corr_pyramid(f1, f2, LEVELS), coords, RADIUS))
    pyr8 = build_corr_pyramid(f1, f2, LEVELS, out_dtype="int8")
    xla8 = np.asarray(corr_lookup(pyr8, coords, RADIUS))
    pyrf = build_corr_pyramid_flat(f1, f2, LEVELS, pad_q=128,
                                   out_dtype="int8")
    assert all(lv.values.dtype == jnp.int8 for lv in pyrf)
    got = np.asarray(pallas_pyramid_lookup_quantized(
        pyrf, coords, RADIUS, 128, True))
    max_scale = max(float(np.asarray(lv.scale).max()) for lv in pyr8)
    assert np.abs(got - want).max() <= 0.5 * max_scale * 1.05
    np.testing.assert_allclose(got, xla8, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_quantized_pyramid_lookup_is_primal_only():
    """No custom_vjp on the quantized lookup by design: the volume is
    stop_gradient'd at the quantize boundary and coords are detached, so
    grads of a loss THROUGH the lookup w.r.t. the feature maps and
    coords are exactly zero — and tracing them must not error."""
    from raft_tpu.ops.corr import build_corr_pyramid_flat
    from raft_tpu.ops.pallas_corr import pallas_pyramid_lookup_quantized

    f1, f2, coords = _setup(6)

    def loss(f1j, f2j, c):
        pyr = build_corr_pyramid_flat(f1j, f2j, LEVELS, pad_q=128,
                                      out_dtype="int8")
        out = pallas_pyramid_lookup_quantized(pyr, c, RADIUS, 128, True)
        return jnp.sum(out ** 2)

    g1, g2, gc = jax.grad(loss, argnums=(0, 1, 2))(f1, f2, coords)
    for g in (g1, g2, gc):
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).sum() == 0.0


# --- the window backward: the features' gradients over the windows read ----

def _window_case(hw, levels, seed):
    """Maps, level-0 centres and a tap cotangent at ``hw``; some windows
    leave the map, a few queries sit far outside it."""
    rng = np.random.default_rng(seed)
    h, w = hw
    f1 = jnp.asarray(rng.standard_normal((B, h, w, C)), jnp.float32)
    f2 = jnp.asarray(rng.standard_normal((B, h, w, C)), jnp.float32)
    coords = coords_grid(B, h, w) + jnp.asarray(
        rng.uniform(-6, 6, (B, h, w, 2)), jnp.float32)
    coords = coords.at[0, 0, :4].set(jnp.asarray([-40.0, 300.0]))
    coords = coords.at[1, -1, -3:].set(jnp.asarray([w + 3.5, -4.25]))
    g = jnp.asarray(rng.standard_normal((B, h, w, levels * 81)),
                    jnp.float32)
    return f1, f2, coords, g


@pytest.mark.parametrize("hw,block_q", [
    ((24, 32), 128),
    # 600 queries: the window kernels' last block of 512 is padded
    ((20, 30), 512),
])
def test_window_backward_matches_the_dense_gradient(hw, block_q,
                                                    monkeypatch):
    """``pallas_pyramid_lookup_windows``'s gradients of both feature maps
    against ``jax.grad`` through the dense path (the pyramid built from
    the maps, ``pallas_pyramid_lookup``'s volume cotangent): one level,
    windows that leave the map, queries far outside it, padded queries."""
    from raft_tpu.ops import pallas_corr as pc
    from raft_tpu.ops.corr import build_corr_pyramid_flat

    monkeypatch.setattr(pc, "_WIN_BLOCK_Q", block_q)
    f1, f2, coords, g = _window_case(hw, 1, 11)

    def dense(a, b):
        pyr = build_corr_pyramid_flat(a, b, 1, pad_q=128)
        return jnp.sum(pc.pallas_pyramid_lookup(pyr, coords, 4, 128) * g)

    def windows(a, b):
        stop = jax.lax.stop_gradient
        pyr = build_corr_pyramid_flat(stop(a), stop(b), 1, pad_q=128)
        return jnp.sum(pc.pallas_pyramid_lookup_windows(
            pyr, a, [b], coords, 4, 128) * g)

    want = jax.grad(dense, argnums=(0, 1))(f1, f2)
    got = jax.grad(windows, argnums=(0, 1))(f1, f2)
    for a, b in zip(got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=2e-5 * scale)


def test_window_backward_over_four_pooled_levels():
    """Four levels: ``window_grads`` on ``f2`` pooled level by level,
    through the pooling's own transpose, against ``jax.grad`` through the
    dense volume of four levels (XLA's lookup)."""
    from raft_tpu.ops import pallas_corr as pc

    f1, f2, coords, g = _window_case((16, 24), 4, 15)

    def dense(a, b):
        pyr = build_corr_pyramid(a, b, 4)
        return jnp.sum(corr_lookup(pyr, coords, 4) * g)

    want = jax.grad(dense, argnums=(0, 1))(f1, f2)
    d1, d2s = pc.window_grads(f1, pool_fmap_pyramid(f2, 4), coords, g, 4,
                              interpret=True)
    _, pull = jax.vjp(lambda b: pool_fmap_pyramid(b, 4), f2)
    got = (d1, pull(list(d2s))[0])
    for a, b in zip(got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=2e-5 * scale)


def test_window_backward_keeps_bfloat16_maps_exact():
    """bfloat16 maps enter whole (one MXU operand each, no low half) and
    the gradients come back bfloat16, as close to the float32 build's as
    their rounding."""
    from raft_tpu.ops import pallas_corr as pc
    from raft_tpu.ops.corr import build_corr_pyramid_flat

    f1, f2, coords, g = _window_case((24, 32), 1, 5)
    f1, f2 = f1.astype(jnp.bfloat16), f2.astype(jnp.bfloat16)

    def exact(a, b):
        pyr = build_corr_pyramid_flat(a, b, 1, pad_q=128)
        return jnp.sum(pc.pallas_pyramid_lookup(pyr, coords, 4, 128) * g)

    want = jax.grad(exact, argnums=(0, 1))(f1.astype(jnp.float32),
                                           f2.astype(jnp.float32))
    got = pc.window_grads(f1, [f2], coords, g, 4, interpret=True)
    got = (got[0], got[1][0])
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=0, atol=2 ** -8 * scale)


def test_model_corr_state_sends_the_same_gradient_either_way(monkeypatch):
    """``models/raft.py``'s builder and lookup, the Mosaic lookup
    interpreted: where ``lookup_backward_path`` says "windows" the state
    carries the maps beside a pyramid of stopped maps, and the gradient
    two lookups send to the maps is the one the dense backward sends."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models import raft as raft_mod
    from raft_tpu.ops import pallas_corr as pc

    cfg = RAFTConfig.full(corr_impl="allpairs_pallas",
                          pallas_offtpu="interpret", corr_levels=1)
    f1, f2, coords, _ = _window_case((16, 24), 1, 3)

    def grads(path):
        monkeypatch.setattr(pc, "lookup_backward_path", lambda *a: path)

        def loss(a, b):
            state = raft_mod._build_corr_state(cfg, a, b, True)
            assert isinstance(state, raft_mod.WindowCorrState) == (
                path == "windows")
            return sum(jnp.sum(jnp.sin(raft_mod.lookup_corr(
                cfg, state, coords + 0.7 * i, jnp.float32)))
                for i in range(2))

        return jax.grad(loss, argnums=(0, 1))(f1, f2)

    for a, b in zip(grads("windows"), grads("volume")):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=2e-5 * scale)


def test_model_bfloat16_maps_sum_their_lookup_gradients_in_float32(
        monkeypatch):
    """bfloat16 maps (arch 'unimatch''s refinement): the window state
    carries them in float32, flagged as bfloat16 values, so six lookups'
    gradients sum in float32 and are rounded once; the result is no
    farther from a float32 volume's gradient than the dense backward's,
    whose cotangent is rounded to bfloat16 in every lookup."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models import raft as raft_mod
    from raft_tpu.ops import pallas_corr as pc

    kw = dict(corr_impl="allpairs_pallas", pallas_offtpu="interpret",
              corr_levels=1)
    f1, f2, coords, _ = _window_case((16, 24), 1, 7)
    f1, f2 = f1.astype(jnp.bfloat16), f2.astype(jnp.bfloat16)

    def grads(cfg, path, a, b):
        monkeypatch.setattr(pc, "lookup_backward_path", lambda *x: path)

        def loss(a, b):
            state = raft_mod._build_corr_state(cfg, a, b, True)
            if path == "windows":
                assert state.bf16_values
                assert state.fmap1.dtype == jnp.float32
            return sum(jnp.sum(jnp.sin(raft_mod.lookup_corr(
                cfg, state, coords + 0.7 * i, jnp.float32)))
                for i in range(6))

        return jax.grad(loss, argnums=(0, 1))(a, b)

    half = RAFTConfig.full(compute_dtype="bfloat16", **kw)
    want = grads(RAFTConfig.full(**kw), "volume", f1.astype(jnp.float32),
                 f2.astype(jnp.float32))
    win, vol = grads(half, "windows", f1, f2), grads(half, "volume", f1, f2)
    for w, v, x in zip(win, vol, want):
        assert w.dtype == jnp.bfloat16
        scale = float(jnp.max(jnp.abs(x)))
        err_w = float(jnp.max(jnp.abs(w.astype(jnp.float32) - x))) / scale
        err_v = float(jnp.max(jnp.abs(v.astype(jnp.float32) - x))) / scale
        assert err_w <= 2 ** -8 and err_w <= err_v, (err_w, err_v)
