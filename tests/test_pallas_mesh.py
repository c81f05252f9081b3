"""Pallas entry points under a data-parallel mesh (PR 22).

GSPMD cannot partition a Mosaic kernel, so while ``make_train_step`` traces
a step for a mesh every Pallas entry point runs once per ``data`` shard
under ``shard_map`` (``ops/pallas_util.per_data_shard``).  The kernels are
independent per batch element, so the per-shard result must equal the
unsharded one — forward AND gradients, including the summed cotangent of
operands every shard holds whole (the fused encoder's conv weights).

Interpret mode on the 8-virtual-device CPU mesh; that the REAL kernel
lowers this way for a v5e mesh is ``tests/test_chip_compile.py``'s case.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu.ops.corr import build_corr_pyramid_flat, pool_fmap_pyramid
from raft_tpu.ops.pallas_corr import (pallas_corr_lookup,
                                      pallas_pyramid_lookup,
                                      pallas_pyramid_lookup_encode,
                                      pallas_pyramid_lookup_quantized)
from raft_tpu.ops.pallas_gru import gru_gate_blend, gru_gate_rh
from raft_tpu.ops.pallas_upsample import pallas_upsample_loss_sums
from raft_tpu.ops.sampler import coords_grid
from raft_tpu.parallel.mesh import (DATA_AXIS, data_parallel_kernels,
                                    kernel_mesh, make_mesh)

B, H, W, C = 4, 8, 16, 16
LEVELS, RADIUS = 2, 2
KK = LEVELS * (2 * RADIUS + 1) ** 2


def _inputs(seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    f1, f2 = normal(B, H, W, C), normal(B, H, W, C)
    coords = coords_grid(B, H, W) + jnp.asarray(
        rng.uniform(-2, 2, (B, H, W, 2)), jnp.float32)
    return dict(f1=f1, f2=f2, coords=coords, normal=normal)


def _case(name):
    """``(fn, args, batched)``: ``fn(*args)`` -> scalar; ``batched[i]``
    says whether ``args[i]`` has the batch leading (sharded over ``data``)
    or is held whole by every shard."""
    d = _inputs()
    f1, f2, coords, normal = d["f1"], d["f2"], d["coords"], d["normal"]

    def total(x):
        return jnp.sum(x.astype(jnp.float32) ** 2)

    if name == "pyramid_lookup":
        pyr = build_corr_pyramid_flat(f1, f2, num_levels=LEVELS)
        return (lambda p, c: total(pallas_pyramid_lookup(
            p, c, RADIUS, 128, True)), (pyr, coords), (True, True))
    if name == "pyramid_lookup_quantized":
        pyr = build_corr_pyramid_flat(f1, f2, num_levels=LEVELS,
                                      out_dtype="int8")
        # Primal-only (the quantize boundary is stop_gradient'd): close
        # over the codes, differentiate nothing but a dummy scale.
        return (lambda s, c: s * total(pallas_pyramid_lookup_quantized(
            pyr, c, RADIUS, 128, True)), (jnp.float32(1.0), coords),
            (False, True))
    if name == "ondemand_corr_lookup":
        return (lambda a, b, c: total(pallas_corr_lookup(
            a, tuple(pool_fmap_pyramid(b, LEVELS)), c, RADIUS, 128, True)),
            (f1, f2, coords), (True, True, True))
    if name == "lookup_encode":
        pyr = build_corr_pyramid_flat(f1, f2, num_levels=LEVELS)
        w, b = normal(KK, 24) * KK ** -0.5, normal(24) * 0.1
        return (lambda p, c, w, b: total(pallas_pyramid_lookup_encode(
            p, c, w, b, RADIUS, 128, True)), (pyr, coords, w, b),
            (True, True, False, False))
    if name == "gru_gates":
        z, q, h = (normal(B, H, W, 32) for _ in range(3))
        return (lambda z, q, h: total(gru_gate_blend(
            z, q, gru_gate_rh(z, h, True), True)), (z, q, h),
            (True, True, True))
    if name == "upsample_loss":
        g = 2   # iterations folded batch-major into the leading dim
        flow, mask = normal(g * B, H, W, 2), normal(g * B, H, W, 576)
        gt, vm = normal(B, H, W, 128), jnp.ones((B, H, W, 64))
        weights = jnp.arange(1.0, g * B + 1)  # order-sensitive on purpose

        def fn(flow, mask):
            sums = pallas_upsample_loss_sums(flow, mask, gt, vm, True)
            return jnp.sum(weights * sums[:, 0])

        return fn, (flow, mask), (True, True)
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "pyramid_lookup", "pyramid_lookup_quantized", "ondemand_corr_lookup",
    "lookup_encode", "gru_gates", "upsample_loss"])
def test_kernel_per_data_shard_matches_unsharded(name):
    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")
    fn, args, batched = _case(name)
    mesh = make_mesh(num_data=4, num_spatial=1, devices=jax.devices()[:4])
    argnums = tuple(range(len(args)))

    want, want_grads = jax.jit(jax.value_and_grad(fn, argnums))(*args)

    def on_mesh(*a):
        with data_parallel_kernels(mesh):
            assert kernel_mesh() is mesh
            return jax.value_and_grad(fn, argnums)(*a)

    placed = tuple(
        jax.device_put(a, NamedSharding(mesh, P(DATA_AXIS) if b else P()))
        for a, b in zip(args, batched))
    lowered = jax.jit(on_mesh).lower(*placed)
    assert "shard_map" in lowered.as_text() \
        or "manual" in lowered.as_text().lower()
    got, got_grads = lowered.compile()(*placed)

    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_single_device_mesh_is_the_plain_program():
    """A mesh whose data axis has one device changes nothing: the
    single-device program stays the one the chip's compiler was shown."""
    mesh = make_mesh(num_data=1, num_spatial=1, devices=jax.devices()[:1])
    with data_parallel_kernels(mesh):
        assert kernel_mesh() is None
    assert kernel_mesh() is None
