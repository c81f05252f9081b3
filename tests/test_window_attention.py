"""GMFlow's window attention as Mosaic kernels (``ops/pallas_attention.py``)
against the ``jnp`` body of ``models/gmflow.py window_attention`` (tier-1,
CPU: the kernels run in the Pallas interpreter).

Pinned here:

- the kernels' forward and their ``q``, ``k``, ``v`` gradients against the
  ``jnp`` body, float32 and bfloat16, shifted and not, a whole window a grid
  step and a block of its rows (``dk``, ``dv`` summed over the blocks);
- that the same comparison tells a planted fault apart (the region mask
  dropped, ``1/sqrt(C)`` dropped);
- which body a program holds (``window_attention_path``), what the model
  hands the choice, and that off a TPU nothing of Mosaic is in the program;
- the ``window_attention`` field of the train loop's records.

What interpret mode cannot see (alignment, VMEM) is compiled for a
described v5e in ``tests/test_chip_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import RAFTConfig
from raft_tpu.models import gmflow
from raft_tpu.models import raft as raft_mod
from raft_tpu.ops import pallas_attention

# windows of 2 x 16 tokens: w/K is bfloat16's sublane tile
B, H8, W8, C = 2, 4, 32, 128
# float32 agrees to rounding of the sums' order; bfloat16 to an ulp or two of
# the rounded result (relative to the array's largest entry)
TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def _operands(dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (B, H8 * W8, C), jnp.float32
                                   ).astype(dtype) for k in keys)


def kernel_attention(q, k, v, shift, block_rows=None, regions="shift"):
    """``gmflow.window_attention``'s kernel branch, spelled out so that the
    interpreter can run it off a TPU and a test can hand it a block size."""
    if regions == "shift":
        regions = gmflow.shift_regions(H8, W8) if shift else None
    out = pallas_attention.window_attention(
        *(x.reshape(B, H8, W8, C) for x in (q, k, v)), gmflow.SPLITS,
        (H8 // 4, W8 // 4) if shift else None, regions, gmflow.MASK_VALUE,
        block_rows=block_rows, interpret=True)
    return out.reshape(B, H8 * W8, C)


def _gaps(fn, dtype, shift):
    """Largest |difference| of the result and of each gradient between
    ``fn`` and the ``jnp`` body, over the reference array's largest
    entry."""
    q, k, v, g = _operands(dtype)
    want, vjp = jax.vjp(lambda q, k, v: gmflow.window_attention(
        q, k, v, H8, W8, shift, dtype), q, k, v)
    got, vjp_k = jax.vjp(fn, q, k, v)
    assert got.dtype == want.dtype and got.shape == want.shape

    def gap(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / np.abs(b).max())

    return [gap(got, want)] + [gap(a, b) for a, b in zip(vjp_k(g), vjp(g))]


@pytest.mark.parametrize("block_rows", [None, 1])
@pytest.mark.parametrize("shift", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_match_the_jnp_body(dtype, shift, block_rows):
    assert gmflow.window_attention_path(H8, W8, C, dtype) == "xla"
    gaps = _gaps(lambda q, k, v: kernel_attention(q, k, v, shift,
                                                  block_rows),
                 jnp.dtype(dtype), shift)
    assert max(gaps) <= TOL[dtype], gaps


@pytest.mark.parametrize("fault", ["mask_dropped", "scale_dropped"])
def test_a_planted_fault_fails_the_same_comparison(fault, monkeypatch):
    if fault == "mask_dropped":
        fn = lambda q, k, v: kernel_attention(q, k, v, True,   # noqa: E731
                                              regions=None)
    else:
        # the kernels scale by 1/sqrt(C) of the C they see: hand them 1
        monkeypatch.setattr(pallas_attention, "_scale", lambda C: 1.0)
        fn = lambda q, k, v: kernel_attention(q, k, v, True)   # noqa: E731
    gaps = _gaps(fn, jnp.float32, True)
    assert min(gaps) > 100 * TOL["float32"], gaps
    assert max(gaps) > 0.05, gaps


def test_under_a_data_mesh_the_kernels_run_per_batch_shard():
    """``per_data_shard``: with the batch split over two devices the same
    numbers come back, forward and backward, the region ids whole on each."""
    from raft_tpu.parallel.mesh import data_parallel_kernels, make_mesh

    q, k, v, g = _operands(jnp.float32)

    def both(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: kernel_attention(q, k, v, True),
                           q, k, v)
        return (out,) + vjp(g)

    want = jax.jit(both)(q, k, v, g)
    mesh = make_mesh(num_data=2, num_spatial=1, devices=jax.devices()[:2])

    def meshed(q, k, v, g):
        with data_parallel_kernels(mesh):
            return both(q, k, v, g)

    for a, b in zip(jax.jit(meshed)(q, k, v, g), want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_regions_are_the_masks_ids():
    """The ids the kernels compare are the ones ``shift_mask`` is made of
    (``tests/test_gmflow.py`` holds the mask to a brute-force count)."""
    for h, w in ((4, 32), (48, 64), (56, 128), (6, 10)):
        ids = gmflow.shift_regions(h, w)
        assert ids.shape == (4, (h // 2) * (w // 2))
        assert ids.dtype == np.int32
        same = ids[:, :, None] == ids[:, None, :]
        np.testing.assert_array_equal(
            gmflow.shift_mask(h, w),
            np.where(same, 0.0, gmflow.MASK_VALUE).astype(np.float32))


# (platform, window rows, window columns, bytes an entry) -> path.  24x32 is
# the chairs crop's window (384x512), 28x64 Sintel's (448x1024), 23x31 the
# RAFT crop's (368x496 would need a pad), 136x240 a 2176x3840 frame's.
@pytest.mark.parametrize("platform,hk,wk,itemsize,want", [
    ("tpu", 24, 32, 2, "mosaic"),
    ("tpu", 28, 64, 2, "mosaic"),
    ("tpu", 24, 32, 4, "mosaic"),
    ("tpu", 28, 64, 4, "mosaic"),
    ("tpu", 24, 24, 2, "xla"),       # w/K not a multiple of bf16's 16
    ("tpu", 24, 24, 4, "mosaic"),    # ... and one of float32's 8
    ("tpu", 23, 31, 2, "xla"),
    ("tpu", 2, 3, 4, "xla"),         # the rehearsal's 4 x 6 map
    ("tpu", 136, 240, 2, "xla"),     # no block of it inside the budget
    ("cpu", 24, 32, 2, "xla"),
    ("gpu", 28, 64, 2, "xla"),
])
def test_path_by_platform_and_shape(platform, hk, wk, itemsize, want):
    got = pallas_attention.window_attention_path(platform, hk, wk, C,
                                                 itemsize)
    assert got == want
    rows = pallas_attention.window_block_rows(hk, wk, C, itemsize)
    if platform == "tpu":
        assert (rows is not None) == (want == "mosaic")
    if rows is not None:
        assert hk % rows == 0
        assert rows == hk or (rows * wk) % 128 == 0
        assert pallas_attention.window_attention_vmem_bytes(
            rows, hk, wk, C, itemsize) <= pallas_attention._ATTN_BUDGET
    # image rows split over devices: never a whole-image kernel
    assert pallas_attention.window_attention_path(
        platform, hk, wk, C, itemsize, rows_split=True) == "xla"


def test_block_rows_shrink_with_the_window():
    """A whole window a grid step where it fits (the faster block on the
    chip at both measured shapes, PERF.md section 6), whole lane tiles of
    its rows where it does not."""
    rows = pallas_attention.window_block_rows
    assert rows(24, 32, C, 2) == 24          # the chairs crop: 9.4 MiB
    assert rows(28, 64, C, 2) == 28          # Sintel: 39.4 MiB
    assert rows(56, 64, C, 2) == 14          # 896x1024: 4 blocks of 896 rows
    assert rows(56, 64, C, 4) == 8
    with pytest.raises(ValueError, match="window_attention_path"):
        x = jnp.zeros((1, 46, 62, C), jnp.bfloat16)
        pallas_attention.window_attention(x, x, x, 2)


def test_the_model_asks_with_what_it_observes(monkeypatch):
    from raft_tpu.parallel.mesh import data_parallel_kernels

    cfg = RAFTConfig.gmflow(compute_dtype="bfloat16")
    assert raft_mod.window_attention_at(cfg, 48, 64) == "xla"     # the CPU
    assert raft_mod.window_attention_at(RAFTConfig.full(), 48, 64) == "none"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert raft_mod.window_attention_at(cfg, 48, 64) == "mosaic"
    assert raft_mod.window_attention_at(cfg, 56, 128) == "mosaic"
    assert raft_mod.window_attention_at(cfg, 46, 62) == "xla"
    assert raft_mod.window_attention_at(cfg, 4, 6) == "xla"
    with data_parallel_kernels(None, rows_split=True):
        assert raft_mod.window_attention_at(cfg, 48, 64) == "xla"
    assert raft_mod.window_attention_at(RAFTConfig.gma(), 48, 64) == "none"


def test_off_a_tpu_the_program_holds_no_mosaic_call():
    """What the train loop's ``hbm`` record counts as ``tpu_custom_calls``
    (``compiled.as_text().count("tpu_custom_call")``): none on the CPU,
    shifted or not, forward and backward."""
    q, k, v, g = _operands(jnp.bfloat16)

    def both(q, k, v, g):
        out = []
        for shift in (False, True):
            o, vjp = jax.vjp(lambda q, k, v: gmflow.window_attention(
                q, k, v, H8, W8, shift, jnp.bfloat16, True), q, k, v)
            out.append((o, vjp(g)))
        return out

    text = jax.jit(both).lower(q, k, v, g).compile().as_text()
    assert text.count("tpu_custom_call") == 0


@pytest.mark.parametrize("arch,backend,size,want", [
    ("gmflow", "cpu", (384, 512), "xla"),
    ("gmflow", "tpu", (384, 512), "mosaic"),
    ("gmflow", "tpu", (368, 496), "xla"),
    ("small", "tpu", (384, 512), "none"),
])
def test_train_records_name_the_window_attention(arch, backend, size, want,
                                                 tmp_path, monkeypatch):
    """Every ``train`` record of the stage clock says which window attention
    the step was traced with, beside ``attn_bytes`` (the step itself is a
    stub: nothing compiles)."""
    from test_obs import _slow_batches, _stub_loop

    from raft_tpu.config import TrainConfig
    from raft_tpu.obs import stages
    from raft_tpu.train import loop as loop_mod

    _stub_loop(monkeypatch, loop_mod)
    monkeypatch.delenv("RAFT_TELEMETRY_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = TrainConfig(name="t", num_steps=2, batch_size=8, image_size=size,
                      iters=2, val_freq=100, log_freq=2,
                      ckpt_dir=str(tmp_path / "ck"))
    loop_mod.train(RAFTConfig.preset(arch), cfg,
                   batches=_slow_batches(2, 8, (16, 16)),
                   telemetry_dir=None)
    records = stages.recent("train")[-2:]
    assert [r["window_attention"] for r in records] == [want, want]
    assert all(r["model"] == arch for r in records)
