"""InputPadder tests (reference utils.py:7-24 semantics) + the shared
bucket policy (eval validators and the serve engine both round through
raft_tpu.ops.pad, so they cannot drift)."""

import numpy as np

import jax.numpy as jnp

from raft_tpu.ops import InputPadder, bucket_hw, ceil_to_multiple, \
    max_bucket_hw


def test_pad_to_multiple_of_8_sintel_centered():
    x = jnp.ones((1, 436, 1024, 3))
    padder = InputPadder(x.shape, mode="sintel")
    y = padder.pad(x)
    assert y.shape == (1, 440, 1024, 3)
    # height pad 4 -> 2 top, 2 bottom (centered)
    back = padder.unpad(y)
    assert back.shape == x.shape


def test_pad_kitti_bottom_only():
    x = jnp.arange(2 * 370 * 1226 * 1, dtype=jnp.float32).reshape(2, 370, 1226, 1)
    padder = InputPadder(x.shape, mode="kitti")
    y = padder.pad(x)
    assert y.shape == (2, 376, 1232, 1)
    # top row unchanged (no top pad in non-sintel mode)
    np.testing.assert_array_equal(np.asarray(y)[:, 0, 3:-3, :],
                                  np.asarray(x)[:, 0, :, :])
    back = padder.unpad(y)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_already_divisible_no_pad():
    x = jnp.ones((1, 64, 128, 3))
    padder = InputPadder(x.shape)
    y = padder.pad(x)
    assert y.shape == x.shape


def test_ceil_to_multiple():
    assert ceil_to_multiple(436) == 440
    assert ceil_to_multiple(440) == 440
    assert ceil_to_multiple(1, 8) == 8
    assert ceil_to_multiple(370, 2) == 370


def test_bucket_hw_exact_roundup():
    assert bucket_hw(436, 1024) == (440, 1024)
    assert bucket_hw(375, 1242) == (376, 1248)
    assert bucket_hw(64, 96) == (64, 96)


def test_bucket_hw_ladder():
    ladder = ((440, 1024), (720, 1280))
    # smallest covering ladder entry wins
    assert bucket_hw(436, 1024, ladder=ladder) == (440, 1024)
    assert bucket_hw(441, 1024, ladder=ladder) == (720, 1280)
    # larger than every entry: exact round-up fallback, still served
    assert bucket_hw(1440, 2560, ladder=ladder) == (1440, 2560)


def test_max_bucket_hw_matches_padder_targets():
    """The validators' one-bucket-per-split policy: every shape in the
    set fits the bucket, and the bucket is the tight /8 round-up of the
    max (KITTI's mixed native resolutions)."""
    shapes = [(375, 1242), (370, 1224), (374, 1238)]
    bucket = max_bucket_hw(shapes)
    assert bucket == (376, 1248)
    for hw in shapes:
        padder = InputPadder(hw, mode="kitti", target=bucket)
        x = np.zeros(hw + (3,), np.float32)
        assert padder.pad_np(x).shape == bucket + (3,)


def test_pad_multiple_of_the_model():
    """A model that splits its 1/8 map into 2x2 windows (arch 'gmflow':
    ``RAFTConfig.pad_multiple`` 16) pads Sintel to 448x1024, as does one
    that splits its 1/4 map into 8x8 (arch 'unimatch': 32); the four
    architectures with a RAFT loop keep 8 and the buckets they always
    had."""
    from raft_tpu.config import ARCHS, RAFTConfig

    assert {a: RAFTConfig.preset(a).pad_multiple for a in ARCHS} == {
        "full": 8, "small": 8, "gma": 8, "searaft": 8, "gmflow": 16,
        "unimatch": 32}
    x = np.zeros((436, 1024, 3), np.float32)
    for arch, want in (("full", 440), ("gmflow", 448), ("unimatch", 448)):
        m = RAFTConfig.preset(arch).pad_multiple
        padder = InputPadder(x.shape, mode="sintel", multiple=m)
        y = padder.pad_np(x)
        assert y.shape == (want, 1024, 3)
        assert padder.unpad(y[None]).shape == (1, 436, 1024, 3)
        assert bucket_hw(436, 1024, m) == (want, 1024)
        assert max_bucket_hw([(436, 1024), (375, 1242)], m) \
            == (want, 1248)
    # sintel mode centres the 12 rows, 6 and 6; every other mode puts
    # them at the bottom
    padder = InputPadder((436, 1024), mode="sintel", multiple=16)
    assert padder._pad == [0, 0, 6, 6]
    assert InputPadder((370, 1226), mode="kitti", multiple=16)._pad \
        == [3, 3, 0, 14]


def test_the_engine_buckets_by_the_model_and_never_under_it():
    """``ServeConfig.bucket_multiple`` is a floor: the engine rounds to the
    least common multiple of it and the model's; a ladder entry that the
    model cannot take is refused when the engine is built."""
    import pytest

    from raft_tpu.config import RAFTConfig
    from raft_tpu.serve import ServeConfig

    assert ServeConfig().bucket_multiple == 8
    assert int(np.lcm(8, RAFTConfig.preset("gmflow").pad_multiple)) == 16
    assert int(np.lcm(32, RAFTConfig.preset("gmflow").pad_multiple)) == 32
    assert int(np.lcm(8, RAFTConfig.full().pad_multiple)) == 8
    with pytest.raises(ValueError, match="not /16-aligned"):
        ServeConfig(bucket_multiple=16, buckets=((440, 1024),))
