"""L1 data pipeline tests: synthetic tmpdir fixtures mimic the real corpora
directory layouts (SURVEY.md §4 — the reference has no tests; fixtures stand
in for the 400GB datasets)."""

import os
import os.path as osp

import numpy as np
import pytest
from PIL import Image

from raft_tpu.data import frame_utils
from raft_tpu.data.augment import (ColorJitter, FlowAugmentor,
                                   SparseFlowAugmentor,
                                   resize_sparse_flow_map)
from raft_tpu.data.datasets import (ConcatFlowDataset, FlyingChairs,
                                    FlyingThings3D, HD1K, KITTI,
                                    MpiSintel, ShardedLoader, fetch_dataset)

from loader_datasets import CountingDataset, HeldDataset, SynthDataset

H, W = 96, 128


def _write_img(path, rng, size=(H, W)):
    arr = rng.integers(0, 255, size=size + (3,), dtype=np.uint8)
    Image.fromarray(arr).save(path)


def _write_ppm(path, rng, size=(H, W)):
    arr = rng.integers(0, 255, size=size + (3,), dtype=np.uint8)
    Image.fromarray(arr).save(path, format="PPM")


@pytest.fixture
def sintel_root(tmp_path):
    rng = np.random.default_rng(0)
    for scene in ["alley_1", "ambush_2"]:
        img_dir = tmp_path / "Sintel/training/clean" / scene
        flow_dir = tmp_path / "Sintel/training/flow" / scene
        img_dir.mkdir(parents=True)
        flow_dir.mkdir(parents=True)
        for i in range(3):
            _write_img(img_dir / f"frame_{i:04d}.png", rng)
        for i in range(2):
            frame_utils.write_flo(
                str(flow_dir / f"frame_{i:04d}.flo"),
                rng.normal(size=(H, W, 2)).astype(np.float32))
    return str(tmp_path / "Sintel")


@pytest.fixture
def chairs_root(tmp_path):
    rng = np.random.default_rng(1)
    data = tmp_path / "FlyingChairs_release/data"
    data.mkdir(parents=True)
    n = 4
    for i in range(n):
        _write_ppm(data / f"{i:05d}_img1.ppm", rng)
        _write_ppm(data / f"{i:05d}_img2.ppm", rng)
        frame_utils.write_flo(str(data / f"{i:05d}_flow.flo"),
                              rng.normal(size=(H, W, 2)).astype(np.float32))
    split = tmp_path / "chairs_split.txt"
    split.write_text("1\n1\n2\n1\n")
    return str(data), str(split)


@pytest.fixture
def kitti_root(tmp_path):
    rng = np.random.default_rng(2)
    img_dir = tmp_path / "KITTI/training/image_2"
    flow_dir = tmp_path / "KITTI/training/flow_occ"
    img_dir.mkdir(parents=True)
    flow_dir.mkdir(parents=True)
    for i in range(2):
        _write_img(img_dir / f"{i:06d}_10.png", rng, size=(H, W))
        _write_img(img_dir / f"{i:06d}_11.png", rng, size=(H, W))
        flow = rng.normal(scale=5, size=(H, W, 2)).astype(np.float32)
        frame_utils.write_flow_kitti(str(flow_dir / f"{i:06d}_10.png"), flow)
    return str(tmp_path / "KITTI")


def test_sintel_pairs_and_load(sintel_root):
    ds = MpiSintel(None, split="training", root=sintel_root, dstype="clean")
    # 2 scenes x (3 frames -> 2 consecutive pairs)
    assert len(ds) == 4 and len(ds.flow_list) == 4
    s = ds.load(0)
    assert s["image1"].shape == (H, W, 3)
    assert s["flow"].shape == (H, W, 2)
    assert s["valid"].shape == (H, W)
    assert s["valid"].all()  # small flows, all |.| < 1000


def test_chairs_split(chairs_root):
    root, split_file = chairs_root
    train = FlyingChairs(None, split="training", root=root,
                         split_file=split_file)
    val = FlyingChairs(None, split="validation", root=root,
                       split_file=split_file)
    assert len(train) == 3 and len(val) == 1


def test_kitti_sparse_load(kitti_root):
    ds = KITTI(None, split="training", root=kitti_root)
    assert len(ds) == 2 and ds.sparse
    s = ds.load(1)
    # KITTI PNG quantizes to 1/64 px
    assert s["flow"].shape == (H, W, 2)
    assert s["valid"].min() >= 0 and s["valid"].max() == 1


def test_mixing_weights_and_concat(sintel_root, kitti_root):
    sintel = MpiSintel(None, split="training", root=sintel_root,
                       dstype="clean")
    kitti = KITTI(None, split="training", root=kitti_root)
    mix = 3 * sintel + 2 * kitti
    assert isinstance(mix, ConcatFlowDataset)
    assert len(mix) == 3 * 4 + 2 * 2
    # The tail of the mixture must route to the sparse member.
    s = mix.load(len(mix) - 1)
    assert s["flow"].shape == (H, W, 2)
    # Replicated indices must resolve to the same underlying sample.
    a = mix.load(0)
    b = mix.load(4)  # second replica of sintel sample 0
    np.testing.assert_array_equal(a["flow"], b["flow"])


def test_fetch_dataset_chairs_stage(chairs_root):
    root, split_file = chairs_root
    ds = fetch_dataset("chairs", (64, 64),
                       root=osp.dirname(osp.dirname(root)),
                       split_file=split_file)
    assert len(ds) == 3
    s = ds.load(0, np.random.default_rng(0))
    assert s["image1"].shape == (64, 64, 3)
    assert s["flow"].shape == (64, 64, 2)


# ---------------------------------------------------------------------------
# Augmentor behavior
# ---------------------------------------------------------------------------

def test_dense_augmentor_shapes_and_determinism():
    rng = np.random.default_rng(7)
    img1 = rng.integers(0, 255, (H, W, 3), dtype=np.uint8)
    img2 = rng.integers(0, 255, (H, W, 3), dtype=np.uint8)
    flow = rng.normal(size=(H, W, 2)).astype(np.float32)
    aug = FlowAugmentor(crop_size=(64, 80))
    for seed in range(4):
        o1 = aug(np.random.default_rng(seed), img1, img2, flow)
        o2 = aug(np.random.default_rng(seed), img1, img2, flow)
        assert o1[0].shape == (64, 80, 3) and o1[2].shape == (64, 80, 2)
        for a, b in zip(o1, o2):
            np.testing.assert_array_equal(a, b)


def test_hflip_flow_sign():
    """A pure-horizontal flow must negate u (not v) under h-flip
    (reference augmentor.py:95)."""
    img = np.full((H, W, 3), 128, np.uint8)
    flow = np.stack([np.full((H, W), 3.0), np.zeros((H, W))],
                    axis=-1).astype(np.float32)
    aug = FlowAugmentor(crop_size=(H - 16, W - 16), do_flip=True,
                        spatial_aug_prob=0.0, eraser_aug_prob=0.0,
                        asymmetric_color_aug_prob=0.0,
                        h_flip_prob=1.0, v_flip_prob=0.0,
                        jitter=ColorJitter(0, 0, 0, 0))
    _, _, out = aug(np.random.default_rng(0), img, img, flow)
    assert np.allclose(out[..., 0], -3.0)
    assert np.allclose(out[..., 1], 0.0)


def test_spatial_scale_scales_flow():
    """Resizing by (sx, sy) must multiply flow components by (sx, sy)
    (reference augmentor.py:89)."""
    img = np.full((H, W, 3), 100, np.uint8)
    flow = np.stack([np.full((H, W), 2.0), np.full((H, W), -1.0)],
                    axis=-1).astype(np.float32)
    aug = FlowAugmentor(crop_size=(64, 64), min_scale=0.5, max_scale=0.5,
                        do_flip=False, spatial_aug_prob=1.0,
                        stretch_prob=0.0, eraser_aug_prob=0.0,
                        asymmetric_color_aug_prob=0.0,
                        jitter=ColorJitter(0, 0, 0, 0))
    _, _, out = aug(np.random.default_rng(0), img, img, flow)
    s = 2.0 ** 0.5
    assert np.allclose(out[..., 0], 2.0 * s, atol=1e-4)
    assert np.allclose(out[..., 1], -1.0 * s, atol=1e-4)


def test_resize_sparse_flow_map_matches_reference():
    """Our vectorized sparse rescale vs the reference's (deterministic, so
    directly comparable; reference augmentor.py:161-193)."""
    from tests.reference_oracle import skip_without_reference
    skip_without_reference()
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_ref_aug_isolated", "/root/reference/core/utils/augmentor.py")
    try:
        ref_aug = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ref_aug)
    except ImportError:
        pytest.skip("reference augmentor deps unavailable")

    rng = np.random.default_rng(3)
    flow = rng.normal(scale=10, size=(50, 70, 2)).astype(np.float32)
    valid = (rng.random((50, 70)) < 0.3).astype(np.float32)
    ref = ref_aug.SparseFlowAugmentor.resize_sparse_flow_map(
        None, flow, valid, fx=1.3, fy=0.9)
    ours = resize_sparse_flow_map(flow, valid, fx=1.3, fy=0.9)
    np.testing.assert_allclose(ours[0], ref[0], atol=1e-5)
    np.testing.assert_array_equal(ours[1], ref[1])


def test_sparse_augmentor_shapes():
    rng = np.random.default_rng(11)
    img1 = rng.integers(0, 255, (H, W, 3), dtype=np.uint8)
    img2 = rng.integers(0, 255, (H, W, 3), dtype=np.uint8)
    flow = rng.normal(scale=5, size=(H, W, 2)).astype(np.float32)
    valid = (rng.random((H, W)) < 0.5).astype(np.float32)
    aug = SparseFlowAugmentor(crop_size=(64, 80))
    i1, i2, f, v = aug(np.random.default_rng(0), img1, img2, flow, valid)
    assert i1.shape == (64, 80, 3) and f.shape == (64, 80, 2)
    assert v.shape == (64, 80)
    assert set(np.unique(v)).issubset({0, 1})


# ---------------------------------------------------------------------------
# ShardedLoader
# ---------------------------------------------------------------------------

def test_sharded_loader_batches_and_host_disjointness(sintel_root):
    ds = MpiSintel({"crop_size": (48, 64), "min_scale": -0.1,
                    "max_scale": 0.1, "do_flip": True},
                   split="training", root=sintel_root, dstype="clean")
    loaders = [ShardedLoader(ds, batch_size=1, seed=5, num_hosts=2,
                             host_id=h, num_workers=2) for h in range(2)]
    idx0 = loaders[0].epoch_indices(0)
    idx1 = loaders[1].epoch_indices(0)
    assert not set(idx0) & set(idx1)
    assert sorted(list(idx0) + list(idx1)) == list(range(len(ds)))
    # Shuffle differs across epochs
    assert not np.array_equal(loaders[0].epoch_indices(0),
                              loaders[0].epoch_indices(1))

    it = loaders[0].batches()
    batch = next(it)
    assert batch["image1"].shape == (1, 48, 64, 3)
    assert batch["flow"].shape == (1, 48, 64, 2)
    assert batch["valid"].shape == (1, 48, 64)
    # Infinite stream: crossing the epoch boundary keeps yielding.
    for _ in range(3):
        next(it)


def test_sharded_loader_deterministic(sintel_root):
    ds = MpiSintel({"crop_size": (48, 64), "min_scale": -0.1,
                    "max_scale": 0.1, "do_flip": True},
                   split="training", root=sintel_root, dstype="clean")
    def first_batch():
        return next(ShardedLoader(ds, batch_size=2, seed=9,
                                  num_workers=3).batches())
    b1, b2 = first_batch(), first_batch()
    for k in b1:
        np.testing.assert_array_equal(b1[k], b2[k])


def test_batches_from_step_resumes_shuffle(sintel_root):
    ds = MpiSintel(root=sintel_root)  # 4 samples
    mk = lambda: ShardedLoader(ds, batch_size=2, seed=7, num_workers=1)
    spe = mk().steps_per_epoch()
    assert spe == 2

    it = mk().batches()
    full = [next(it) for _ in range(5)]
    it2 = mk().batches_from_step(3)
    resumed = [next(it2) for _ in range(2)]
    for a, b in zip(full[3:], resumed):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("drop_last", [True, False])
def test_batches_are_each_epochs_own_across_its_end(drop_last):
    """No batch spans two epochs, every batch holds the epoch's indices in
    their order with the draws of ``[seed, epoch, index]``, and a short last
    batch appears only where ``drop_last`` is off."""
    ds = CountingDataset(7)
    loader = ShardedLoader(ds, batch_size=3, seed=11, num_workers=2,
                           drop_last=drop_last)
    it = loader.batches()
    for epoch in range(3):
        idx = loader.epoch_indices(epoch)
        want = [idx[0:3], idx[3:6]] + ([] if drop_last else [idx[6:7]])
        for rows in want:
            got = next(it)
            np.testing.assert_array_equal(got["x"][:, 0], rows)
            for i, r in zip(rows, got["r"]):
                rng = np.random.default_rng(
                    np.random.SeedSequence([11, epoch, int(i)]))
                assert r[0] == np.float32(rng.uniform())


def test_decode_window_stays_full_across_an_epochs_end(tmp_path):
    """The window of samples in flight runs on into the next epoch: the
    last sample of a 4-sample epoch is held until a fifth has been asked
    for, which a loader that drains its window at the epoch's end never
    does."""
    last = int(ShardedLoader(CountingDataset(4), batch_size=2,
                             seed=3).epoch_indices(0)[-1])
    it = ShardedLoader(HeldDataset(4, str(tmp_path), hold=last),
                       batch_size=2, seed=3, num_workers=2).batches()
    next(it), next(it)                   # epoch 0, whole
    it.close()
    assert (tmp_path / "in_time").read_text().split() == ["1"]


@pytest.mark.parametrize("source,start_step,workers", [
    ("synth", 0, 3), ("synth", 5, 3), ("sintel", 0, 3), ("sintel", 5, 3),
    ("synth", 5, (os.cpu_count() or 1) + 2)])   # more workers than cores
def test_worker_batches_equal_the_serial_loads(source, start_step, workers,
                                               sintel_root):
    """The workers' batches are ``np.stack`` of ``_load_one``'s samples
    loaded one by one here, bit for bit and in order, across epoch ends
    and from a mid-epoch ``batches_from_step``; no worker imports jax."""
    if source == "synth":
        ds = SynthDataset(n=13, jax_key=True)
    else:
        ds = MpiSintel({"crop_size": (48, 64), "min_scale": -0.1,
                        "max_scale": 0.1, "do_flip": True},
                       split="training", root=sintel_root, dstype="clean")
    loader = ShardedLoader(ds, batch_size=2, seed=9, num_workers=workers)
    it = loader.batches_from_step(start_step)
    got = [next(it) for _ in range(8)]
    it.close()
    want, buf = [], []
    epoch, skip = divmod(start_step, loader.steps_per_epoch())
    for e, i, ends_epoch in loader._samples(epoch, skip):
        buf.append(loader._load_one(e, i))
        if len(buf) == 2 or ends_epoch:
            want.append({k: np.stack([b[k] for b in buf]) for k in buf[0]})
            buf = []
        if len(want) == len(got):
            break
    for g, w in zip(got, want):
        assert list(g) == list(w)
        for k in g:
            if k != "jax":
                np.testing.assert_array_equal(g[k], w[k])
    if source == "synth":   # the parent loads the stream's first sample
        assert not np.concatenate([g["jax"] for g in got])[1:].any()


def test_a_sample_of_another_layout_fails_its_batch():
    """A batch is one slot laid out by the stream's first sample: a sample
    of other shapes raises out of ``next()`` (as ``np.stack`` would have),
    and is not quarantined as a corrupt file."""
    loader = ShardedLoader(CountingDataset(5, grow=True), batch_size=2,
                           seed=1, num_workers=2)
    with pytest.raises(ValueError, match="layout"):
        for _ in loader.batches():
            pass
    assert loader.quarantined_total == 0


def test_a_held_batch_stays_unchanged_while_later_ones_are_made():
    """A batch is a view of a shared slot, and the slot is not written
    again while any view of it lives: not while later batches are made,
    nor after the workers are gone."""
    it = ShardedLoader(SynthDataset(n=13), batch_size=2, seed=7,
                       num_workers=2).batches()
    held = next(it)
    kept = {k: v.copy() for k, v in held.items()}
    for _ in range(13):                  # two epochs and more
        next(it)
    for k in held:
        np.testing.assert_array_equal(held[k], kept[k])
    it.close()
    for k in held:
        np.testing.assert_array_equal(held[k], kept[k])


@pytest.fixture
def things_root(tmp_path):
    rng = np.random.default_rng(3)
    scene = tmp_path / "FlyingThings3D"
    img_dir = scene / "frames_cleanpass/TRAIN/A/0000/left"
    img_dir.mkdir(parents=True)
    for d in ("into_future", "into_past"):
        (scene / "optical_flow/TRAIN/A/0000" / d / "left").mkdir(
            parents=True)
    for i in range(3):
        _write_img(img_dir / f"{i:04d}.png", rng)
        for d in ("into_future", "into_past"):
            flow = rng.normal(size=(H, W, 2)).astype(np.float32)
            path = scene / "optical_flow/TRAIN/A/0000" / d / "left" / \
                f"{i:04d}.pfm"
            # 3-channel little-endian PFM (flow in the first two channels)
            arr3 = np.concatenate(
                [flow, np.zeros((H, W, 1), np.float32)], axis=-1)
            with open(path, "wb") as f:
                f.write(b"PF\n")
                f.write(f"{W} {H}\n".encode())
                f.write(b"-1.0\n")
                f.write(arr3[::-1].astype("<f4").tobytes())
    return str(scene)


def test_flyingthings_directions(things_root):
    ds = FlyingThings3D(root=things_root)
    # 3 frames -> 2 future pairs + 2 past pairs (order swapped)
    assert len(ds) == 4
    s = ds.load(0)
    assert s["image1"].shape == (H, W, 3)
    assert s["flow"].shape == (H, W, 2)
    # into_past entries swap the image order relative to into_future
    futures = ds.image_list[:2]
    pasts = ds.image_list[2:]
    assert futures[0][0] == pasts[0][1]


@pytest.fixture
def hd1k_root(tmp_path):
    rng = np.random.default_rng(4)
    img_dir = tmp_path / "HD1k/hd1k_input/image_2"
    flow_dir = tmp_path / "HD1k/hd1k_flow_gt/flow_occ"
    img_dir.mkdir(parents=True)
    flow_dir.mkdir(parents=True)
    for seq in range(2):
        for i in range(3):
            _write_img(img_dir / f"{seq:06d}_{i:04d}.png", rng)
            frame_utils.write_flow_kitti(
                str(flow_dir / f"{seq:06d}_{i:04d}.png"),
                rng.normal(scale=3, size=(H, W, 2)).astype(np.float32))
    return str(tmp_path / "HD1k")


def test_hd1k_sequence_scan(hd1k_root):
    ds = HD1K(root=hd1k_root)
    # per sequence: len(flows)-1 = 2 pairs, 2 sequences -> 4
    assert len(ds) == 4
    s = ds.load(0)
    assert s["flow"].shape == (H, W, 2)
    assert s["valid"].shape == (H, W)
