"""Optical-flow datasets and the per-host sharded input pipeline.

Parity with the reference ``core/datasets.py`` (C8 in SURVEY.md): the five
dataset classes (MpiSintel, FlyingChairs, FlyingThings3D, KITTI, HD1K) with
identical directory conventions, the replicate-and-concat dataset mixing
(``100*clean + 100*final + ...``, datasets.py:218-221), grayscale tiling and
alpha dropping (datasets.py:67-73), and the dense validity rule
``|flow| < 1000`` (datasets.py:88).

TPU-first redesign (replaces torch DataLoader + nn.DataParallel scatter):

- Samples are NumPy NHWC; batches are plain dicts of stacked arrays handed
  straight to ``jax.device_put`` — no torch anywhere in the input path.
- ``ShardedLoader`` owns a *global* shuffle per epoch from a seeded
  generator, then each host takes a disjoint stride of the permutation
  (``indices[host_id::num_hosts]``): every host feeds its local devices and
  the SPMD train step sees a globally-shuffled batch — the pod-scale
  replacement for DataParallel's single-process scatter (train.py:138).
- Per-sample augmentation RNG is derived from
  ``SeedSequence([seed, epoch, index])`` — deterministic and independent of
  worker scheduling, unlike the reference's per-worker reseed
  (datasets.py:45-51).
- Decode/augment runs in ``num_workers`` worker processes, each a fresh
  interpreter started with fork + exec (``raft_tpu/data/workers.py``), that
  write every sample straight into its row of a shared batch slot.  Not
  threads: the Python and NumPy work between the native calls holds the
  interpreter's lock, and eight threads were off a core 36-41 % of a
  sample's time (PERF.md, PR 37).  Not a bare ``fork``: a forked copy of a
  process that holds libtpu and JAX's threads inherits their locks, so the
  loader stays safe after JAX initializes.  Chaos decisions, counters and
  quarantine events stay in the parent (``SampleSource``).
"""

from __future__ import annotations

import dataclasses
import os
import os.path as osp
import time
from glob import glob
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from raft_tpu import chaos
from raft_tpu.data import frame_utils
from raft_tpu.data.augment import FlowAugmentor, SparseFlowAugmentor
from raft_tpu.obs.registry import default_registry


class SampleReadError(ValueError):
    """A per-sample read/decode failure with its provenance attached.

    The bare reader errors (``ValueError: truncated .flo``) name the
    symptom but not which of 20k files is bad; every reader call in
    :meth:`FlowDataset.load` is wrapped so the exception (and the
    quarantine event built from it, docs/ROBUSTNESS.md) carries the
    dataset name, split, sample index, and file path.  Subclasses
    ``ValueError`` so existing handlers of decode errors keep working.
    """

    def __init__(self, path: str, dataset=None, index=None,
                 detail: str = ""):
        self.path = path
        self.dataset_name = getattr(dataset, "name", None) \
            or type(dataset).__name__
        self.split = getattr(dataset, "split", None)
        self.index = index
        super().__init__(
            f"{path}: {detail} [dataset={self.dataset_name} "
            f"split={self.split or '-'} sample={index}]")


def _read_sample(ds, index: int, path: str, reader):
    """Run one reader call with sample context attached to decode/IO
    failures (real corruption raises ValueError/OSError out of
    frame_utils; anything else is a bug and propagates untouched)."""
    try:
        return reader(path)
    except SampleReadError:
        raise
    except (ValueError, OSError) as e:
        raise SampleReadError(path, ds, index, str(e)) from e


def _to_rgb(img: np.ndarray) -> np.ndarray:
    """Grayscale -> 3-channel tile, drop alpha (reference datasets.py:67-73)."""
    img = np.asarray(img)
    if img.ndim == 2:
        return np.tile(img[..., None], (1, 1, 3)).astype(np.uint8)
    return img[..., :3].astype(np.uint8)


class FlowDataset:
    """Base dataset: lists of (img1, img2) paths and flow paths.

    ``dataset * n`` replicates the sample list and ``a + b`` concatenates —
    the reference's ``__rmul__`` mixing idiom (datasets.py:93-96) — except
    both return NEW datasets instead of mutating in place.
    """

    def __init__(self, aug_params: Optional[dict] = None,
                 sparse: bool = False):
        self.sparse = sparse
        self.is_test = False
        self.augmentor = None
        # Sample-error provenance (SampleReadError / quarantine events):
        # concrete datasets overwrite split after super().__init__.
        self.name = type(self).__name__
        self.split: Optional[str] = None
        if aug_params is not None:
            cls = SparseFlowAugmentor if sparse else FlowAugmentor
            self.augmentor = cls(**aug_params)
        self.flow_list: List[str] = []
        self.image_list: List[Tuple[str, str]] = []
        self.extra_info: List[tuple] = []

    # -- mixing ----------------------------------------------------------
    def _clone_shell(self) -> "FlowDataset":
        out = FlowDataset.__new__(FlowDataset)
        out.sparse = self.sparse
        out.is_test = self.is_test
        out.augmentor = self.augmentor
        out.name = self.name
        out.split = self.split
        out.flow_list = list(self.flow_list)
        out.image_list = list(self.image_list)
        out.extra_info = list(self.extra_info)
        return out

    def __mul__(self, v: int) -> "FlowDataset":
        out = self._clone_shell()
        out.flow_list = v * out.flow_list
        out.image_list = v * out.image_list
        out.extra_info = v * out.extra_info
        return out

    __rmul__ = __mul__

    def __add__(self, other: "FlowDataset") -> "ConcatFlowDataset":
        return ConcatFlowDataset([self, other])

    def __len__(self) -> int:
        return len(self.image_list)

    # -- loading ---------------------------------------------------------
    def _sample_parts(self, index: int):
        """Which member dataset + local index serves ``index`` (overridden
        by ConcatFlowDataset)."""
        return self, index

    def load(self, index: int,
             rng: Optional[np.random.Generator] = None) -> Dict[str, np.ndarray]:
        """Load (and optionally augment) one sample.

        Returns NHWC float32 ``image1``/``image2`` in [0,255], ``flow``
        (H,W,2) float32, ``valid`` (H,W) float32.  Test-mode datasets return
        images + ``extra_info`` only (reference datasets.py:36-43).
        """
        ds, index = self._sample_parts(index)
        index = index % len(ds.image_list)
        img1 = _read_sample(ds, index, ds.image_list[index][0],
                            lambda p: _to_rgb(frame_utils.read_gen(p)))
        img2 = _read_sample(ds, index, ds.image_list[index][1],
                            lambda p: _to_rgb(frame_utils.read_gen(p)))

        if ds.is_test:
            return {"image1": img1.astype(np.float32),
                    "image2": img2.astype(np.float32),
                    "extra_info": ds.extra_info[index]}

        valid = None
        if ds.sparse:
            flow, valid = _read_sample(ds, index, ds.flow_list[index],
                                       frame_utils.read_flow_kitti)
        else:
            flow = _read_sample(
                ds, index, ds.flow_list[index],
                lambda p: np.asarray(frame_utils.read_gen(p), np.float32))

        if ds.augmentor is not None:
            if rng is None:
                rng = np.random.default_rng()
            if ds.sparse:
                img1, img2, flow, valid = ds.augmentor(
                    rng, img1, img2, flow, valid)
            else:
                img1, img2, flow = ds.augmentor(rng, img1, img2, flow)

        if valid is None:
            valid = ((np.abs(flow[..., 0]) < 1000)
                     & (np.abs(flow[..., 1]) < 1000))
        return {"image1": img1.astype(np.float32),
                "image2": img2.astype(np.float32),
                "flow": flow.astype(np.float32),
                "valid": np.asarray(valid, np.float32)}


class ConcatFlowDataset(FlowDataset):
    """Concatenation of datasets with possibly different augmentors/sparsity
    (the reference concatenates via torch ConcatDataset, datasets.py:210)."""

    def __init__(self, parts: Sequence[FlowDataset]):
        flat: List[FlowDataset] = []
        for p in parts:
            flat.extend(p.parts if isinstance(p, ConcatFlowDataset) else [p])
        self.parts = flat
        self.is_test = False
        self.name = "Concat(" + "+".join(p.name for p in flat) + ")"
        self.split = None  # per-sample context comes from the member
        self._offsets = np.cumsum([len(p) for p in flat])

    def __len__(self) -> int:
        return int(self._offsets[-1]) if len(self.parts) else 0

    def __mul__(self, v: int) -> "ConcatFlowDataset":
        return ConcatFlowDataset(list(self.parts) * v)

    __rmul__ = __mul__

    def _sample_parts(self, index: int):
        index = index % len(self)
        part = int(np.searchsorted(self._offsets, index, side="right"))
        local = index - (0 if part == 0 else int(self._offsets[part - 1]))
        return self.parts[part], local


# ---------------------------------------------------------------------------
# Concrete datasets (directory conventions: reference datasets.py:102-197)
# ---------------------------------------------------------------------------

class MpiSintel(FlowDataset):
    """Consecutive-frame pairs per scene (reference datasets.py:102-118)."""

    def __init__(self, aug_params=None, split="training",
                 root="datasets/Sintel", dstype="clean"):
        super().__init__(aug_params)
        self.split = split
        flow_root = osp.join(root, split, "flow")
        image_root = osp.join(root, split, dstype)
        if split == "test":
            self.is_test = True
        for scene in sorted(os.listdir(image_root)):
            images = sorted(glob(osp.join(image_root, scene, "*.png")))
            for i in range(len(images) - 1):
                self.image_list.append((images[i], images[i + 1]))
                self.extra_info.append((scene, i))
            if split != "test":
                self.flow_list += sorted(
                    glob(osp.join(flow_root, scene, "*.flo")))


class FlyingChairs(FlowDataset):
    """Train/val split via ``chairs_split.txt`` (reference
    datasets.py:121-134)."""

    def __init__(self, aug_params=None, split="training",
                 root="datasets/FlyingChairs_release/data",
                 split_file="chairs_split.txt"):
        super().__init__(aug_params)
        self.split = split
        images = sorted(glob(osp.join(root, "*.ppm")))
        flows = sorted(glob(osp.join(root, "*.flo")))
        assert len(images) // 2 == len(flows), (len(images), len(flows))
        split_ids = np.loadtxt(split_file, dtype=np.int32)
        if split in ("training", "train"):
            want = 1
        elif split in ("validation", "val"):
            want = 2
        else:
            raise ValueError(f"unknown FlyingChairs split: {split!r}")
        for i in range(len(flows)):
            if split_ids[i] == want:
                self.flow_list.append(flows[i])
                self.image_list.append((images[2 * i], images[2 * i + 1]))


class FlyingThings3D(FlowDataset):
    """Left camera, both temporal directions; the into_past direction swaps
    the image order (reference datasets.py:137-158)."""

    def __init__(self, aug_params=None, root="datasets/FlyingThings3D",
                 dstype="frames_cleanpass"):
        super().__init__(aug_params)
        self.split = dstype
        for cam in ["left"]:
            for direction in ["into_future", "into_past"]:
                image_dirs = sorted(glob(osp.join(root, dstype, "TRAIN/*/*")))
                image_dirs = sorted(osp.join(f, cam) for f in image_dirs)
                flow_dirs = sorted(
                    glob(osp.join(root, "optical_flow/TRAIN/*/*")))
                flow_dirs = sorted(
                    osp.join(f, direction, cam) for f in flow_dirs)
                for idir, fdir in zip(image_dirs, flow_dirs):
                    images = sorted(glob(osp.join(idir, "*.png")))
                    flows = sorted(glob(osp.join(fdir, "*.pfm")))
                    for i in range(len(flows) - 1):
                        if direction == "into_future":
                            self.image_list.append((images[i], images[i + 1]))
                            self.flow_list.append(flows[i])
                        else:
                            self.image_list.append((images[i + 1], images[i]))
                            self.flow_list.append(flows[i + 1])


class KITTI(FlowDataset):
    """Sparse ``*_10/_11.png`` pairs (reference datasets.py:161-177)."""

    def __init__(self, aug_params=None, split="training",
                 root="datasets/KITTI"):
        super().__init__(aug_params, sparse=True)
        self.split = split
        if split == "testing":
            self.is_test = True
        root = osp.join(root, split)
        images1 = sorted(glob(osp.join(root, "image_2/*_10.png")))
        images2 = sorted(glob(osp.join(root, "image_2/*_11.png")))
        for img1, img2 in zip(images1, images2):
            self.extra_info.append((osp.basename(img1),))
            self.image_list.append((img1, img2))
        if split == "training":
            self.flow_list = sorted(glob(osp.join(root, "flow_occ/*_10.png")))


class HD1K(FlowDataset):
    """Sparse HD1K sequences, scanned by sequence index (reference
    datasets.py:180-196)."""

    def __init__(self, aug_params=None, root="datasets/HD1k"):
        super().__init__(aug_params, sparse=True)
        seq_ix = 0
        while True:
            flows = sorted(glob(osp.join(
                root, "hd1k_flow_gt", "flow_occ/%06d_*.png" % seq_ix)))
            images = sorted(glob(osp.join(
                root, "hd1k_input", "image_2/%06d_*.png" % seq_ix)))
            if not flows:
                break
            for i in range(len(flows) - 1):
                self.flow_list.append(flows[i])
                self.image_list.append((images[i], images[i + 1]))
            seq_ix += 1


# ---------------------------------------------------------------------------
# Stage mixtures (reference fetch_dataloader, datasets.py:199-234)
# ---------------------------------------------------------------------------

def fetch_dataset(stage: str, image_size: Tuple[int, int],
                  root: str = "datasets", train_ds: str = "C+T+K+S+H",
                  split_file: str = "chairs_split.txt") -> FlowDataset:
    """Build the per-stage training mixture with the reference's aug params
    and replication weights (datasets.py:202-228)."""
    crop = {"crop_size": tuple(image_size)}
    if stage == "chairs":
        aug = dict(crop, min_scale=-0.1, max_scale=1.0, do_flip=True)
        return FlyingChairs(aug, split="training",
                            root=osp.join(root, "FlyingChairs_release/data"),
                            split_file=split_file)
    if stage == "things":
        aug = dict(crop, min_scale=-0.4, max_scale=0.8, do_flip=True)
        things_root = osp.join(root, "FlyingThings3D")
        clean = FlyingThings3D(aug, root=things_root,
                               dstype="frames_cleanpass")
        final = FlyingThings3D(aug, root=things_root,
                               dstype="frames_finalpass")
        return clean + final
    if stage == "sintel":
        aug = dict(crop, min_scale=-0.2, max_scale=0.6, do_flip=True)
        things = FlyingThings3D(aug, root=osp.join(root, "FlyingThings3D"),
                                dstype="frames_cleanpass")
        clean = MpiSintel(aug, split="training",
                          root=osp.join(root, "Sintel"), dstype="clean")
        final = MpiSintel(aug, split="training",
                          root=osp.join(root, "Sintel"), dstype="final")
        if train_ds == "C+T+K+S+H":
            kitti = KITTI(dict(crop, min_scale=-0.3, max_scale=0.5,
                               do_flip=True), root=osp.join(root, "KITTI"))
            hd1k = HD1K(dict(crop, min_scale=-0.5, max_scale=0.2,
                             do_flip=True), root=osp.join(root, "HD1k"))
            return 100 * clean + 100 * final + 200 * kitti + 5 * hd1k + things
        if train_ds == "C+T+K/S":
            return 100 * clean + 100 * final + things
        raise ValueError(f"unknown train_ds mixture: {train_ds!r}")
    if stage == "kitti":
        aug = dict(crop, min_scale=-0.2, max_scale=0.4, do_flip=False)
        return KITTI(aug, split="training", root=osp.join(root, "KITTI"))
    raise ValueError(f"unknown stage: {stage!r}")


# ---------------------------------------------------------------------------
# One sample, anywhere: the part of a loader that crosses into a worker
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SampleHeader:
    """What became of one sample: the seconds it took, the quarantine
    events it caused (``sample_quarantine`` fields) and the error that
    ended it, if any."""

    seconds: float
    quarantined: List[dict]
    error: Optional[BaseException] = None


@dataclasses.dataclass(frozen=True)
class SampleSource:
    """The dataset with the loader's seed and self-healing settings: all a
    worker process needs to load any sample of the stream.  Its ``load``
    touches no counter, sink or chaos plan; the parent books the header."""

    dataset: FlowDataset
    seed: int
    retries: int
    resamples: int

    #: Seed-stream salt separating replacement-index draws from the
    #: per-sample augmentation streams (arbitrary constant).
    RESAMPLE_SALT = 0x51A7

    def load(self, epoch: int, index: int, *, corrupt: bool = False,
             crash: bool = False
             ) -> Tuple[Optional[Dict[str, np.ndarray]], SampleHeader]:
        """Load one sample, self-healing decode failures.

        Healthy path: one ``dataset.load`` with the generator of
        ``SeedSequence([seed, epoch, index])``.  On ValueError/OSError the
        sample is retried then quarantined and a deterministic replacement
        drawn (``ShardedLoader.sample_retries``).  ``corrupt`` and
        ``crash`` are the chaos decisions the parent drew for this sample:
        the first read fails as a corrupt file, or the worker fails as a
        bug would.  Any other exception (a loader bug) propagates."""
        t0 = time.perf_counter()
        if crash:
            from raft_tpu.chaos import InjectedWorkerCrash

            return None, SampleHeader(0.0, [], InjectedWorkerCrash(
                "chaos-injected loader-worker crash (not a decode "
                "error: must fail the run, not quarantine)"))
        index = int(index)
        idx, last_err, quarantined = index, None, []
        for resample in range(self.resamples + 1):
            for attempt in range(self.retries + 1):
                # Fresh generator per attempt: a failed load may have
                # consumed part of the stream, and the replacement
                # sample must see exactly the draw it would get were it
                # drawn first-class (stream determinism).
                rng = np.random.default_rng(
                    np.random.SeedSequence([self.seed, epoch, idx]))
                try:
                    if corrupt and resample == attempt == 0:
                        ds, i = self.dataset._sample_parts(idx)
                        i %= len(ds.image_list)
                        raise SampleReadError(ds.image_list[i][0], ds, i,
                                              "chaos-injected corrupt sample")
                    sample = self.dataset.load(idx, rng)
                except (ValueError, OSError) as e:
                    last_err = e
                else:
                    return sample, SampleHeader(time.perf_counter() - t0,
                                                quarantined)
            quarantined.append(dict(
                dataset=getattr(last_err, "dataset_name", None)
                or getattr(self.dataset, "name",
                           type(self.dataset).__name__),
                split=getattr(last_err, "split", None),
                path=getattr(last_err, "path", None),
                epoch=int(epoch), index=int(idx), original_index=index,
                resample=resample, retries=int(self.retries),
                error=f"{type(last_err).__name__}: {last_err}"))
            r = np.random.default_rng(np.random.SeedSequence(
                [self.seed, epoch, index, self.RESAMPLE_SALT, resample]))
            idx = int(r.integers(len(self.dataset)))
        err = RuntimeError(
            f"sample {index} and {self.resamples} replacement "
            f"draw(s) all failed to load — giving up (last error: "
            f"{type(last_err).__name__}: {last_err})")
        err.__cause__ = last_err
        return None, SampleHeader(time.perf_counter() - t0, quarantined, err)


# ---------------------------------------------------------------------------
# Sharded loader
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardedLoader:
    """Globally-shuffled, per-host-sharded, process-prefetched batch
    iterator.

    Replaces the reference's ``DataLoader(shuffle=True, num_workers=4,
    drop_last=True)`` (datasets.py:230-231).  Batches are dicts of NHWC
    float32 arrays with leading dim = per-host batch size, each a view of
    a shared batch slot that ``num_workers`` worker processes filled
    (``raft_tpu/data/workers.py``).
    """

    dataset: FlowDataset
    batch_size: int            # per-host batch size
    seed: int = 1234
    num_hosts: int = 1
    host_id: int = 0
    num_workers: int = 4
    drop_last: bool = True
    # Decode-window depth in BATCHES: how many batches of samples the
    # workers keep in flight ahead of the consumer.  0 = the
    # legacy default of max(2*batch_size, 2*num_workers) SAMPLES.
    # Raise it when per-sample decode latency is spiky (network
    # filesystems) so a slow sample doesn't drain the window; it bounds
    # decoded-sample host RAM at ~prefetch_batches*batch_size samples.
    prefetch_batches: int = 0
    # Self-healing sample reads (docs/ROBUSTNESS.md): a decode/IO error
    # (ValueError/OSError — a corrupt image, a truncated .flo) is
    # retried ``sample_retries`` times against the SAME file (transient
    # filesystem flakes), then the sample is QUARANTINED — skipped with
    # a `sample_quarantine` JSONL event + `raft_data_quarantined_total`
    # counter — and a deterministic replacement index (keyed on
    # seed/epoch/index, NOT on wall clock or scheduling) is drawn so
    # batch shape and the rest of the stream are unchanged.  Up to
    # ``sample_resamples`` replacements are tried before the loader
    # gives up: one bad file costs one event, a rotten dataset still
    # fails loudly.  Non-decode errors (a loader bug) propagate.
    sample_retries: int = 1
    sample_resamples: int = 8
    # Telemetry destinations for quarantine; None = the process-wide
    # defaults (train() points these at its own sink/registry).
    sink: Optional[object] = None
    registry: Optional[object] = None

    def __post_init__(self):
        assert 0 <= self.host_id < self.num_hosts
        assert len(self.dataset) > 0, "empty dataset"
        assert self.prefetch_batches >= 0, self.prefetch_batches
        assert self.sample_retries >= 0, self.sample_retries
        assert self.sample_resamples >= 0, self.sample_resamples
        assert self.num_workers >= 1, self.num_workers
        self.quarantined_total = 0

    def epoch_indices(self, epoch: int) -> np.ndarray:
        """The host's sample indices for ``epoch`` — a disjoint stride of a
        global permutation shared by all hosts (same seed everywhere)."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, epoch]))
        perm = rng.permutation(len(self.dataset))
        return perm[self.host_id::self.num_hosts]

    def _source(self) -> SampleSource:
        return SampleSource(self.dataset, self.seed, self.sample_retries,
                            self.sample_resamples)

    @staticmethod
    def _chaos_draws() -> Tuple[bool, bool]:
        """This sample's chaos decisions, drawn here in the parent where
        the installed plan lives: ``(corrupt, crash)``."""
        crash = chaos.should_inject("worker_err", point="data.loader_worker")
        corrupt = chaos.should_inject("corrupt_image",
                                      point="data.sample_read")
        return corrupt, crash

    def _load_one(self, epoch: int, index: int) -> Dict[str, np.ndarray]:
        """Load one sample in this process, booked as a worker's would be
        (the stream's first sample, which fixes the batch layout)."""
        corrupt, crash = self._chaos_draws()
        sample, header = self._source().load(epoch, index, corrupt=corrupt,
                                              crash=crash)
        self._book(header)
        if header.error is not None:
            raise header.error
        return sample

    def _metrics(self):
        """Where this loader's counters go: ``registry``, else the
        process-wide default."""
        return self.registry if self.registry is not None \
            else default_registry()

    def _book(self, header: SampleHeader) -> None:
        """A sample's header, in the parent: its quarantine events, and
        the seconds a worker spent loading it (decode + augment) — the
        cumulative pair behind "how busy are the loader workers"
        (``loader_busy_share.train``; the stage clock's producer records
        quote the process-wide totals, docs/OBSERVABILITY.md)."""
        from raft_tpu.obs import stages
        from raft_tpu.obs.events import default_sink

        reg = self._metrics()
        for event in header.quarantined:
            self.quarantined_total += 1
            reg.counter(
                "raft_data_quarantined_total",
                "samples skipped after repeated read failures "
                "(replaced by a deterministic resample)").inc()
            sink = self.sink if self.sink is not None else default_sink()
            sink.emit("sample_quarantine", **event)
        if header.error is not None:
            return
        stages.bump("data_sample_seconds", header.seconds)
        stages.bump("data_samples", 1)
        reg.counter("raft_data_sample_seconds_total",
                    "loader-worker seconds spent loading samples "
                    "(decode + augment)").inc(header.seconds)
        reg.counter("raft_data_samples_total",
                    "samples the loader workers delivered").inc()

    def steps_per_epoch(self) -> int:
        """Per-host batches per epoch (constant across epochs: the global
        permutation is resharded but its length never changes)."""
        n = len(self.epoch_indices(0))
        return n // self.batch_size if self.drop_last \
            else -(-n // self.batch_size)

    def batches_from_step(self, step: int) -> Iterator[Dict[str, np.ndarray]]:
        """Resume the stream as if ``step`` batches had already been drawn —
        auto-resume continues the shuffle instead of replaying epoch 0."""
        spe = self.steps_per_epoch()
        if spe == 0:
            raise ValueError(
                f"per-host dataset share smaller than "
                f"batch_size={self.batch_size} with drop_last — no "
                "batches would ever be produced")
        start_epoch, skip = divmod(step, spe)
        return self.batches(start_epoch, skip_batches=skip)

    def _samples(self, start_epoch: int, skip_batches: int):
        """``(epoch, index, ends_epoch)`` for every sample of the stream,
        epoch after epoch without end; ``ends_epoch`` marks the last sample
        an epoch uses (a batch never spans two epochs)."""
        epoch = start_epoch
        while True:
            idx = self.epoch_indices(epoch)
            n = len(idx)
            usable = (n // self.batch_size) * self.batch_size \
                if self.drop_last else n
            if usable == 0:
                raise ValueError(
                    f"per-host dataset share ({n} samples) smaller than "
                    f"batch_size={self.batch_size} with drop_last — no "
                    "batches would ever be produced")
            skipped = 0
            if epoch == start_epoch and skip_batches:
                skipped = min(skip_batches * self.batch_size, usable)
            for k in range(skipped, usable):
                yield epoch, idx[k], k == usable - 1
            epoch += 1

    def batches(self, start_epoch: int = 0,
                skip_batches: int = 0) -> Iterator[Dict[str, np.ndarray]]:
        """Infinite batch stream, epoch after epoch (the reference wraps its
        loader in an outer while-loop, train.py:161-208).  ``skip_batches``
        drops the first batches of the first epoch without decoding them
        (checkpoint resume mid-epoch)."""
        from raft_tpu.data.workers import Feed

        # Bounded prefetch: a fixed window of samples in flight, so the
        # workers can't race ahead of the consumer and fill an entire
        # epoch of slots.  The depth is the ``prefetch_batches`` knob (in
        # batches); 0 keeps the legacy ~2-batch default.  The window runs
        # on across epochs: the next epoch's first samples decode while
        # this one's last are consumed, so a short epoch does not leave
        # the workers idle at its end.
        window = (self.prefetch_batches * self.batch_size
                  if self.prefetch_batches > 0
                  else max(2 * self.batch_size, 2 * self.num_workers))
        samples = self._samples(start_epoch, skip_batches)
        epoch, i, ends_epoch = next(samples)
        first = self._load_one(epoch, i)
        with Feed(self._source(), first, ends_epoch, self.batch_size,
                  self.num_workers, self._book) as feed:
            def submit():
                epoch, i, ends_epoch = next(samples)
                feed.submit(epoch, i, ends_epoch, *self._chaos_draws())

            for _ in range(window - 1):
                submit()
            while True:
                rows, batch = feed.next_batch()
                for _ in range(rows):
                    submit()
                yield batch
