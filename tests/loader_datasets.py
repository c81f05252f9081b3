"""Datasets the loader tests hand to ``ShardedLoader``.

The loader's workers are processes that receive the dataset pickled, so a
dataset's class must be importable by module name, and whatever a test
wants to see of the workers' calls has to cross a process: the ledgers here
are files.  This module imports NumPy and ``raft_tpu.data`` only, as a
worker does (no ``jax``).
"""

import os
import sys
import time

import numpy as np

from raft_tpu.data.datasets import FlowDataset, SampleReadError


def _log(path, line):
    """Append one line to a ledger file (one ``write`` of O_APPEND: whole
    lines from concurrent processes)."""
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    try:
        os.write(fd, f"{line}\n".encode())
    finally:
        os.close(fd)


def ledger(path):
    """The indices a dataset's ``load`` was called with, in call order."""
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [int(x) for x in f.read().split()]


class SynthDataset(FlowDataset):
    """In-memory samples: ``image1`` = index + the sample's own draw
    (so stream checks cover the per-sample RNG), ``image2`` = 2 × index.
    Indices in ``poison`` always fail to decode; ``ledger`` records every
    ``load`` call; ``jax_key`` adds whether ``jax`` is imported where the
    sample was loaded."""

    def __init__(self, n=13, hw=(8, 10), poison=(), ledger=None,
                 jax_key=False, sleep_s=0.0):
        super().__init__()
        self.split = "synthetic"
        self.hw, self.poison, self.ledger = hw, set(poison), ledger
        self.jax_key, self.sleep_s = jax_key, sleep_s
        self.image_list = [(f"synth://{i}/a", f"synth://{i}/b")
                           for i in range(n)]

    def load(self, index, rng=None):
        if self.ledger:
            _log(self.ledger, index)
        if index in self.poison:
            raise SampleReadError(self.image_list[index][0], self, index,
                                  "synthetic corruption")
        time.sleep(self.sleep_s)
        H, W = self.hw
        base = np.full((H, W, 3), float(index), np.float32)
        jitter = (rng.standard_normal((H, W, 3)).astype(np.float32)
                  if rng is not None else 0.0)
        out = {"image1": base + jitter, "image2": base * 2.0,
               "flow": np.zeros((H, W, 2), np.float32),
               "valid": np.ones((H, W), np.float32)}
        if self.jax_key:
            out["jax"] = np.float32(["jax" in sys.modules])
        return out


class CountingDataset:
    """Samples that say who they are (``x``) and the draw they got
    (``r``); not a ``FlowDataset``.  With ``grow``, ``x`` of sample i
    has 2 + i entries."""

    def __init__(self, n, grow=False):
        self.n, self.grow = n, grow

    def __len__(self):
        return self.n

    def load(self, idx, rng):
        return {"x": np.full((2 + idx * self.grow,), idx, np.float32),
                "r": rng.uniform(size=(1,)).astype(np.float32)}


class HeldDataset(CountingDataset):
    """The first load of index ``hold`` waits (up to 20 s) until a fifth
    load has started, and writes whether one did to ``<dir>/in_time``.
    Loads are counted across processes by claiming ``<dir>/call.N``."""

    def __init__(self, n, directory, hold):
        super().__init__(n)
        self.dir, self.hold = directory, hold

    def _claim(self, name):
        try:
            os.close(os.open(os.path.join(self.dir, name),
                             os.O_CREAT | os.O_EXCL))
            return True
        except FileExistsError:
            return False

    def load(self, idx, rng):
        call = 1
        while not self._claim(f"call.{call}"):
            call += 1
        if idx == self.hold and self._claim("held"):
            fifth = os.path.join(self.dir, "call.5")
            t = time.monotonic() + 20.0
            while not os.path.exists(fifth) and time.monotonic() < t:
                time.sleep(0.01)
            _log(os.path.join(self.dir, "in_time"),
                 int(os.path.exists(fifth)))
        return super().load(idx, rng)


class RandomPairs:
    """``n`` deterministic samples of random frames keyed on the index
    (stands in for decode + augment in the multi-host train test)."""

    def __init__(self, n, hw):
        self.n, self.hw = n, hw

    def __len__(self):
        return self.n

    def load(self, index, rng=None):
        H, W = self.hw
        r = np.random.default_rng(1000 + index)
        return {
            "image1": r.uniform(0, 255, (H, W, 3)).astype(np.float32),
            "image2": r.uniform(0, 255, (H, W, 3)).astype(np.float32),
            "flow": (4 * r.standard_normal((H, W, 2))).astype(np.float32),
            "valid": np.ones((H, W), np.float32),
        }
