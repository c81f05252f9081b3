"""Input padding to multiples of 8 (reference ``core/utils/utils.py:7-24``),
or of the model's own ``RAFTConfig.pad_multiple``.

The models downsample by 8, so H and W must be divisible by 8; a model
that splits its 1/8 map into 2x2 windows (arch 'gmflow') needs 16, and
its callers hand that in as ``multiple``.  'sintel'
mode centers the height padding; every other mode puts all height padding at
the bottom.  Width padding is always centered.  Padding is edge-replicate.

``target=(H, W)`` pads up to a fixed bucket shape instead of the next
multiple of 8 — the batched-evaluation path pads every KITTI resolution to
one common shape so the jitted forward compiles once (the placement policy
of the mode is preserved, and edge-replicate rows are identical however
many there are).

Bucket policy lives here too (:func:`ceil_to_multiple`,
:func:`bucket_hw`): both the offline validators
(``raft_tpu/evaluate.py``) and the serving engine
(``raft_tpu/serve/engine.py``) round request shapes to /8-aligned compile
buckets, and keeping the rounding in one place means eval and serve
cannot drift in which shapes they consider "the same program".
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


def ceil_to_multiple(x: int, multiple: int = 8) -> int:
    """Smallest multiple of ``multiple`` that is >= ``x``."""
    return -(-int(x) // multiple) * multiple


def bucket_hw(ht: int, wd: int, multiple: int = 8,
              ladder: Optional[Sequence[Tuple[int, int]]] = None,
              ) -> Tuple[int, int]:
    """The /``multiple``-aligned compile bucket covering an ``(ht, wd)``
    image.

    Without a ``ladder`` this is the exact next-multiple round-up (one
    bucket per distinct aligned shape — what the validators use, where
    the shape population is known up front).  With a ``ladder`` of
    ``(H, W)`` bucket shapes, the smallest ladder entry that covers the
    image wins — a serving engine with unknown traffic uses a coarse
    ladder so nearby resolutions coalesce into one micro-batch instead
    of fragmenting into per-shape programs.  Images larger than every
    ladder entry fall back to the exact round-up (served correctly, at
    the cost of a dedicated compile)."""
    bh, bw = ceil_to_multiple(ht, multiple), ceil_to_multiple(wd, multiple)
    if ladder:
        fits = [(H, W) for (H, W) in ladder if H >= bh and W >= bw]
        if fits:
            return min(fits, key=lambda t: (t[0] * t[1], t))
    return bh, bw


def max_bucket_hw(shapes: Iterable[Tuple[int, int]],
                  multiple: int = 8) -> Tuple[int, int]:
    """One bucket covering every ``(ht, wd)`` in ``shapes`` (the
    validators' pad-everything-to-the-max policy, so a whole mixed-
    resolution split costs ONE compile)."""
    hs, ws = zip(*shapes)
    return ceil_to_multiple(max(hs), multiple), \
        ceil_to_multiple(max(ws), multiple)


class InputPadder:
    """Pads NHWC images so H, W are divisible by ``multiple`` (the model's
    ``RAFTConfig.pad_multiple``; 8 for every architecture with a
    refinement loop) or match ``target``; unpads flow back."""

    def __init__(self, dims, mode: str = "sintel",
                 target: Optional[Tuple[int, int]] = None,
                 multiple: int = 8):
        self.ht, self.wd = dims[-3:-1] if len(dims) >= 3 else dims
        if target is None:
            pad_ht = ceil_to_multiple(self.ht, multiple) - self.ht
            pad_wd = ceil_to_multiple(self.wd, multiple) - self.wd
        else:
            pad_ht, pad_wd = target[0] - self.ht, target[1] - self.wd
            assert pad_ht >= 0 and pad_wd >= 0, (
                f"target {target} smaller than image "
                f"({self.ht}, {self.wd})")
        if mode == "sintel":
            self._pad = [pad_wd // 2, pad_wd - pad_wd // 2,
                         pad_ht // 2, pad_ht - pad_ht // 2]
        else:
            self._pad = [pad_wd // 2, pad_wd - pad_wd // 2, 0, pad_ht]

    def pad(self, *inputs):
        l, r, t, b = self._pad
        out = [jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0)), mode="edge")
               for x in inputs]
        return out if len(out) > 1 else out[0]

    def pad_np(self, *inputs):
        """Host-side variant: ``(H, W, C)`` numpy arrays, for assembling
        batched eval inputs without a device round-trip per image."""
        l, r, t, b = self._pad
        out = [np.pad(x, ((t, b), (l, r), (0, 0)), mode="edge")
               for x in inputs]
        return out if len(out) > 1 else out[0]

    def unpad(self, x):
        ht, wd = x.shape[-3:-1]
        l, r, t, b = self._pad
        return x[..., t:ht - b, l:wd - r, :]
