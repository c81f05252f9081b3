"""The one general generator: inputs and load from ``--seed`` and a traffic
file (``benchmark/traffic/<name>.json``).

Two kinds of traffic exist so far, and each is only parameters:

- ``train``: an on-disk FlyingChairs-layout tree of ``pairs`` image pairs
  with ground-truth flow, which the program's own loader and augmentor read;
- ``serve``: ``clients`` callers in a closed loop (a caller sends its next
  pair when the flow of the last comes back), pairs drawn from a pool of
  ``pool`` seeded images of one ``shape``; no two submissions of any callers
  closer together than ``min_gap_ms`` (0: no such rule).

The closed-loop client and the percentile arithmetic follow
``scripts/bench_serve.py`` (sound; copied so that the yardstick cannot
change under a later PR).  Every seed gives the same *amount* of work: the
same sizes and counts, other pixels and another order.
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np


def load(path):
    with open(path) as f:
        return json.load(f)


def rng_for(seed, stream):
    """Independent generator per purpose; seeds pass 2**31."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def make_pair(rng, hw, max_shift=6):
    """A textured frame, the frame moved by a smooth displacement field,
    and that field: no two pairs, and no two rows of a batch, alike."""
    h, w = hw
    coarse = rng.integers(0, 255, size=(h // 8 + 1, w // 8 + 1, 3))
    img1 = np.kron(coarse, np.ones((8, 8, 1)))[:h, :w]
    img1 = np.clip(img1 + rng.normal(0, 6, img1.shape), 0, 255)
    dx, dy = (int(v) for v in rng.integers(-max_shift, max_shift + 1, size=2))
    img2 = np.roll(img1, (dy, dx), axis=(0, 1))
    img2 = np.clip(img2 + rng.normal(0, 2, img2.shape), 0, 255)
    flow = np.broadcast_to(np.float32([dx, dy]), (h, w, 2)).copy()
    return img1.astype(np.uint8), img2.astype(np.uint8), flow


def write_flo(path, flow):
    """Middlebury .flo (the format FlyingChairs ships)."""
    h, w = flow.shape[:2]
    with open(path, "wb") as f:
        np.array([202021.25], np.float32).tofile(f)
        np.array([w, h], np.int32).tofile(f)
        flow.astype(np.float32).tofile(f)


def write_chairs_tree(root, seed, image_hw, pairs):
    """``<root>/datasets/FlyingChairs_release/data/NNNNN_img{1,2}.ppm`` +
    ``NNNNN_flow.flo`` and a split file of all-training lines."""
    from PIL import Image

    rng = rng_for(seed, 1)
    data = os.path.join(root, "datasets", "FlyingChairs_release", "data")
    os.makedirs(data, exist_ok=True)
    for i in range(pairs):
        img1, img2, flow = make_pair(rng, image_hw)
        Image.fromarray(img1).save(
            os.path.join(data, f"{i + 1:05d}_img1.ppm"), format="PPM")
        Image.fromarray(img2).save(
            os.path.join(data, f"{i + 1:05d}_img2.ppm"), format="PPM")
        write_flo(os.path.join(data, f"{i + 1:05d}_flow.flo"), flow)
    split = os.path.join(root, "chairs_split.txt")
    with open(split, "w") as f:
        f.write("1\n" * pairs)
    return os.path.join(root, "datasets"), split


def make_pool(seed, shape, pool):
    rng = rng_for(seed, 2)
    return [make_pair(rng, tuple(shape))[:2] for _ in range(pool)]


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


class ClosedLoop:
    """``clients`` threads, each: submit, wait for the flow, submit again.

    ``submit(image1, image2)`` returns a future.  Runs for ``seconds``;
    a request sent inside the window is waited for past its close (a late
    answer is late, not wrong), ``grace`` seconds at the most.  Keeps the
    flows of ``keep_clients`` callers for the correctness sample.

    ``min_gap_ms`` keeps any two submissions that far apart: callers that
    are independent of each other do not start, or send, in the same few
    milliseconds.  It binds while the callers start (they pass one by one)
    and hardly ever after, when a saturated engine's replies, and so the
    next submissions, come a service time apart.  With a gap above the
    engine's batching wait every run makes the same batches; without it the
    callers' threads start within a few milliseconds of each other and the
    engine groups them by chance, other groups in every run."""

    def __init__(self, submit, pool, clients, seed, keep_clients=4,
                 grace=60.0, min_gap_ms=0.0):
        self.submit, self.pool, self.clients = submit, pool, clients
        self.min_gap = float(min_gap_ms) / 1e3
        self._gate = threading.Lock()
        self._last_submit = float("-inf")
        rng = rng_for(seed, 3)
        self.order = [rng.permutation(len(pool)) for _ in range(clients)]
        self.keep = set(int(c) for c in rng.choice(
            clients, size=min(keep_clients, clients), replace=False))
        self.grace = grace
        self.records = []          # (client, k, pool_index, t_sub, t_done, ok)
        self.flows = {}            # (client, k) -> flow
        self._lock = threading.Lock()

    def _client(self, c, t_end):
        k = 0
        while time.perf_counter() < t_end:
            idx = int(self.order[c][k % len(self.pool)])
            a, b = self.pool[idx]
            ok, flow = True, None
            with self._gate:
                wait = self._last_submit + self.min_gap - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                t0 = self._last_submit = time.perf_counter()
                try:
                    fut = self.submit(a, b)
                except Exception:
                    ok = False
            try:
                if ok:
                    flow = fut.result(timeout=max(t_end - t0, 0) + self.grace)
            except Exception:  # a failed request counts, it does not stop
                ok = False
            t1 = time.perf_counter()
            with self._lock:
                self.records.append((c, k, idx, t0, t1, ok))
                if ok and c in self.keep:
                    self.flows[(c, k)] = flow
            k += 1

    def run(self, seconds, on_start=None):
        self.t_start = time.perf_counter()
        t_end = self.t_start + seconds
        threads = [threading.Thread(target=self._client, args=(c, t_end),
                                    daemon=True)
                   for c in range(self.clients)]
        for t in threads:
            t.start()
        if on_start is not None:
            on_start()
        for t in threads:
            t.join(timeout=seconds + self.grace + 30)
        self.t_close = t_end
        return self

    def summary(self, seconds):
        """Whole-window figures: replies inside the window over its length;
        latency over every request sent in it, a failure counted as the
        worst (the wait it cost, at least the slowest success)."""
        rec = list(self.records)
        lat_ok = [(t1 - t0) * 1e3 for (_, _, _, t0, t1, ok) in rec if ok]
        worst = max(lat_ok) if lat_ok else float("inf")
        lat = [(t1 - t0) * 1e3 if ok else max((t1 - t0) * 1e3, worst)
               for (_, _, _, t0, t1, ok) in rec]
        inside = sum(1 for (_, _, _, _, t1, ok) in rec
                     if ok and t1 <= self.t_close)
        p50 = percentile(lat, 50) if lat else None
        # to be read by hand: (seconds into the window, ms) of the requests
        # 3 % and more over the median, the first 40 in order of sending
        slow = [(round(r[3] - self.t_start, 2), round((r[4] - r[3]) * 1e3))
                for r in sorted(rec, key=lambda r: r[3])
                if p50 and (r[4] - r[3]) * 1e3 > 1.03 * p50][:40]
        return {
            "slow": slow,
            "attempted": len(rec),
            "failed": sum(1 for r in rec if not r[5]),
            "pairs_per_s": inside / seconds,
            "latency_p50_ms": p50,
            "latency_p95_ms": percentile(lat, 95) if lat else None,
        }

    def sample(self, seed, n):
        """``n`` finished requests of the kept callers, drawn from the
        seed: [((client, k), pool_index)]."""
        done = sorted((c, k, idx) for (c, k, idx, _, _, ok) in self.records
                      if ok and (c, k) in self.flows)
        if not done:
            return []
        rng = rng_for(seed, 4)
        pick = rng.choice(len(done), size=min(n, len(done)), replace=False)
        return [((done[i][0], done[i][1]), done[i][2]) for i in sorted(pick)]
