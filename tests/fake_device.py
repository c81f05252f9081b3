"""A stand-in for the request-mode pipeline (``engine._get_executable``'s
``(variables, a1, a2) -> (None, flow_up)``) that behaves like one device
behind asynchronous dispatch: a call returns at once (the launch), and its
result is on the host only once ``np.asarray`` of it returns — ``work_s``
after the later of its launch and the result before it (one device, in
order), and not before ``gate`` is set.  Lets a CPU test hold a batch "on
the device" for as long as it likes and see what the engine does
meanwhile."""

import threading
import time

import numpy as np


class FakeDevice:
    def __init__(self, work_s=0.0, gated=False, shape=(1, 40, 56, 2)):
        self.work_s, self.shape = work_s, shape
        self.gate = threading.Event()
        if not gated:
            self.gate.set()
        #: launch k -> exceptions its drains raise, first drain first
        self.drain_errors = {}
        self.launched = []      # perf_counter of every call
        self.drained = []       # (launch ordinal, perf_counter) of every result read
        self._free = 0.0
        self._lock = threading.Lock()

    def install(self, engine):
        engine._get_executable = lambda bucket, lanes: self
        return self

    def __call__(self, variables, a1, a2):
        with self._lock:
            now = time.perf_counter()
            k = len(self.launched)
            self.launched.append(now)
            self._free = ready = max(self._free, now) + self.work_s
        return None, _Result(self, k, ready)

    def wait_launched(self, n, timeout=10.0):
        deadline = time.perf_counter() + timeout
        while len(self.launched) < n and time.perf_counter() < deadline:
            time.sleep(0.002)
        return len(self.launched) >= n


class _Result:
    def __init__(self, dev, k, ready):
        self.dev, self.k, self.ready = dev, k, ready

    def __array__(self, dtype=None, copy=None):
        assert self.dev.gate.wait(30), "the test never released the device"
        time.sleep(max(self.ready - time.perf_counter(), 0.0))
        errors = self.dev.drain_errors.get(self.k)
        if errors:
            raise errors.pop(0)
        self.dev.drained.append((self.k, time.perf_counter()))
        # every lane of launch k reads k: an answer names its launch
        return np.full(self.dev.shape, float(self.k), np.float32)
