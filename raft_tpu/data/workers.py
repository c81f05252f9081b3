"""Loader worker processes and the shared batch slots they write into.

``ShardedLoader.batches`` decodes and augments in ``num_workers`` worker
processes.  A sample is ~25 ms of CPU on the chip's host, and the Python,
NumPy and ``astype`` work between the native calls holds the interpreter's
lock: eight threads of one process delivered 112-124 pairs/s there, a
sample off a core 36-41 % of its wall time, where eight processes deliver
189 (PERF.md section 6, PR 37).

**How a worker starts.**  As a fresh interpreter, ``python -m
raft_tpu.data.workers <fd>`` (fork + exec, like ``subprocess``): nothing of
the parent's address space comes along.  Never ``fork`` alone: a forked
copy of a process that holds libtpu and JAX's threads inherits locks that
those threads, absent in the copy, will never release.  Nor
``multiprocessing``'s ``spawn``, which runs the parent's ``__main__``
script again in every worker (``benchmark/run.py``, ``train.py``: JAX and
the TPU's start-up code).  A worker imports ``raft_tpu.data`` (NumPy, cv2,
PIL) and no ``jax``; the dataset reaches it pickled, so its class must be
importable by module name.  Workers sit in a session of their own, so a
signal sent to the parent's process group (Ctrl-C, a preemption notice)
reaches the parent alone; a worker ends when its socket reaches end of
file, which the parent's exit also brings about.

**The channels.**  ``AF_UNIX`` ``SOCK_SEQPACKET`` socket pairs: messages
keep their boundaries and carry file descriptors.  One task socket that
every worker reads: the kernel hands each task, ``(k, epoch, index, slot,
row, corrupt, crash)``, to one free worker, so a slow sample holds up no
other worker's queue.  One result socket that every worker writes: the
row's header, ``(k, seconds, quarantine events, error)``.  And a control
socket a worker: the dataset at start-up, each new slot's ``memfd``, and
the worker's end of file when it dies.  A message over ``_INLINE`` bytes
(the dataset) travels in a ``memfd``.

**The slots.**  A batch is one slot: a ``memfd`` holding, key by key, the
``[batch, ...]`` arrays of the stream's first sample's layout.  A worker
writes its sample straight into its row: no pickle of the ~7 MB a sample,
no ``np.stack`` on the consumer's thread.  A slot is handed out as NumPy
views whose memory is leased (``_Lease``): it returns to the ring only when
the last view of it is gone, so a batch the consumer holds never changes
under it, and neither does one whose host-to-device copy is still reading
it (``jax.device_put`` keeps its host buffer until the copy has landed, and
``data/prefetch.py`` holds the host batch until then too).  The ring grows
to what the window and the consumer hold, and no further.
"""

from __future__ import annotations

import collections
import dataclasses
import mmap
import os
import pickle
import signal
import socket
import subprocess
import sys
import traceback
import weakref
from multiprocessing.connection import wait
from typing import Callable, Dict, List, Tuple

import numpy as np

#: Messages larger than this travel in a memfd, not in the datagram.
_INLINE = 32 << 10
#: Alignment of each key's array inside a slot.
_ALIGN = 64


def _send(sock: socket.socket, obj, fds: Tuple[int, ...] = ()) -> None:
    data = pickle.dumps(obj, pickle.HIGHEST_PROTOCOL)
    if len(data) <= _INLINE:
        socket.send_fds(sock, [b"\0" + data], list(fds))
        return
    fd = os.memfd_create("raft-loader-msg", os.MFD_CLOEXEC)
    try:
        with open(fd, "wb", closefd=False) as f:
            f.write(data)
        socket.send_fds(sock, [b"\1"], [fd, *fds])
    finally:
        os.close(fd)


def _recv(sock: socket.socket):
    """-> ``(obj, fds)``; ``(None, [])`` when the peer is gone."""
    try:
        msg, fds, _flags, _addr = socket.recv_fds(sock, _INLINE + 1, 8)
    except ConnectionError:
        return None, []
    if not msg:
        for fd in fds:
            os.close(fd)
        return None, []
    if msg[:1] == b"\1":
        with open(fds[0], "rb") as f:     # the sender left it at the end
            f.seek(0)
            return pickle.loads(f.read()), fds[1:]
    return pickle.loads(msg[1:]), fds


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where each key's ``[batch, ...]`` array lies in a slot, fixed by
    the stream's first sample."""

    batch: int
    #: ``(name, dtype, row shape, byte offset)`` of each key
    keys: Tuple[Tuple[str, str, Tuple[int, ...], int], ...]
    nbytes: int

    @classmethod
    def of(cls, sample: Dict[str, np.ndarray], batch: int) -> "Layout":
        keys, off = [], 0
        for name, value in sample.items():
            a = np.asarray(value)
            keys.append((name, a.dtype.str, a.shape, off))
            off += -(-batch * a.nbytes // _ALIGN) * _ALIGN
        return cls(batch, tuple(keys), off)

    def arrays(self, buf) -> Dict[str, np.ndarray]:
        return {name: np.frombuffer(
                    buf, dtype, self.batch * int(np.prod(row)), off
                ).reshape((self.batch,) + row)
                for name, dtype, row, off in self.keys}

    def write(self, views: Dict[str, np.ndarray], row: int,
              sample: Dict[str, np.ndarray]) -> None:
        """Copy ``sample`` into ``row``; a sample of another layout is an
        error (``np.stack`` of the samples would have failed too)."""
        got = {k: (np.asarray(v).dtype.str, np.shape(v))
               for k, v in sample.items()}
        want = {name: (dtype, row_shape)
                for name, dtype, row_shape, _ in self.keys}
        if got != want:
            raise ValueError(
                f"a sample's arrays {got} differ from the layout of the "
                f"stream's first sample {want}: a batch needs one layout")
        for k, v in sample.items():
            views[k][row] = v


def _map(fd: int, nbytes: int) -> mmap.mmap:
    """A slot's memory, its pages faulted in now: a worker writes any row
    of any slot, and first touches of a 7 MB row cost several ms."""
    return mmap.mmap(fd, nbytes, flags=mmap.MAP_SHARED | mmap.MAP_POPULATE)


class _Lease:
    """The owner of one handed-out slot: every view of the batch keeps it
    alive, and its death returns the slot to the ring."""

    __slots__ = ("_buf", "__weakref__")

    def __init__(self, buf):
        self._buf = buf

    def __buffer__(self, flags):
        return memoryview(self._buf)


def _portable(e: BaseException):
    """An exception that survives the trip to the parent, and its
    traceback as text."""
    tb = "".join(traceback.format_exception(e))
    try:
        pickle.loads(pickle.dumps(e))
    except Exception:
        e = RuntimeError(f"{type(e).__name__}: {e}")
    return e, tb


# ---------------------------------------------------------------------------
# The worker
# ---------------------------------------------------------------------------

def _serve(ctl: socket.socket, tasks: socket.socket,
           results: socket.socket) -> None:
    from raft_tpu.data.datasets import SampleHeader as _Header

    try:
        (source, layout), _ = _recv(ctl)
    except BaseException as e:   # e.g. the dataset's class cannot be found
        _send(results, ("failed", os.getpid(), *_portable(e)))
        return
    slots: Dict[int, Dict[str, np.ndarray]] = {}
    while True:
        task, _ = _recv(tasks)
        if task is None:
            return
        k, epoch, index, sid, row, corrupt, crash = task
        while sid not in slots:          # announced before its first task
            msg, fds = _recv(ctl)
            if msg is None:
                return
            with open(fds[0], "r+b") as f:
                slots[msg] = layout.arrays(_map(f.fileno(), layout.nbytes))
        header, tb = _Header(0.0, []), None
        try:
            sample, header = source.load(epoch, index, corrupt=corrupt,
                                         crash=crash)
            if header.error is None:
                layout.write(slots[sid], row, sample)
        except Exception as e:           # a loader bug, or a bad layout
            header.error = e
        if header.error is not None:
            header.error, tb = _portable(header.error)
        _send(results, ("done", os.getpid(), k, header, tb))


def main(argv: List[str]) -> None:
    signal.signal(signal.SIGINT, signal.SIG_IGN)
    try:
        _serve(*(socket.socket(fileno=int(fd)) for fd in argv))
    except (BrokenPipeError, ConnectionError):
        pass                     # the parent went away: nothing to tell


# ---------------------------------------------------------------------------
# The parent
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Batch:
    sid: int
    rows: int = 0
    closed: bool = False
    done: int = 0
    errors: Dict[int, BaseException] = dataclasses.field(
        default_factory=dict)


def _pair():
    return socket.socketpair(socket.AF_UNIX, socket.SOCK_SEQPACKET)


class Feed:
    """The parent's side: ``num_workers`` worker processes, the ring of
    slots and the batches in flight, in stream order.

    ``first`` (the stream's first sample, loaded by the caller) fixes the
    layout and is row 0 of the first batch.  ``submit`` places the next
    sample in the open batch and queues it on the one task socket all
    workers read, so whichever worker is free takes it; ``next_batch``
    waits for the oldest batch's rows and hands it out.  Every row's
    ``SampleHeader`` goes to ``on_header`` as it arrives.  Each worker also has a control socket of its own: the
    dataset at start-up, every new slot's memfd, and its end of file
    when the worker dies."""

    def __init__(self, source, first: Dict[str, np.ndarray],
                 first_ends_epoch: bool, batch: int, num_workers: int,
                 on_header: Callable):
        self.layout = Layout.of(first, batch)
        self._on_header = on_header
        self._slots: List[Tuple[int, mmap.mmap, Dict]] = []  # fd, map, views
        self._free: collections.deque = collections.deque()
        self._open: collections.deque = collections.deque()
        self._tasks: Dict[int, Tuple[_Batch, int]] = {}
        self._k = 0
        self._procs, self._ctls = [], []
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            p or os.getcwd() for p in sys.path))
        tasks_r, self._tasks_w = _pair()
        self._results, results_w = _pair()
        try:
            with tasks_r, results_w:
                for _ in range(num_workers):
                    ctl, ctl_w = _pair()
                    self._ctls.append(ctl)
                    fds = [ctl_w.fileno(), tasks_r.fileno(),
                           results_w.fileno()]
                    with ctl_w:
                        self._procs.append(subprocess.Popen(
                            [sys.executable, "-m", "raft_tpu.data.workers",
                             *map(str, fds)], pass_fds=fds, env=env,
                            stdin=subprocess.DEVNULL,
                            start_new_session=True))
                    _send(ctl, (source, self.layout))
            b = self._new_batch()
            b.rows = b.done = 1
            b.closed = batch == 1 or first_ends_epoch
            self.layout.write(self._slots[b.sid][2], 0, first)
        except BaseException:
            self.close()
            raise

    # -- ring ---------------------------------------------------------------
    def _new_batch(self) -> _Batch:
        try:
            sid = self._free.popleft()
        except IndexError:
            fd = os.memfd_create("raft-batch-slot", os.MFD_CLOEXEC)
            os.ftruncate(fd, self.layout.nbytes)
            buf = _map(fd, self.layout.nbytes)
            sid = len(self._slots)
            self._slots.append((fd, buf, self.layout.arrays(buf)))
            for w, ctl in enumerate(self._ctls):
                try:
                    _send(ctl, sid, (fd,))
                except OSError:
                    self._died(w)
        b = _Batch(sid)
        self._open.append(b)
        return b

    def _hand_out(self, b: _Batch) -> Dict[str, np.ndarray]:
        lease = _Lease(self._slots[b.sid][1])
        weakref.finalize(lease, self._free.append, b.sid)
        return {k: v[:b.rows] for k, v in self.layout.arrays(lease).items()}

    # -- tasks --------------------------------------------------------------
    def submit(self, epoch: int, index: int, ends_epoch: bool,
               corrupt: bool, crash: bool) -> None:
        b = self._open[-1] if self._open else None
        if b is None or b.closed:
            b = self._new_batch()
        row = b.rows
        b.rows += 1
        b.closed = b.rows == self.layout.batch or ends_epoch
        self._tasks[self._k] = (b, row)
        _send(self._tasks_w, (self._k, int(epoch), int(index), b.sid, row,
                              corrupt, crash))
        self._k += 1

    def next_batch(self) -> Tuple[int, Dict[str, np.ndarray]]:
        """``(rows, batch)``: the oldest batch, once all its rows are in;
        a row's error is raised here, in stream order."""
        b = self._open[0]
        assert b.closed, "the window must cover a whole batch"
        while b.done < b.rows:
            self._wait()
        self._open.popleft()
        if b.errors:
            raise b.errors[min(b.errors)]
        return b.rows, self._hand_out(b)

    def _wait(self) -> None:
        for sock in wait([self._results, *self._ctls]):
            if sock is not self._results:   # a worker's end of file
                self._died(self._ctls.index(sock))
            msg, _ = _recv(sock)
            if msg[0] == "failed":
                _, pid, e, tb = msg
                raise RuntimeError(
                    f"loader worker (pid {pid}) could not start; a "
                    f"dataset's class must be importable by its module "
                    f"name:\n{tb}") from e
            _, pid, k, header, tb = msg
            b, row = self._tasks.pop(k)
            self._on_header(header)
            if header.error is not None:
                header.error.add_note(f"in loader worker pid {pid}:\n{tb}")
                b.errors[row] = header.error
            b.done += 1

    def _died(self, w: int):
        proc = self._procs[w]
        try:
            rc = proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            rc = None
        how = (f"was killed by signal {-rc}" if rc is not None and rc < 0
               else f"exited with code {rc}" if rc is not None
               else "closed its socket")
        raise RuntimeError(f"loader worker (pid {proc.pid}) {how}: the "
                           f"samples it held are lost, so the run stops")

    def close(self) -> None:
        """End the workers and let go of the ring; slots still leased stay
        mapped until their last view is gone."""
        for sock in (self._tasks_w, self._results, *self._ctls):
            sock.close()
        for proc in self._procs:
            proc.kill()
            proc.wait()
        for fd, _, _ in self._slots:
            os.close(fd)
        self._ctls, self._procs, self._slots = [], [], []

    def __enter__(self) -> "Feed":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


if __name__ == "__main__":
    main(sys.argv[1:])
