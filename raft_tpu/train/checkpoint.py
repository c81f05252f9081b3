"""Orbax checkpointing with auto-resume, torn-write fallback, elastic
reshard-on-restore, and non-blocking background commits.

Improves on the reference (SURVEY.md §5): ``torch.save(state_dict())``
every 5000 steps kept weights only — optimizer/scheduler/step state was
lost and the LR schedule restarted on resume (train.py:186-187,141-142).
Here the FULL TrainState (params + batch_stats + optimizer state + step)
is saved asynchronously, and ``restore_latest`` makes a preempted pod run
continue exactly where it stopped.  Weights-only restore (for curriculum
stage seeding, the reference's ``strict=False`` use case) is
``restore_params``.

Fault tolerance (docs/ROBUSTNESS.md): a preempted host can die
mid-write, leaving the NEWEST step directory torn — present in
``all_steps()`` but unrestorable.  ``restore_latest`` therefore treats
restore as the integrity check and walks the saved steps newest →
oldest, emitting one ``ckpt_fallback`` JSONL event (+
``raft_ckpt_fallback_total``) per step it has to skip; only when every
step is unrestorable does it raise :class:`CheckpointRestoreError`
(resuming silently from scratch would be worse than dying).  ``python
-m raft_tpu verify-ckpt <dir>`` runs the same verification offline.
The ``torn_ckpt``/``restore_err`` chaos faults exercise both paths
deterministically (``raft_tpu/chaos``).

Elastic resume (docs/ROBUSTNESS.md "Elastic resume"): pass ``mesh=`` to
``restore_latest``/``restore_params`` and the restore is templated on
abstract arrays CARRYING the target sharding
(:func:`raft_tpu.parallel.abstract_replicated`), so a checkpoint saved
under any mesh shape — any device count — restores bit-exactly onto the
current one.  Each save also stamps the saving topology into a
run-level ``topology.json`` ledger next to the step directories (never
inside them, so a torn step cannot take the ledger with it);
``verify-ckpt`` reports it.

Non-blocking commits: :meth:`CheckpointManager.save_async` hands the
save to a single background committer thread through a bounded window
of ``commit_window`` in-flight requests — the step loop never waits on
checkpoint I/O unless it laps the window.  The committer snapshots the
state on-device first (the train step donates its input buffers, so
the caller's arrays are dead one step later), commits, re-checks the
files with a cheap metadata probe, and emits one ``ckpt_commit`` event
per save with the commit latency.  A committer failure is re-raised on
the next ``save_async``/``wait`` — a dying disk must fail the run
loudly, not silently stop persisting.
"""

from __future__ import annotations

import json
import os
import queue
import re
import threading
import time
from typing import Any, List, Optional

import orbax.checkpoint as ocp

from raft_tpu import chaos
from raft_tpu.train.state import TrainState

#: Message fingerprints of a pytree-structure mismatch between the
#: restore template and the on-disk checkpoint (orbax wording varies by
#: version; lenient versions don't raise at all).  Only THIS class of
#: error means "legacy checkpoint, retry with the counter-less
#: template" — a torn file raises decode/IO errors that must surface as
#: corruption, not be retried against a different template and
#: re-raised with a misleading traceback.
_STRUCT_MISMATCH_RE = re.compile(
    r"(?i)structure|mismatch|do(es)? not match|missing|nonfinite_steps"
    r"|custom node type")

#: Veto: torn/corrupt-file wording that must NEVER classify as a
#: structure mismatch even when it also says "missing" — tensorstore
#: and orbax phrase missing/truncated chunk files exactly like that
#: ("Error opening ... missing", "NOT_FOUND: ...", checksum failures),
#: and retrying those against the counter-less template buries the real
#: corruption under a misleading second traceback.  "nonfinite_steps"
#: in the message always wins (that IS the legacy-template signature).
_CORRUPTION_RE = re.compile(
    r"(?i)no such file|not_found|data_loss|failed_precondition"
    r"|checksum|corrupt|truncat|unterminated|invalid json|decod"
    r"|error (?:opening|reading)|missing [a-z_./]*(?:file|chunk|array"
    r"|metadata|manifest|data)|\.zarray|\.ocdbt")


def _is_structure_mismatch(e: BaseException) -> bool:
    if not isinstance(e, (ValueError, TypeError, KeyError)):
        return False
    msg = str(e)
    if "nonfinite_steps" not in msg and _CORRUPTION_RE.search(msg):
        return False
    return bool(_STRUCT_MISMATCH_RE.search(msg))


class CheckpointRestoreError(RuntimeError):
    """Every saved step failed to restore — nothing valid to resume
    from.  Deliberately fatal: silently restarting a multi-day run from
    step 0 because the checkpoint directory rotted is the worst
    outcome, not a recovery."""


#: Run-level topology ledger filename (sibling of the step dirs).
TOPOLOGY_FILE = "topology.json"

# jitted whole-tree device copy, built lazily and cached per tree
# structure by jit itself.  jnp.copy under jit cannot alias its input,
# so the snapshot is real new device buffers — required because
# make_train_step donates the state (train/step.py): the caller's
# buffers are invalid one step after save_async returns.
_COPY_FN = None


def _device_snapshot(tree):
    global _COPY_FN
    if _COPY_FN is None:
        import jax
        import jax.numpy as jnp

        _COPY_FN = jax.jit(
            lambda t: jax.tree_util.tree_map(jnp.copy, t))
    return _COPY_FN(tree)


def _current_topology(mesh=None) -> dict:
    import jax

    topo = {
        "device_count": jax.device_count(),
        "process_count": jax.process_count(),
        "platform": jax.devices()[0].platform,
    }
    if mesh is not None:
        from raft_tpu.parallel.mesh import mesh_shape

        topo["mesh"] = mesh_shape(mesh)
    return topo


# committer-queue shutdown sentinel
_SHUTDOWN = object()


class CheckpointManager:
    """Thin wrapper over orbax CheckpointManager for TrainState pytrees.

    ``sink``: optional :class:`raft_tpu.obs.EventSink` for
    ``ckpt_fallback``/``ckpt_commit`` events (default: the process-wide
    sink, a no-op unless ``RAFT_TELEMETRY_DIR`` is set).
    ``commit_window``: bound on in-flight :meth:`save_async` commits —
    the caller blocks only when this many saves are still uncommitted.
    """

    def __init__(self, directory: str, max_to_keep: int = 5,
                 async_save: bool = True, sink=None,
                 commit_window: int = 2):
        self._dir = os.path.abspath(directory)
        os.makedirs(self._dir, exist_ok=True)
        opts = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            enable_async_checkpointing=async_save)
        self._mgr = ocp.CheckpointManager(self._dir, options=opts)
        self._sink = sink
        # background committer (lazy: plain save()/restore-only users
        # never start the thread)
        self._commit_window = max(int(commit_window), 1)
        self._commit_q: Optional[queue.Queue] = None
        self._commit_thread: Optional[threading.Thread] = None
        self._commit_err: Optional[BaseException] = None
        self._last_requested: Optional[int] = None

    def _events(self):
        if self._sink is not None:
            return self._sink
        from raft_tpu.obs.events import default_sink

        return default_sink()

    # -- topology stamp --------------------------------------------------
    def _topology_path(self) -> str:
        return os.path.join(self._dir, TOPOLOGY_FILE)

    def _stamp_topology(self, step: int, mesh) -> None:
        """Record the saving topology for ``step`` in the run-level
        ledger (atomic tmp+rename; best-effort — the stamp is an audit
        aid, never worth failing a save over)."""
        try:
            ledger = self.saved_topology()
            ledger[str(int(step))] = dict(_current_topology(mesh),
                                          time=time.time())
            tmp = self._topology_path() + ".tmp"
            with open(tmp, "w") as f:
                json.dump(ledger, f, indent=2, sort_keys=True)
            os.replace(tmp, self._topology_path())
        except Exception:
            pass

    def saved_topology(self, step: Optional[int] = None):
        """The topology ledger: ``{str(step): {mesh, device_count,
        process_count, platform, time}}`` for every stamped save (steps
        rotated out by ``max_to_keep`` keep their stamps — the ledger
        doubles as a resume audit trail).  With ``step``, that one
        entry or None.  Pre-stamp run directories return ``{}``."""
        try:
            with open(self._topology_path()) as f:
                ledger = json.load(f)
        except (OSError, ValueError):
            ledger = {}
        if step is not None:
            return ledger.get(str(int(step)))
        return ledger

    # -- save ------------------------------------------------------------
    def save(self, step: int, state: TrainState, force: bool = False,
             mesh=None) -> None:
        """Synchronous-path save (orbax may still flush in background;
        ``wait()`` joins it).  The train loop's hot path uses
        :meth:`save_async` instead; this is the emergency/final-flush
        and offline-tool path.  Commits handed to :meth:`save_async`
        finish first: orbax takes one save at a time, and the loop's
        final flush can follow a periodic save within a step."""
        if self._commit_q is not None:
            self._commit_q.join()
        self._mgr.save(step, args=ocp.args.StandardSave(state), force=force)
        self._last_requested = int(step)
        self._stamp_topology(step, mesh)
        if chaos.should_inject("torn_ckpt", step=int(step),
                               point="ckpt.save"):
            # Torn-write simulator: commit the save, then truncate its
            # files — exactly what a host death mid-flush leaves behind
            # (the step stays listed; restore raises).
            self.wait()
            torn = chaos.tear_files(os.path.join(self._dir, str(int(step))))
            self._events().emit("chaos_torn_ckpt", step=int(step),
                                files=len(torn))

    def save_async(self, step: int, state: TrainState,
                   force: bool = False, mesh=None) -> None:
        """Hand ``(step, state)`` to the background committer and return
        without waiting on any checkpoint I/O.

        The only blocking this call can do is backpressure: at most
        ``commit_window`` commits are in flight, so a step loop that
        laps the committer waits here instead of growing an unbounded
        snapshot queue in HBM.  The state is snapshotted on-device
        BEFORE returning (one jitted tree-copy dispatch), so the caller
        may immediately donate/overwrite its buffers.  A failure of a
        previous commit re-raises here."""
        self._raise_commit_err()
        import jax

        if jax.process_count() > 1:
            # Multi-host orbax saves synchronize through cross-host
            # barriers; driving those from a per-host background thread
            # is unproven — keep the established synchronous path.
            self.save(step, state, force=force, mesh=mesh)
            return
        snap = _device_snapshot(state)
        if self._commit_thread is None:
            self._commit_q = queue.Queue(maxsize=self._commit_window)
            self._commit_thread = threading.Thread(
                target=self._commit_loop, name="raft-ckpt-commit",
                daemon=True)
            self._commit_thread.start()
        self._last_requested = int(step)
        # Carry the caller's trace context across the thread hop so the
        # commit shows up as a ``ckpt_commit`` span under the step trace
        # that requested it (obs/trace; None when tracing is off).
        from raft_tpu.obs import trace

        self._commit_q.put((int(step), snap, bool(force), mesh,
                            time.perf_counter(), trace.current()))

    def _commit_loop(self) -> None:
        while True:
            item = self._commit_q.get()
            try:
                if item is _SHUTDOWN:
                    return
                step, snap, force, mesh, t_enq, ctx = item
                self._commit_one(step, snap, force, mesh, t_enq, ctx)
            finally:
                self._commit_q.task_done()

    def _commit_one(self, step, snap, force, mesh, t_enq,
                    ctx=None) -> None:
        t0 = time.perf_counter()
        try:
            self._mgr.save(step, args=ocp.args.StandardSave(snap),
                           force=force)
            self._mgr.wait_until_finished()
            self._stamp_topology(step, mesh)
            if chaos.should_inject("torn_ckpt", step=int(step),
                                   point="ckpt.save"):
                # Post-commit, like the sync path: the fault lands on
                # fully committed files (the commit above finished).
                torn = chaos.tear_files(
                    os.path.join(self._dir, str(int(step))))
                self._events().emit("chaos_torn_ckpt", step=int(step),
                                    files=len(torn))
        except BaseException as e:
            self._commit_err = e
            self._emit_commit(step, t0, t_enq, ok=False,
                              error=f"{type(e).__name__}: {str(e)[:200]}")
            self._trace_commit(ctx, step, t0, ok=False)
            return
        ok, err = self._probe_commit(step)
        self._emit_commit(step, t0, t_enq, ok=ok, error=err)
        self._trace_commit(ctx, step, t0, ok=ok)

    def _trace_commit(self, ctx, step, t0, *, ok) -> None:
        """Record the commit as a span under the requesting step's
        trace (no-op when the caller wasn't traced)."""
        if not ctx:
            return
        try:
            from raft_tpu.obs import trace

            trace.record_span(ctx, "ckpt_commit", t0,
                              time.perf_counter(),
                              status="ok" if ok else "error",
                              step=int(step))
        except Exception:
            pass  # telemetry must never fail a commit

    def _emit_commit(self, step, t0, t_enq, *, ok, error=None) -> None:
        try:
            from raft_tpu.obs.registry import default_registry

            now = time.perf_counter()
            fields = dict(ok=bool(ok),
                          commit_latency_s=round(now - t0, 6),
                          queue_wait_s=round(t0 - t_enq, 6))
            if error:
                fields["error"] = error
            self._events().emit("ckpt_commit", step=int(step), **fields)
            default_registry().counter(
                "raft_ckpt_commits_total",
                "background checkpoint commits by probe outcome").inc(
                    ok=str(bool(ok)).lower())
        except Exception:
            pass  # telemetry must never fail a commit

    def _probe_commit(self, step: int):
        """Cheap post-commit integrity probe: the step is listed, every
        file is non-empty, and the orbax/tensorstore JSON metadata
        parses.  Catches torn writes without paying a full restore
        (``verify`` stays the authoritative check).  The probe REPORTS
        — it never deletes: a torn step must stay on disk for the
        restore fallback chain (and the chaos tests) to walk past."""
        d = os.path.join(self._dir, str(int(step)))
        try:
            if int(step) not in self.all_steps():
                return False, "step not listed after commit"
            if not os.path.isdir(d):
                return False, "step directory missing"
            for root, _dirs, files in os.walk(d):
                for name in files:
                    path = os.path.join(root, name)
                    if os.path.getsize(path) == 0:
                        return False, f"empty file {name}"
                    if name in ("_CHECKPOINT_METADATA", "_METADATA",
                                "manifest.ocdbt") or \
                            name.endswith(".json"):
                        with open(path, "rb") as f:
                            blob = f.read()
                        if name.endswith("_METADATA") \
                                or name.endswith(".json"):
                            json.loads(blob)
            return True, None
        except Exception as e:
            return False, f"{type(e).__name__}: {str(e)[:200]}"

    def _raise_commit_err(self) -> None:
        if self._commit_err is not None:
            e, self._commit_err = self._commit_err, None
            raise RuntimeError(
                "background checkpoint commit failed") from e

    def wait(self) -> None:
        """Drain the committer window, then orbax's own async flush.
        Raises the first background commit failure (the caller-visible
        surface of a dying disk)."""
        if self._commit_q is not None:
            self._commit_q.join()
        self._raise_commit_err()
        self._mgr.wait_until_finished()

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def last_requested_step(self) -> Optional[int]:
        """Newest step handed to save()/save_async(), committed or not
        — what the final-flush check must compare against (latest_step
        lags while commits are in flight)."""
        return self._last_requested

    def all_steps(self) -> List[int]:
        """Saved steps, oldest first (torn steps included — presence is
        not integrity; see :meth:`verify`)."""
        return sorted(int(s) for s in self._mgr.all_steps())

    def _restore_step(self, step: int, template: TrainState,
                      mesh=None) -> TrainState:
        """Restore ONE step against ``template``.

        ``mesh``: reshard-on-restore — the template is abstracted to
        shape/dtype structs replicated over this mesh
        (:func:`raft_tpu.parallel.abstract_replicated`), so the bytes
        land directly on the target topology no matter which mesh (or
        device count) wrote them.  None keeps the template's own
        placement (single-topology behavior).

        Checkpoints written before the non-finite guard lack the
        ``nonfinite_steps`` counter; a structure-mismatch restore (and
        ONLY that — see ``_is_structure_mismatch``) is retried against
        a counter-less template and the counter re-attached at zero, so
        old run directories resume cleanly while genuine corruption
        surfaces with its original traceback."""
        if chaos.should_inject("restore_err", step=int(step),
                               point="ckpt.restore"):
            raise chaos.InjectedCheckpointCorruption(
                f"chaos-injected restore failure at step {step}")

        def _args(t):
            if mesh is not None:
                from raft_tpu.parallel.mesh import abstract_replicated

                t = abstract_replicated(t, mesh)
            return ocp.args.StandardRestore(t)

        has_counter = getattr(template, "nonfinite_steps", None) is not None
        try:
            st = self._mgr.restore(step, args=_args(template))
        except Exception as e:
            if not (has_counter and _is_structure_mismatch(e)):
                raise
            st = self._mgr.restore(
                step, args=_args(template.replace(nonfinite_steps=None)))
        if has_counter and getattr(st, "nonfinite_steps", None) is None:
            # Lenient orbax restores the absent leaf as None — either
            # way the counter restarts at zero.
            import jax.numpy as jnp

            zero = jnp.zeros((), jnp.int32)
            if mesh is not None:
                import jax

                from raft_tpu.parallel.mesh import replicated_sharding

                zero = jax.device_put(zero, replicated_sharding(mesh))
            st = st.replace(nonfinite_steps=zero)
        return st

    def restore_latest(self, template: TrainState,
                       mesh=None) -> Optional[TrainState]:
        """Full-state restore for preemption recovery; None if no ckpt.

        ``mesh``: restore onto this mesh regardless of the saving
        topology (see :meth:`_restore_step`) — the elastic-resume path.
        Walks saved steps newest → oldest past corrupt/torn ones
        (``ckpt_fallback`` event + ``raft_ckpt_fallback_total`` counter
        per skipped step); raises :class:`CheckpointRestoreError` when
        nothing restores."""
        steps = sorted(self.all_steps(), reverse=True)
        if not steps:
            return None
        failures = []
        for step in steps:
            try:
                st = self._restore_step(step, template, mesh=mesh)
            except Exception as e:
                failures.append((step, e))
                self._note_fallback(step, e, tried=len(failures),
                                    remaining=len(steps) - len(failures))
                continue
            if failures:
                print(f"checkpoint fallback: step(s) "
                      f"{[s for s, _ in failures]} unrestorable "
                      f"(torn write?); resumed from step {step}",
                      flush=True)
            return st
        raise CheckpointRestoreError(
            f"no restorable checkpoint in {self._dir} — all "
            f"{len(steps)} step(s) failed: "
            + "; ".join(f"step {s}: {type(e).__name__}: {str(e)[:120]}"
                        for s, e in failures))

    def _note_fallback(self, step: int, e: BaseException, *,
                       tried: int, remaining: int) -> None:
        from raft_tpu.obs.registry import default_registry

        default_registry().counter(
            "raft_ckpt_fallback_total",
            "saved checkpoint steps skipped as unrestorable during "
            "resume").inc()
        self._events().emit("ckpt_fallback", step=int(step),
                            error=f"{type(e).__name__}: {str(e)[:200]}",
                            tried=tried, remaining_steps=remaining)

    def verify(self, step: int,
               template: Optional[TrainState] = None) -> dict:
        """Integrity-check one saved step by actually restoring it (the
        only check that proves the bytes decode).  With no ``template``
        the raw metadata-driven restore is used, so verification needs
        no model code.  Returns ``{step, ok[, error]}``; never raises."""
        try:
            if template is None:
                # Explicit StandardRestore: a freshly opened manager
                # (the verify CLI) has no handler registry yet, and the
                # bare restore(step) would fail for the wrong reason.
                self._mgr.restore(step,
                                  args=ocp.args.StandardRestore())
            else:
                self._restore_step(step, template)
            return {"step": int(step), "ok": True}
        except Exception as e:
            return {"step": int(step), "ok": False,
                    "error": f"{type(e).__name__}: {str(e)[:300]}"}

    def verify_all(self,
                   template: Optional[TrainState] = None) -> List[dict]:
        """:meth:`verify` over every saved step, oldest first."""
        return [self.verify(s, template) for s in self.all_steps()]

    def restore_params(self, template: TrainState,
                       mesh=None) -> Optional[Any]:
        """Weights(+batch_stats)-only restore: seeds the next curriculum
        stage without carrying optimizer state (reference strict=False
        restore, train.py:141-142).  ``mesh``: reshard onto this mesh
        (see :meth:`restore_latest`)."""
        st = self.restore_latest(template, mesh=mesh)
        if st is None:
            return None
        return {"params": st.params, "batch_stats": st.batch_stats}

    def close(self) -> None:
        if self._commit_thread is not None:
            self._commit_q.put(_SHUTDOWN)
            self._commit_thread.join(timeout=600.0)
            self._commit_thread = None
        self._mgr.close()


def save_variables(path: str, variables: Any) -> None:
    """Save a bare ``{'params': ..., 'batch_stats': ...}`` pytree (model
    zoo / converted-weights format — no optimizer state)."""
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.abspath(path), variables)
    ckptr.wait_until_finished()


def load_variables(path: str) -> Any:
    """Load a bare variables pytree saved by ``save_variables`` (or the
    torch->pytree converter)."""
    ckptr = ocp.StandardCheckpointer()
    return ckptr.restore(os.path.abspath(path))
