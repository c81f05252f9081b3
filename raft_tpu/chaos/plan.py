"""Deterministic, seeded fault injection (docs/ROBUSTNESS.md).

A :class:`FaultPlan` is a parsed ``RAFT_CHAOS_SPEC`` — a set of rules
saying WHICH fault fires WHEN — installed process-wide.  The hardened
layers (data quarantine, checkpoint fallback, serve retry) each expose
a *named injection point* at their hot seam; the point asks
:func:`should_inject` whether its fault fires on this call.  With no
plan installed the answer is one module-global ``None`` check — the
disabled path stays off the profile and the batch stream bit-identical
(pinned by ``tests/test_chaos.py`` against the ``test_prefetch``
determinism contract).

Spec grammar (``RAFT_CHAOS_SPEC`` / ``--chaos``)::

    spec  := rule (';' rule)*
    rule  := fault '@' arg (',' arg)*
    arg   := key '=' value

    corrupt_image@step=7,p=0.01;torn_ckpt@step=50;device_err@batch=3

keys (conditions AND together within one rule):

- ``step=N`` (aliases ``batch=N``, ``call=N``): fire when the caller's
  step/batch context equals N — or, at seams without a step context
  (sample reads), when this rule's own check ordinal equals N.
- ``p=F``: fire with probability F per check.  Seeded per rule from the
  plan seed, so a given (spec, seed, check order) always fires the same
  checks — chaos runs replay.
- ``times=K``: stop after K fires (default 1 for deterministic
  triggers, unlimited for pure ``p=`` and windowed ``heal=`` rules).
- ``heal=M``: with ``step=N``, fire on every check whose step/ordinal
  falls in ``[N, M)`` and STOP at M — a windowed outage that heals,
  e.g. ``net_partition@step=0,heal=40`` partitions a remote replica
  for its first 40 wire operations and then lets it rejoin.

Fault kinds and their seams (the point names appear in the
``chaos_inject`` event):

==================  ===========================  =======================
fault               seam (point)                 injected error
==================  ===========================  =======================
``corrupt_image``   ``data.sample_read``         ``SampleReadError``
``worker_err``      ``data.loader_worker``       ``InjectedWorkerCrash``
``producer_err``    ``pipeline.producer``        ``InjectedProducerCrash``
``torn_ckpt``       ``ckpt.save``                files torn post-commit
``restore_err``     ``ckpt.restore``             ``InjectedCheckpointCorruption``
``device_err``      ``serve.device``             ``InjectedDeviceError``
``replica_kill``    ``serve.replica``            ``InjectedReplicaKill``
                                                 (engine marks itself
                                                 crashed; supervisor
                                                 restarts it)
``replica_hang``    ``serve.replica``            device worker wedges
                                                 until the engine stops
                                                 (health goes stalled;
                                                 supervisor restarts)
``replica_slow``    ``serve.replica``            device batch sleeps
                                                 ``chaos_slow_s`` (the
                                                 straggler the router
                                                 hedges around)
``preempt``         ``train.preempt``            cooperative-preemption
                                                 flag set (SystemExit
                                                 143 at the boundary)
``stage_kill``      ``curriculum.stage_boundary``  ``SystemExit(143)``
                                                 before stage index N
``net_refuse``      ``serve.remote``             ``RemoteRefusedError``
                                                 (connect refused)
``net_slow``        ``serve.remote``             request delayed
                                                 ``chaos_slow_s``
``net_drop``        ``serve.remote``             ``RemoteDisconnectedError``
                                                 (request sent, response
                                                 never arrives)
``net_partition``   ``serve.remote``             ``RemoteTimeoutError``
                                                 on every wire op until
                                                 the rule's ``heal=``
                                                 ordinal
==================  ===========================  =======================

``preempt@step=N`` models a SIGTERM landing mid-stage: the train loop
checks it once per step boundary (step context = global step) and sets
the same cooperative flag the CLI's real SIGTERM handler sets, so
kill-and-resume is testable without delivering signals.
``stage_kill@step=N`` models the SIGTERM landing BETWEEN curriculum
stages: the driver checks it before starting stage index N (after the
previous stage's ledger entry committed).

Every fire emits a ``chaos_inject`` JSONL event (default sink) and
bumps ``raft_chaos_injections_total{fault=...}`` in the default
registry, so an injected fault is never confusable with a real one in
the telemetry record.

The loader checks its two seams in the parent process, once a sample and
in stream order, as it hands the sample to a worker process: ordinal rules
there replay with any number of workers (docs/ROBUSTNESS.md).
"""

from __future__ import annotations

import dataclasses
import os
import re
import threading
from typing import Dict, List, Optional

import numpy as np

ENV_SPEC = "RAFT_CHAOS_SPEC"
ENV_SEED = "RAFT_CHAOS_SEED"

_FAULT_RE = re.compile(r"^[a-z][a-z0-9_]*$")


class ChaosSpecError(ValueError):
    """Malformed ``RAFT_CHAOS_SPEC`` — raised at parse time, never from
    an injection point (a typo'd plan must fail the launch, not the
    2000th step)."""


@dataclasses.dataclass
class Rule:
    """One parsed spec rule (mutable: carries its own check/fire
    counters and RNG; the owning plan's lock serializes access)."""

    fault: str
    step: Optional[int] = None
    p: Optional[float] = None
    times: int = 1          # -1 = unlimited
    heal: Optional[int] = None  # fire window [step, heal), then stop
    seen: int = 0
    fired: int = 0
    _rng: Optional[np.random.Generator] = None

    def check(self, ctx_step: Optional[int]) -> bool:
        """Advance this rule by one check; True when it fires.  ALWAYS
        advances counters/RNG even when exhausted, so a multi-rule plan
        stays deterministic regardless of which rule fires first."""
        ordinal = self.seen
        self.seen += 1
        hit = True
        if self.step is not None:
            ref = ctx_step if ctx_step is not None else ordinal
            hit = (self.step <= ref < self.heal
                   if self.heal is not None else ref == self.step)
        if self.p is not None:
            draw = float(self._rng.random()) if self._rng is not None \
                else 1.0
            hit = hit and draw < self.p
        if hit and self.times >= 0 and self.fired >= self.times:
            return False
        if hit:
            self.fired += 1
        return hit


class FaultPlan:
    """A parsed chaos spec: rules grouped by fault, thread-safe check
    state, per-rule seeded RNG (``seed`` + rule position)."""

    def __init__(self, rules: List[Rule], *, seed: int = 0,
                 spec: str = ""):
        self.seed = int(seed)
        self.spec = spec
        self._lock = threading.Lock()
        self._by_fault: Dict[str, List[Rule]] = {}
        for i, rule in enumerate(rules):
            rule._rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, i]))
            self._by_fault.setdefault(rule.fault, []).append(rule)

    @classmethod
    def parse(cls, spec: str, seed: int = 0) -> "FaultPlan":
        rules = []
        for part in (s.strip() for s in spec.split(";")):
            if not part:
                continue
            fault, sep, argstr = part.partition("@")
            fault = fault.strip()
            if not sep or not argstr.strip():
                raise ChaosSpecError(
                    f"rule {part!r}: expected fault@key=value[,...]")
            if not _FAULT_RE.match(fault):
                raise ChaosSpecError(f"bad fault name {fault!r}")
            kw: dict = {}
            for tok in argstr.split(","):
                key, eq, val = (t.strip() for t in tok.partition("="))
                if not eq:
                    raise ChaosSpecError(
                        f"rule {part!r}: bad arg {tok.strip()!r} "
                        "(expected key=value)")
                try:
                    if key in ("step", "batch", "call"):
                        kw["step"] = int(val)
                    elif key == "p":
                        kw["p"] = float(val)
                    elif key == "times":
                        kw["times"] = int(val)
                    elif key == "heal":
                        kw["heal"] = int(val)
                    else:
                        raise ChaosSpecError(
                            f"rule {part!r}: unknown key {key!r} "
                            "(step/batch/call, p, times, heal)")
                except ValueError as e:
                    if isinstance(e, ChaosSpecError):
                        raise
                    raise ChaosSpecError(
                        f"rule {part!r}: bad value for {key!r}: {val!r}")
            if "step" not in kw and "p" not in kw:
                raise ChaosSpecError(
                    f"rule {part!r}: needs a trigger (step=/batch=/"
                    "call= or p=)")
            p = kw.get("p")
            if p is not None and not 0.0 < p <= 1.0:
                raise ChaosSpecError(f"rule {part!r}: p must be in "
                                     f"(0, 1], got {p}")
            heal = kw.get("heal")
            if heal is not None:
                if "step" not in kw:
                    raise ChaosSpecError(
                        f"rule {part!r}: heal= needs step= (the "
                        "outage window is [step, heal))")
                if heal <= kw["step"]:
                    raise ChaosSpecError(
                        f"rule {part!r}: heal ({heal}) must be > "
                        f"step ({kw['step']})")
            times = kw.get("times",
                           1 if "step" in kw and heal is None else -1)
            if times == 0 or times < -1:
                raise ChaosSpecError(f"rule {part!r}: times must be "
                                     ">= 1 (or -1 = unlimited)")
            rules.append(Rule(fault=fault, step=kw.get("step"), p=p,
                              times=times, heal=heal))
        if not rules:
            raise ChaosSpecError(f"empty chaos spec {spec!r}")
        return cls(rules, seed=seed, spec=spec)

    def fires(self, fault: str, step: Optional[int] = None) -> bool:
        rules = self._by_fault.get(fault)
        if not rules:
            return False
        with self._lock:
            # List comprehension, not any(generator): every rule's
            # counter/RNG must advance on every check (determinism).
            return any([r.check(step) for r in rules])

    def counts(self) -> Dict[str, int]:
        """``{fault: total fires so far}`` (zero-fire faults included)."""
        with self._lock:
            return {fault: sum(r.fired for r in rules)
                    for fault, rules in sorted(self._by_fault.items())}


# ---------------------------------------------------------------------------
# process-wide controller
# ---------------------------------------------------------------------------

_active: Optional[FaultPlan] = None


def install(plan: FaultPlan) -> FaultPlan:
    """Activate ``plan`` process-wide (replacing any previous plan)."""
    global _active
    _active = plan
    return plan


def uninstall() -> None:
    global _active
    _active = None


def active() -> Optional[FaultPlan]:
    return _active


def enabled() -> bool:
    return _active is not None


def install_from_env() -> Optional[FaultPlan]:
    """Install a plan from ``RAFT_CHAOS_SPEC`` / ``RAFT_CHAOS_SEED``
    (no-op, returning None, when the spec is unset) — the CLI edges
    call this once at startup."""
    spec = os.environ.get(ENV_SPEC)
    if not spec:
        return None
    seed = int(os.environ.get(ENV_SEED, "0"))
    plan = install(FaultPlan.parse(spec, seed=seed))
    print(f"chaos: fault plan {spec!r} installed (seed {seed})",
          flush=True)
    return plan


def should_inject(fault: str, step: Optional[int] = None,
                  point: Optional[str] = None) -> bool:
    """Ask the installed plan whether ``fault`` fires on this check.

    The disabled path is one global read + ``None`` test; a fire is
    recorded to telemetry (``chaos_inject`` event +
    ``raft_chaos_injections_total`` counter) before returning True."""
    plan = _active
    if plan is None:
        return False
    if not plan.fires(fault, step=step):
        return False
    _record_fire(fault, step, point)
    return True


def _record_fire(fault: str, step: Optional[int],
                 point: Optional[str]) -> None:
    try:
        from raft_tpu.obs.events import default_sink
        from raft_tpu.obs.registry import default_registry

        default_sink().emit("chaos_inject", step=step, fault=fault,
                            point=point or "")
        default_registry().counter(
            "raft_chaos_injections_total",
            "faults fired by the installed chaos plan").inc(fault=fault)
    except Exception:
        pass  # telemetry must never turn an injected fault into a real one
