"""Cost-model observability: FLOPs/bytes accounting, MFU and roofline
classification per compiled program (docs/OBSERVABILITY.md, "Cost model
& roofline").

Every number the stack emitted before this module was wall-clock only —
bench records, trace spans and the regression gates all measured *time*,
never *work*, so "74.8 pairs/sec on CPU" and a future TPU number were
incomparable, and a regression that halves MFU while shapes shrink
passed every gate.  This module closes that gap with three pieces:

- **Extraction** (:func:`program_cost`): per-jitted-program FLOPs and
  HBM bytes from XLA's ``Compiled.cost_analysis()`` — captured ONCE at
  compile time from the lowered executable and amortized over every
  subsequent call.  Capture is pure host-side metadata: it never runs
  the program, never touches a device buffer, never syncs (the
  zero-device-sync contract, pinned by ``tests/test_cost.py``).
- **Analytic fallback** (:func:`analytic_lookup_encode_cost`,
  :func:`analytic_gru_gate_cost`): hand-derived flop/byte formulas for
  the fused Pallas kernels, keyed off their block specs.  On TPU the
  kernel body is an opaque ``custom_call`` XLA counts as zero flops;
  the analytic entries are what ``scripts/bench_kernels.py`` stamps
  into its records and what the r07 backlog validates against XProf.
- **Normalization** (:data:`PEAK_SPECS`, :class:`ProgramCost`): a
  per-``device_kind`` peak-specs table (bf16 TFLOP/s + HBM GB/s for
  v5e/v4; CPU peaks are *unknown*, so CPU MFU is ``None``, never a
  made-up number) turning (flops, bytes, seconds) into MFU, HBM
  bandwidth utilization, arithmetic intensity and a compute- vs
  memory-bound roofline verdict (intensity vs the ridge point
  ``peak_flops / peak_bw``).

Derived metrics stream through the existing layer: ``raft_cost_mfu``,
``raft_cost_hbm_bw_util`` and ``raft_cost_flops_per_pair`` gauges
(labeled by program) plus one ``cost_report`` JSONL event per captured
program.  ``python -m raft_tpu cost`` dumps the table interactively;
``scripts/trace_report.py --roofline`` folds the span-attached copies.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Hashable, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# per-device_kind peak specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PeakSpec:
    """Datasheet peaks for one accelerator kind.  ``tflops`` is the
    dense bf16 MXU rate (the compute dtype every hot path here runs);
    ``hbm_gbps`` the peak HBM bandwidth.  ``None`` fields mean the peak
    is UNKNOWN — derived utilizations become ``None`` rather than a
    fabricated ratio (the CPU container has no honest peak, and a fake
    one would arm ``--min-mfu`` with noise)."""

    kind: str
    tflops: Optional[float]
    hbm_gbps: Optional[float]

    @property
    def ridge(self) -> Optional[float]:
        """Roofline ridge point, flops/byte: programs with lower
        arithmetic intensity are memory-bound on this part."""
        if not self.tflops or not self.hbm_gbps:
            return None
        return self.tflops * 1e12 / (self.hbm_gbps * 1e9)


#: Datasheet peaks by normalized device kind.  v5e: 197 bf16 TFLOP/s,
#: 16 GB HBM2 @ 819 GB/s; v4: 275 bf16 TFLOP/s, 32 GB HBM2 @ 1228 GB/s.
#: Extend here when a new kind shows up — an unknown kind degrades to
#: unknown peaks, never to a wrong spec.
PEAK_SPECS: Dict[str, PeakSpec] = {
    "v5e": PeakSpec("v5e", 197.0, 819.0),
    "v4": PeakSpec("v4", 275.0, 1228.0),
    "cpu": PeakSpec("cpu", None, None),
}


def peak_spec(device_kind: Optional[str] = None) -> PeakSpec:
    """The :class:`PeakSpec` for ``device_kind`` (default: the current
    backend's ``jax.devices()[0].device_kind``).  Matching is
    normalized substring matching — libtpu spells v5e both ``TPU v5e``
    and ``TPU v5 lite`` depending on version."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    dk = str(device_kind).lower()
    if "v5e" in dk or "v5 lite" in dk or "v5lite" in dk:
        return PEAK_SPECS["v5e"]
    if "v4" in dk:
        return PEAK_SPECS["v4"]
    if "cpu" in dk:
        return PEAK_SPECS["cpu"]
    return PeakSpec(str(device_kind), None, None)


# ---------------------------------------------------------------------------
# extraction
# ---------------------------------------------------------------------------


def xla_cost(compiled) -> Optional[Dict[str, float]]:
    """``{'flops', 'bytes', 'transcendentals'}`` from a ``Compiled``'s
    ``cost_analysis()``, or ``None`` when the backend reports nothing
    (some jaxlibs return ``None``/empty for custom-call-only modules).

    Host-side metadata only — this never executes the program.  Values
    are per-device: under SPMD the compiled module IS the per-device
    program, so its flops cover ``batch / num_devices`` pairs.
    """
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not ca:
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    byts = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and byts <= 0.0:
        return None
    return {"flops": flops, "bytes": byts,
            "transcendentals": float(ca.get("transcendentals", 0.0)
                                     or 0.0)}


@dataclasses.dataclass(frozen=True)
class ProgramCost:
    """Compile-time work accounting for ONE compiled program.

    ``flops``/``bytes`` are per *call* of the per-device executable;
    ``pairs_per_call`` is how many image pairs one call advances on
    this device (``None`` for programs with no per-pair meaning, e.g.
    a bare kernel arm).  ``source`` says where the numbers came from:
    ``xla`` (cost_analysis), ``analytic`` (hand-derived formula — the
    TPU custom-call fallback), or ``unavailable``.
    """

    program: str
    flops: float
    bytes: float
    transcendentals: float = 0.0
    pairs_per_call: Optional[float] = None
    source: str = "xla"
    device_kind: str = "unknown"
    interpret: bool = False

    @property
    def spec(self) -> PeakSpec:
        return peak_spec(self.device_kind)

    @property
    def arithmetic_intensity(self) -> Optional[float]:
        if self.bytes <= 0.0:
            return None
        return self.flops / self.bytes

    @property
    def bound_by(self) -> str:
        """Roofline verdict: ``compute`` / ``memory`` when both the
        program's intensity and the device ridge point are known,
        ``unknown`` otherwise (CPU, or a byte-less analytic entry)."""
        ai = self.arithmetic_intensity
        ridge = self.spec.ridge
        if ai is None or ridge is None:
            return "unknown"
        return "compute" if ai >= ridge else "memory"

    @property
    def flops_per_pair(self) -> Optional[float]:
        if not self.pairs_per_call:
            return None
        return self.flops / float(self.pairs_per_call)

    def achieved_tflops(self, seconds: float) -> Optional[float]:
        if seconds <= 0.0:
            return None
        return self.flops / seconds / 1e12

    def mfu(self, seconds: float) -> Optional[float]:
        """Model FLOP utilization in [0, 1] for one call taking
        ``seconds`` — ``None`` when the peak is unknown (CPU) or the
        program ran the Pallas interpreter (an emulation's wall time
        says nothing about the kernel)."""
        peak = self.spec.tflops
        at = self.achieved_tflops(seconds)
        if peak is None or at is None or self.interpret:
            return None
        return at / peak

    def hbm_bw_util(self, seconds: float) -> Optional[float]:
        peak = self.spec.hbm_gbps
        if peak is None or seconds <= 0.0 or self.interpret:
            return None
        return self.bytes / seconds / 1e9 / peak

    def as_record(self, seconds: Optional[float] = None) -> dict:
        """Flat JSON-ready dict (the ``cost_report`` event payload and
        the ``raft_tpu cost`` table row)."""
        spec = self.spec
        rec = {
            "program": self.program,
            "flops": self.flops,
            "bytes": self.bytes,
            "transcendentals": self.transcendentals,
            "source": self.source,
            "device_kind": self.device_kind,
            "interpret": self.interpret,
            "peak_tflops": spec.tflops,
            "peak_hbm_gbps": spec.hbm_gbps,
            "ridge_flops_per_byte": (round(spec.ridge, 2)
                                     if spec.ridge else None),
            "arithmetic_intensity": (round(self.arithmetic_intensity, 3)
                                     if self.arithmetic_intensity
                                     is not None else None),
            "bound_by": self.bound_by,
        }
        if self.pairs_per_call:
            rec["pairs_per_call"] = self.pairs_per_call
            rec["flops_per_pair"] = self.flops_per_pair
        if seconds is not None:
            rec["seconds"] = round(seconds, 6)
            at = self.achieved_tflops(seconds)
            rec["achieved_tflops"] = (round(at, 4) if at is not None
                                      else None)
            m = self.mfu(seconds)
            rec["mfu"] = round(m, 4) if m is not None else None
            bw = self.hbm_bw_util(seconds)
            rec["hbm_bw_util"] = (round(bw, 4) if bw is not None
                                  else None)
        return rec


def program_cost(compiled_or_fn, *args, program: str,
                 pairs_per_call: Optional[float] = None,
                 device_kind: Optional[str] = None,
                 interpret: bool = False,
                 analytic: Optional[Tuple[float, float]] = None,
                 ) -> ProgramCost:
    """Capture a :class:`ProgramCost` from a lowered executable.

    Pass either an already-``.compile()``d executable (the serving
    engine's ledger path — zero extra work) or a jitted function plus
    example args (one extra ``lower().compile()`` — a full compile:
    a re-lowered program does not reliably hit the persistent cache,
    so prefer handing over the executable you already have).

    ``analytic``: optional hand-derived ``(flops, bytes)`` used when
    XLA reports nothing (TPU custom-call bodies).  When XLA *does*
    report, its numbers win and ``analytic`` is ignored — interpret
    mode lowers Pallas kernels to countable HLO, so the XLA count is
    the kernel math there.
    """
    compiled = (compiled_or_fn if not args
                else compiled_or_fn.lower(*args).compile())
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    got = xla_cost(compiled)
    if got is not None:
        return ProgramCost(program=program, flops=got["flops"],
                           bytes=got["bytes"],
                           transcendentals=got["transcendentals"],
                           pairs_per_call=pairs_per_call, source="xla",
                           device_kind=str(device_kind),
                           interpret=interpret)
    if analytic is not None:
        return ProgramCost(program=program, flops=float(analytic[0]),
                           bytes=float(analytic[1]),
                           pairs_per_call=pairs_per_call,
                           source="analytic",
                           device_kind=str(device_kind),
                           interpret=interpret)
    return ProgramCost(program=program, flops=0.0, bytes=0.0,
                       pairs_per_call=pairs_per_call,
                       source="unavailable",
                       device_kind=str(device_kind),
                       interpret=interpret)


# ---------------------------------------------------------------------------
# analytic fallback table — the fused Pallas kernels
# ---------------------------------------------------------------------------

# Block constants mirrored from the kernels' own specs (ops/pallas_gru.py
# flattens to (256, 128) tiles; ops/pallas_corr.py pads queries to
# block_q and the convc1 contraction to (8, 128) tiles).  Keyed here so
# the formulas track the block specs, not the logical shapes alone.
_GRU_LANES = 128
_GRU_BLOCK_ROWS = 256


def _gru_padded_elems(shape: Sequence[int]) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    rows = -(-n // _GRU_LANES)
    rows = -(-rows // _GRU_BLOCK_ROWS) * _GRU_BLOCK_ROWS
    return rows * _GRU_LANES


def analytic_gru_gate_cost(shape: Sequence[int], kind: str = "blend",
                           dtype_bytes: int = 4,
                           ) -> Tuple[float, float]:
    """``(flops, bytes)`` for one fused GRU gate-chain kernel call
    (``ops/pallas_gru.py``) over operands of ``shape``.

    Per padded element (XLA's own elementwise accounting, which the
    parity test compares against): a sigmoid is 3 flops + 1
    transcendental (negate, exp, add, divide — the transcendental is
    counted into flops here, matching how the fused-vs-unfused timing
    compares work), tanh 1 transcendental, multiplies/adds 1 each.

    - ``rh``    — ``sigmoid(r) * h``: 5 flops/elem; reads r+h, writes
      out (3 operands).
    - ``blend`` — ``(1-sz)*h + sz*tanh(q)``: 9 flops/elem; reads
      z+q+h, writes out (4 operands).
    """
    n = _gru_padded_elems(shape)
    if kind == "rh":
        return 5.0 * n, 3.0 * n * dtype_bytes
    if kind == "blend":
        return 9.0 * n, 4.0 * n * dtype_bytes
    raise ValueError(f"unknown gru gate kind {kind!r} "
                     "(expected 'rh' or 'blend')")


def analytic_lookup_encode_cost(batch: int,
                                level_hw: Sequence[Tuple[int, int]],
                                n_queries: int, radius: int,
                                features: int, block_q: int = 128,
                                pyramid_bytes: int = 4,
                                ) -> Tuple[float, float]:
    """``(flops, bytes)`` for one fused lookup→convc1 kernel call
    (``ops/pallas_corr.pallas_pyramid_lookup_encode``), derived from
    the kernel's block structure.

    Per level ``l`` with pooled shape ``(Hl, Wl)`` and ``k = 2r+1``
    taps per axis, each of the ``Npad`` padded queries runs:

    - the y tap accumulation — ``k`` FMAs per image row over ``Wl``
      lanes: ``2 * k * Hl * Wl`` flops per query;
    - the x contraction — ``k*k`` taps, each a multiply+reduce over
      ``Wl``: ``2 * k * k * Wl`` flops per query;

    then the fused convc1 contracts the ``kk_pad``-padded tap block
    against ``Fpad`` features (one MXU matmul + bias + relu):
    ``2 * kk_pad * Fpad + 2 * Fpad`` flops per query.

    Bytes: every level's correlation block streams through VMEM once
    per query block (``pyramid_bytes`` per element tracks the stored
    ``corr_dtype`` — int8 pyramids read 4x less than fp32), plus
    coords, the (broadcast) folded weights, and the output write.
    """
    k = 2 * radius + 1
    L = max(len(level_hw), 1)
    kk = L * k * k
    kk_pad = -(-kk // 8) * 8
    fpad = -(-int(features) // 128) * 128
    npad = -(-int(n_queries) // block_q) * block_q
    nblocks = npad // block_q
    flops = 0.0
    byts = 0.0
    for hl, wl in level_hw:
        hl, wl = int(hl), int(wl)
        if hl <= 0 or wl <= 0:
            continue
        flops += npad * (2.0 * k * hl * wl + 2.0 * k * k * wl)
        # each level's full (Hl, Wl, Npad) correlation volume is read
        # once per kernel call (block specs stream it per query block)
        byts += hl * wl * npad * float(pyramid_bytes)
    flops += npad * (2.0 * kk_pad * fpad + 2.0 * fpad)
    byts += 2 * npad * 4.0                      # coords (x, y) fp32
    byts += nblocks * kk_pad * fpad * 4.0       # weights re-read per block
    byts += npad * fpad * 4.0                   # output write
    return batch * flops, batch * byts


# ---------------------------------------------------------------------------
# cost book — the per-process / per-engine ledger
# ---------------------------------------------------------------------------


class CostBook:
    """Thread-safe ledger of captured :class:`ProgramCost` entries,
    keyed however the owner compiles (the serve engine uses its
    ``(bucket, lanes, prog)`` compile-ledger keys; the CLIs use plain
    program names).

    ``stamp`` optionally streams the capture out: ``raft_cost_*``
    gauges into ``registry`` (labeled ``program=<name>``) and one
    ``cost_report`` event into ``sink``.  ``observe`` attaches a
    measured wall time to a stamped program — THAT is when MFU/BW
    utilization become computable — refreshing the gauges and
    returning the span-attachable attrs (``flops``/``bytes``/``mfu``).
    Telemetry must never fail the workload: both swallow their own
    errors.
    """

    def __init__(self, registry=None, sink=None):
        self._lock = threading.Lock()
        self._costs: Dict[Hashable, ProgramCost] = {}
        self._registry = registry
        self._sink = sink

    def stamp(self, key: Hashable, cost: ProgramCost,
              emit: bool = True) -> ProgramCost:
        with self._lock:
            self._costs[key] = cost
        if emit:
            try:
                self._emit(cost)
            except Exception:
                pass
        return cost

    def get(self, key: Hashable) -> Optional[ProgramCost]:
        with self._lock:
            return self._costs.get(key)

    def table(self) -> Dict[Hashable, ProgramCost]:
        with self._lock:
            return dict(self._costs)

    def _emit(self, cost: ProgramCost,
              seconds: Optional[float] = None) -> None:
        if self._registry is not None:
            fpp = cost.flops_per_pair
            if fpp is not None:
                self._registry.gauge(
                    "raft_cost_flops_per_pair",
                    "compile-time FLOPs per image pair of the program "
                    "(per-device; XLA cost_analysis or analytic "
                    "fallback)").set(fpp, program=cost.program)
            if seconds is not None:
                m = cost.mfu(seconds)
                if m is not None:
                    self._registry.gauge(
                        "raft_cost_mfu",
                        "achieved / peak FLOP rate of the program's "
                        "last observed call (device-kind peak table; "
                        "absent on unknown peaks)").set(
                            m, program=cost.program)
                bw = cost.hbm_bw_util(seconds)
                if bw is not None:
                    self._registry.gauge(
                        "raft_cost_hbm_bw_util",
                        "achieved / peak HBM bandwidth of the "
                        "program's last observed call").set(
                            bw, program=cost.program)
        if self._sink is not None and seconds is None:
            # the one-per-program capture event; observe() refreshes
            # gauges only (a per-call event would be per-step noise)
            self._sink.emit("cost_report", **cost.as_record())

    def observe(self, key: Hashable, seconds: float) -> dict:
        """Attach one measured call duration to a stamped program.
        Returns trace-span attrs (``flops``/``bytes`` always; ``mfu``
        when the peak is known), ``{}`` for an unstamped key."""
        cost = self.get(key)
        if cost is None:
            return {}
        try:
            self._emit(cost, seconds=seconds)
        except Exception:
            pass
        attrs = {"flops": cost.flops, "bytes": cost.bytes}
        m = cost.mfu(seconds)
        if m is not None:
            attrs["mfu"] = round(m, 4)
        return attrs
