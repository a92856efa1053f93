"""Roofline cost model — every compiled program self-reports its cost.

The bench rows used to carry hand-derived FLOP/byte constants (the
``RESNET50_TRAIN_GFLOP_PER_IMG`` era); the compiler already knows the
truth.  This module pulls FLOPs and bytes-accessed from compiled XLA
programs via ``jitted.lower(*abstract_args).compile().cost_analysis()``.
That AOT compile is a REAL duplicate XLA compilation under the default
config (a persistent-cache hit once the entry point has called
``config.place_compile_cache``), so instrumented hot paths enqueue it
on a background worker (:func:`schedule_analysis`) — the step/dispatch
path itself only ever pays dict lookups and gauge sets.  The facts become the roofline
quantities ("Tensor Processing Primitives", PAPERS.md):

- **arithmetic intensity** — FLOPs per byte of memory traffic,
- **roofline ceiling** — ``min(peak_flops, AI × peak_bandwidth)`` for
  the backend's peak table (TPU v5e/v4/v5p + a CPU fallback so tier-1
  exercises the whole path),
- **MFU** — achieved FLOP/s over peak FLOP/s per measured step,
- **HBM-bandwidth utilization** — achieved bytes/s over peak bytes/s.

Instrumentation contract: the trainer / serving engine call
:func:`schedule_analysis` once per compiled program *signature* (one
fn holds one program per shape bucket) and :func:`observe_step` once
per measured step with the matching ``sig`` (dict lookups + gauge sets
— no device sync, no compile).  Results land in the
``tpudl_perf_*`` metric family and in the flight-recorder ring; bench
records read them back through :func:`bench_detail`.

Per-program kinds come from :func:`tag_program` — ``train.step_cache``
tags every step it builds with its cache-key kind, so the top-K
breakdown (:func:`top_programs`) names programs ``train:MLP...``,
``serve:...``, ``dcn_grad_encode`` rather than ``<anonymous jit>``.

Gate: ``config.costmodel`` (``DL4J_TPU_COSTMODEL=0`` disables).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import weakref
from typing import Any, Optional

from deeplearning4j_tpu.config import get_config
from deeplearning4j_tpu.obs import tracing
from deeplearning4j_tpu.obs.registry import get_registry, setup_metrics

# ------------------------------------------------------------ peak table
# Public per-chip peaks: (bf16 dense FLOP/s, HBM bytes/s).  The CPU row
# is a deliberately modest synthetic ceiling (estimated=True) so the
# whole MFU/roofline path runs — and is testable — without a TPU.
_PEAK_TABLE = (
    # (device_kind substring, peak_flops, peak_bytes/s)
    ("v5 lite", 197e12, 819e9),          # v5e: device_kind "TPU v5 lite"
    ("v5e", 197e12, 819e9),
    ("v5p", 459e12, 2765e9),
    ("v5", 459e12, 2765e9),              # v5p: device_kind "TPU v5"
    ("v6", 918e12, 1640e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
)
_CPU_FALLBACK = (0.5e12, 50e9)           # synthetic; marked estimated


@dataclasses.dataclass(frozen=True)
class BackendPeaks:
    """What the roofline is drawn against for one backend."""

    name: str                  # e.g. "TPU v5 lite" / "cpu"
    peak_flops: float          # dense FLOP/s (bf16 on TPU)
    peak_bytes_per_s: float    # HBM (or DRAM) bandwidth
    estimated: bool = False    # True = synthetic/fallback numbers

    @property
    def ridge_intensity(self) -> float:
        """FLOPs/byte at which the roofline bends compute-bound."""
        return self.peak_flops / self.peak_bytes_per_s


def backend_peaks(device=None) -> BackendPeaks:
    """Peak table entry for ``device`` (default: local device 0), with
    ``DL4J_TPU_PEAK_TFLOPS`` / ``DL4J_TPU_PEAK_HBM_GBPS`` env overrides
    (set them when the silicon's measured ceiling differs from nominal).
    An accelerator the table does not know raises unless both overrides
    name its peaks: a roofline against another chip's peaks is a wrong
    number, not an estimate."""
    import jax
    dev = device if device is not None else jax.local_devices()[0]
    platform = dev.platform
    kind = (dev.device_kind or platform).lower()
    flops = bw = None
    estimated = platform == "cpu"
    if estimated:
        flops, bw = _CPU_FALLBACK
    else:
        for marker, f, b in _PEAK_TABLE:
            if marker in kind:
                flops, bw = f, b
                break
    # `estimated` clears only when BOTH axes are real (table hit or
    # override) — one override must not launder the other, still-
    # synthetic peak into a "measured" stamp
    flops_est = bw_est = estimated

    def _env_peak(name: str) -> Optional[float]:
        # malformed overrides are ignored with a warning, never raised:
        # analyze_jitted promises telemetry cannot break a training step
        raw = os.environ.get(name)
        if not raw:
            return None
        try:
            return float(raw)
        except ValueError:
            import logging
            logging.getLogger("deeplearning4j_tpu").warning(
                "ignoring malformed %s=%r (expected a number)", name, raw)
            return None

    env_f = _env_peak("DL4J_TPU_PEAK_TFLOPS")
    env_b = _env_peak("DL4J_TPU_PEAK_HBM_GBPS")
    if env_f is not None:
        flops, flops_est = env_f * 1e12, False
    if env_b is not None:
        bw, bw_est = env_b * 1e9, False
    if flops is None or bw is None:
        raise ValueError(
            f"no peak FLOP/s and HBM bandwidth known for device kind "
            f"{kind!r} on platform {platform!r}: add it to "
            f"obs.costmodel._PEAK_TABLE, or set DL4J_TPU_PEAK_TFLOPS "
            f"and DL4J_TPU_PEAK_HBM_GBPS")
    estimated = flops_est or bw_est
    reg = get_registry()
    reg.gauge("tpudl_perf_peak_flops").set(flops)
    reg.gauge("tpudl_perf_peak_hbm_bytes").set(bw)
    return BackendPeaks(kind, flops, bw, estimated)


# --------------------------------------------------------- program costs
@dataclasses.dataclass
class ProgramCost:
    """cost_analysis facts + derived roofline position for ONE compiled
    program (per single execution)."""

    kind: str
    flops: float
    bytes_accessed: float
    peaks: BackendPeaks

    @property
    def arith_intensity(self) -> float:
        return self.flops / max(self.bytes_accessed, 1.0)

    @property
    def roofline_flops(self) -> float:
        """Attainable FLOP/s at this program's arithmetic intensity."""
        return min(self.peaks.peak_flops,
                   self.arith_intensity * self.peaks.peak_bytes_per_s)

    @property
    def bound(self) -> str:
        return ("compute" if self.arith_intensity >= self.peaks.ridge_intensity
                else "memory")

    def mfu(self, step_seconds: float, calls: int = 1) -> float:
        return self.flops * calls / max(step_seconds, 1e-12) \
            / self.peaks.peak_flops

    def hbm_util(self, step_seconds: float, calls: int = 1) -> float:
        return self.bytes_accessed * calls / max(step_seconds, 1e-12) \
            / self.peaks.peak_bytes_per_s

    def to_dict(self) -> dict:
        return {"kind": self.kind, "flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "arith_intensity": round(self.arith_intensity, 3),
                "roofline_bound": self.bound,
                "backend": self.peaks.name,
                "peak_flops": self.peaks.peak_flops,
                "peak_hbm_bytes_per_s": self.peaks.peak_bytes_per_s,
                "peak_estimated": self.peaks.estimated}


_LOCK = threading.RLock()   # flight recorder's signal-path dump reads
                            # top_programs() and may re-enter from the
                            # same (interrupted) thread
# Cost entries are keyed (id(fn), sig): one jit-wrapped callable holds
# one compiled program PER call signature (serving buckets, bucketed
# train tails), and applying one bucket's FLOPs to another bucket's
# wall time would mis-report MFU by the bucket-size ratio.  ids recycle
# once the original fn is garbage-collected, so every entry carries a
# weakref to the fn it was recorded for and lookups validate identity
# (stale entry → absent).
_COSTS: dict[tuple, tuple] = {}         # (id(fn), sig) → (ref, cost)
_KINDS: dict[int, tuple] = {}           # id(fn) → (ref, kind tag)
_FAILED: dict[tuple, Any] = {}          # (id(fn), sig) → (ref, True)
_PENDING: set = set()                   # (id(fn), sig) queued for analysis
_LAST: dict[str, dict] = {}             # kind → last observed step facts
_LAST_KEY: Optional[str] = None         # most recently observed kind
_MAX_PROGRAMS = 256                     # sweep-proof bound on both maps


def _mkref(fn: Any):
    try:
        return weakref.ref(fn)
    except TypeError:                    # non-weakrefable callable: pin it
        return lambda f=fn: f


def _live(table: dict, fn: Any, key) -> Any:
    """Entry value for ``key``, dropping entries whose fn id was
    recycled by a different object (call under _LOCK)."""
    entry = table.get(key)
    if entry is None:
        return None
    ref, value = entry
    if ref() is not fn:
        del table[key]
        return None
    return value


def enabled() -> bool:
    return bool(get_config().costmodel)


def tag_program(fn: Any, kind: str) -> None:
    """Name a jit-wrapped callable for the cost breakdown (step_cache
    tags each step it builds with its cache-key kind)."""
    if fn is None:
        return
    with _LOCK:
        _KINDS[id(fn)] = (_mkref(fn), str(kind))
        while len(_KINDS) > _MAX_PROGRAMS:
            _KINDS.pop(next(iter(_KINDS)))


def program_kind(fn: Any) -> Optional[str]:
    with _LOCK:
        return _live(_KINDS, fn, id(fn))


def shape_sig(tree: Any) -> tuple:
    """Cheap call-signature key for per-signature cost entries: the
    (shape, dtype) of every array leaf.  Callers with one static shape
    per program can skip it (``sig=None``)."""
    import jax
    return tuple((tuple(leaf.shape), str(getattr(leaf, "dtype", "?")))
                 for leaf in jax.tree_util.tree_leaves(tree)
                 if hasattr(leaf, "shape"))


def should_analyze(fn: Any, sig=None) -> bool:
    """True when ``fn`` has no cost entry for this call signature and
    the model is on — the per-step fast-path check (dict lookups)."""
    if fn is None or not enabled():
        return False
    key = (id(fn), sig)
    with _LOCK:
        return (_live(_COSTS, fn, key) is None
                and _live(_FAILED, fn, key) is None
                and key not in _PENDING)


def costs_for(fn: Any, sig=None) -> Optional[ProgramCost]:
    with _LOCK:
        return _live(_COSTS, fn, (id(fn), sig))


def abstractify(tree: Any) -> Any:
    """args → ShapeDtypeStructs (None passes through), so analysis never
    holds (or donates) real buffers.  Mesh placements (NamedSharding)
    ride along: a unified-mesh layout's step is a DIFFERENT program than
    its single-device sibling — an AOT lower/compile (cost analysis,
    artifact bake) must reproduce the live call's SPMD partitioning, or
    the baked executable would bind single-device shardings and refuse
    (or mis-place) the sharded call.  Single-device placements stay
    implicit, keeping pre-layout artifacts byte-identical."""
    import jax
    from jax.sharding import NamedSharding

    def one(a):
        if a is None or not hasattr(a, "shape"):
            return a
        sharding = getattr(a, "sharding", None)
        if isinstance(sharding, NamedSharding):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return jax.tree_util.tree_map(one, tree)


def _total_cost(compiled) -> tuple[float, float]:
    """(flops, bytes accessed) across a compiled program's computations;
    cost_analysis returns a dict on some backends, a list of dicts on
    others."""
    analysis = compiled.cost_analysis()
    if analysis is None:
        return 0.0, 0.0
    parts = analysis if isinstance(analysis, (list, tuple)) else [analysis]
    flops = sum(float(p.get("flops", 0.0) or 0.0) for p in parts)
    bytes_accessed = sum(float(p.get("bytes accessed", 0.0) or 0.0)
                         for p in parts)
    return flops, bytes_accessed


def analyze_jitted(fn: Any, abstract_args: Any, kind: Optional[str] = None,
                   device=None, sig=None, tracer=None,
                   parent=None) -> Optional[ProgramCost]:
    """Pull cost_analysis from the compiled program behind ``fn`` for
    the given abstract call signature.  Once ``fn`` has run,
    ``fn.lower().compile()`` reuses its lowering and executable (jax 0.9:
    one trace event of about 0 s, no lowering or backend compile; 0.05-0.09
    s a program on a v5e); before, it is a real compile.  Use
    :func:`schedule_analysis` to keep it off the step/dispatch path
    entirely.  Never raises — telemetry must not break a training step.

    Its wall time is one observation of ``tpudl_perf_analysis_seconds``,
    and its compiles go into none of the ``tpudl_compile_*`` histograms
    (``tracing.owned_compiles``); where ``tracer`` (default: the global
    one) is on, a ``costmodel.analyze`` span under ``parent`` (default:
    the current span) holds their ``compile.*`` spans."""
    if fn is None or not enabled():
        return None
    kind = kind or program_kind(fn) or getattr(fn, "__name__", "program")
    t0 = time.perf_counter()
    try:
        with tracing.owned_compiles("costmodel.analyze", tracer=tracer,
                                    parent=parent, program=kind):
            return _analyze(fn, abstract_args, kind, device, sig)
    finally:
        setup_metrics().analysis.observe(time.perf_counter() - t0)


def _analyze(fn: Any, abstract_args: Any, kind: str, device,
             sig) -> Optional[ProgramCost]:
    key = (id(fn), sig)
    try:
        compiled = fn.lower(*abstract_args).compile()
        flops, bytes_accessed = _total_cost(compiled)
    except Exception:
        with _LOCK:
            _FAILED[key] = (_mkref(fn), True)
            while len(_FAILED) > _MAX_PROGRAMS:
                _FAILED.pop(next(iter(_FAILED)))
        return None
    if flops <= 0 and bytes_accessed <= 0:
        with _LOCK:
            _FAILED[key] = (_mkref(fn), True)
        return None
    cost = ProgramCost(kind, flops, bytes_accessed, backend_peaks(device))
    with _LOCK:
        ref = _mkref(fn)
        _COSTS[key] = (ref, cost)
        _KINDS[id(fn)] = (ref, kind)
        while len(_COSTS) > _MAX_PROGRAMS:
            _COSTS.pop(next(iter(_COSTS)))
    reg = get_registry()
    reg.labeled_gauge("tpudl_perf_program_flops",
                      label_names=("program",)).set(flops, program=kind)
    reg.labeled_gauge("tpudl_perf_program_bytes",
                      label_names=("program",)).set(bytes_accessed,
                                                   program=kind)
    from deeplearning4j_tpu.obs import flight_recorder
    flight_recorder.record("program_analyzed", program=kind, flops=flops,
                           bytes_accessed=bytes_accessed,
                           arith_intensity=round(cost.arith_intensity, 3),
                           roofline_bound=cost.bound)
    return cost


# ----------------------------------------------- background analysis
# fn.lower().compile() duplicates the program's XLA compile (seconds on
# CPU, minutes for a big model on TPU).  Instrumented hot paths
# (trainer step, serving dispatch, DCN codec) must not stall on it, so
# they enqueue the analysis onto ONE daemon worker; observe_step is a
# no-op for that signature until the analysis lands, after which every
# subsequent step self-reports.  Serialized on purpose: N concurrent
# duplicate compiles would contend with real work for host cores.
_ANALYSIS_QUEUE: Any = None
_WORKER: Optional[threading.Thread] = None


def _worker_loop(q) -> None:
    # analyze_jitted never raises for analysis failures (it records them
    # in _FAILED); this guard keeps the daemon alive across anything
    # unexpected (e.g. a registry error while publishing gauges).
    import logging
    log = logging.getLogger("deeplearning4j_tpu")
    while True:
        fn, abstract_args, kind, sig, tracer, parent = q.get()
        try:
            analyze_jitted(fn, abstract_args, kind=kind, sig=sig,
                           tracer=tracer, parent=parent)
        except Exception:
            log.warning("cost-model analysis failed for program %r",
                        kind, exc_info=True)
        finally:
            with _LOCK:
                _PENDING.discard((id(fn), sig))
            q.task_done()


def schedule_analysis(fn: Any, abstract_args: Any,
                      kind: Optional[str] = None, sig=None) -> None:
    """Queue :func:`analyze_jitted` on the background worker (idempotent
    per (fn, sig); the queue holds a strong ref to ``fn`` until the
    analysis runs).  Its ``costmodel.analyze`` span goes to the tracer
    active here, under the current span, so that a traced run shows
    where the analysis overlapped the steps."""
    global _ANALYSIS_QUEUE, _WORKER
    if fn is None or not enabled():
        return
    key = (id(fn), sig)
    with _LOCK:
        if key in _PENDING or _live(_COSTS, fn, key) is not None \
                or _live(_FAILED, fn, key) is not None:
            return
        _PENDING.add(key)
        if _ANALYSIS_QUEUE is None:
            import queue
            _ANALYSIS_QUEUE = queue.Queue()
            _WORKER = threading.Thread(
                target=_worker_loop, args=(_ANALYSIS_QUEUE,), daemon=True,
                name="tpudl-costmodel-analyzer")
            _WORKER.start()
    _ANALYSIS_QUEUE.put((fn, abstract_args, kind, sig, tracing.get_tracer(),
                         tracing.current_context()))


def drain(timeout_s: float = 60.0) -> bool:
    """Block until every scheduled analysis has run (tests / bench
    harnesses that assert on gauges right after a step).  Returns False
    on timeout."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with _LOCK:
            if not _PENDING:
                return True
        time.sleep(0.01)
    return False


def observe_step(fn: Any, step_seconds: float, calls: int = 1,
                 sig=None) -> None:
    """One measured execution of an analyzed program: update the
    ``tpudl_perf_mfu`` / ``tpudl_perf_hbm_util`` / intensity gauges and
    the per-program step-time histogram.  ``sig`` must match the value
    the program was analyzed under (bucketed callers pass their bucket —
    one fn holds one compiled program PER signature).  No-op for
    un-analyzed (fn, sig) pairs."""
    cost = costs_for(fn, sig=sig)
    if cost is None or step_seconds <= 0:
        return
    mfu = cost.mfu(step_seconds, calls)
    hbm = cost.hbm_util(step_seconds, calls)
    if mfu > 1.0 or hbm > 1.0:
        # jax dispatch is async: with tracing off (no loss sync) the
        # measured wall is dispatch-only, and a pipeline-filling burst
        # can "beat" the physical peak — on either axis (a memory-bound
        # program overshoots hbm_util long before mfu).  Such a sample
        # mis-attributes device time, so drop it — once dispatch
        # backpressure throttles the loop, steady-state samples land
        # below peak and record normally.
        return
    achieved = cost.flops * calls / step_seconds
    reg = get_registry()
    reg.gauge("tpudl_perf_mfu").set(mfu)
    reg.gauge("tpudl_perf_hbm_util").set(hbm)
    reg.gauge("tpudl_perf_arith_intensity").set(cost.arith_intensity)
    reg.gauge("tpudl_perf_roofline_fraction").set(
        achieved / max(cost.roofline_flops, 1.0))
    reg.labeled_histogram("tpudl_perf_step_seconds").observe(
        step_seconds, program=cost.kind)
    global _LAST_KEY
    with _LOCK:
        _LAST[cost.kind] = {"mfu": mfu, "hbm_util": hbm,
                            "arith_intensity": cost.arith_intensity,
                            "step_seconds": step_seconds, "calls": calls,
                            "cost": cost}
        _LAST_KEY = cost.kind


def last_observation(kind: Optional[str] = None) -> Optional[dict]:
    with _LOCK:
        key = kind or _LAST_KEY
        return dict(_LAST[key]) if key in _LAST else None


def top_programs(k: int = 5) -> list[dict]:
    """Top-K LIVE analyzed programs by FLOPs — the per-compiled-program
    cost breakdown surfaced in bench records and flight dumps.  Entries
    whose program was garbage-collected (a retired serving engine's
    forward) are purged here so dead programs don't crowd out live
    ones."""
    with _LOCK:
        dead = [key for key, (ref, _) in _COSTS.items() if ref() is None]
        for key in dead:
            del _COSTS[key]
        costs = [cost for _, cost in _COSTS.values()]
    costs.sort(key=lambda c: c.flops, reverse=True)
    return [c.to_dict() for c in costs[:k]]


def bench_detail(kind: Optional[str] = None) -> Optional[dict]:
    """The stamp every bench/serving record carries: MFU, HBM
    utilization and arithmetic intensity of the most recent measured
    step (optionally of a specific program kind), derived from XLA
    cost_analysis — never hand-entered."""
    obs = last_observation(kind)
    if obs is None:
        return None
    cost: ProgramCost = obs["cost"]
    return {
        "mfu": round(obs["mfu"], 4),
        "hbm_util": round(obs["hbm_util"], 4),
        "arith_intensity": round(obs["arith_intensity"], 3),
        "roofline_bound": cost.bound,
        "flops_per_step": cost.flops * obs["calls"],
        "bytes_per_step": cost.bytes_accessed * obs["calls"],
        "step_seconds": round(obs["step_seconds"], 6),
        "program": cost.kind,
        "backend": cost.peaks.name,
        "peak_flops": cost.peaks.peak_flops,
        "peak_hbm_bytes_per_s": cost.peaks.peak_bytes_per_s,
        "peak_estimated": cost.peaks.estimated,
        "source": "xla_cost_analysis",
    }


def measure(fn: Any, abstract_args: Any, step_seconds: float,
            kind: str, calls: int = 1) -> Optional[dict]:
    """Analyze (if needed, synchronously — the bench harness wants the
    stamp now) + observe + return the bench stamp."""
    if should_analyze(fn):
        analyze_jitted(fn, abstract_args, kind=kind)
    observe_step(fn, step_seconds, calls=calls)
    return bench_detail(kind=program_kind(fn) or kind)


def clear() -> None:
    """Drop all analyzed programs and observations (tests).  In-flight
    background analyses finish against the cleared maps."""
    global _LAST_KEY
    drain(timeout_s=5.0)
    with _LOCK:
        _COSTS.clear()
        _KINDS.clear()
        _FAILED.clear()
        _PENDING.clear()
        _LAST.clear()
        _LAST_KEY = None
