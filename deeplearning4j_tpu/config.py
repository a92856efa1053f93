"""Global configuration and dtype policy.

Replaces the reference's three overlapping config surfaces
(``ND4JSystemProperties`` / ``Nd4jEnvironmentVars`` /
``Nd4j.getEnvironment()`` — see nd4j-api ``org/nd4j/config/`` and
``sd::Environment`` in libnd4j ``include/system/Environment.h``) with ONE
dataclass-based config overridable by ``DL4J_TPU_*`` environment variables.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any

import jax
import jax.numpy as jnp

_ENV_PREFIX = "DL4J_TPU_"

# Sharding-invariant random streams: with the legacy (non-partitionable)
# threefry lowering, the VALUES jax.random produces under GSPMD depend on
# how XLA happens to partition the op — a dropout mask computed on a
# dp2xtp2 mesh differed from the single-device mask (measured on
# XLA:CPU), which breaks the unified-mesh layout-equivalence contract
# (same per-step losses to 1e-6 on ANY layout, dropout active).  The
# partitionable implementation computes each element as a pure function
# of (key, index), so every layout draws identical bits.  Set once,
# process-wide, before any program traces.
jax.config.update("jax_threefry_partitionable", True)


@dataclasses.dataclass
class DTypePolicy:
    """Mixed-precision policy: params stored in ``param_dtype``, matmuls/convs
    computed in ``compute_dtype``, outputs (losses, metrics) in
    ``output_dtype``.  On TPU the MXU wants bfloat16 inputs; float32 params
    keep optimizer numerics intact (the reference is float32-everywhere —
    libnd4j ``DataType`` enum — so ``float32`` policy gives bit-parity while
    ``bfloat16`` policy gives speed)."""

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    output_dtype: Any = jnp.float32

    @classmethod
    def bf16(cls) -> "DTypePolicy":
        """Mixed-precision speed policy: f32 params, bf16 MXU compute AND
        bf16 layer outputs.  Keeping activations bf16 end-to-end halves
        HBM traffic — ResNet-50 training on v5e is HBM-bound, and an f32
        output dtype was measured to cost ~35% throughput (before PR 1).
        Loss/score math stays f32 (OutputLayer casts before the loss)."""
        return cls(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16, output_dtype=jnp.bfloat16)

    @classmethod
    def f32(cls) -> "DTypePolicy":
        return cls()


@dataclasses.dataclass
class Config:
    """Runtime knobs (``Nd4j.getEnvironment()`` parity).

    - ``debug`` / ``verbose``: mirrors sd::Environment toggles.
    - ``nan_panic`` / ``inf_panic``: OpProfiler NAN_PANIC/INF_PANIC modes
      (nd4j-api ``org/nd4j/linalg/profiler/OpProfiler``): scan step outputs
      and raise on the first non-finite value.
    - ``default_seed``: global RNG seed used when nets don't specify one.
    - ``metrics_dir``: where jsonl metric streams are written.
    - ``prefetch_size``: prefetch queue depth (AsyncDataSetIterator and
      the DeviceFeeder background stage).
    - ``device_feed``: overlap host ETL + host→device transfer with the
      device step via ``data.device_pipeline.DeviceFeeder`` in
      ``Trainer.fit`` (double buffering ahead of the donating step).
    - ``shape_bucketing``: pad ragged tail batches up to a static bucket
      shape with mask-extension (zero loss / zero gradient padding) so
      an epoch compiles the train step once — see docs/data_pipeline.md.
    - ``artifact_store``: honor the compiled-artifact store
      (``train.artifact_store``): warm-load serialized executables from
      checkpoint zips at deploy/resume/respawn time and dispatch
      matching calls to them with zero JIT on the request path.  On by
      default (loading is cheap and refuses stale artifacts);
      ``DL4J_TPU_ARTIFACT_STORE=0`` reverts to live compilation
      everywhere.
    - ``artifact_bake``: let trainers bake (AOT-compile + serialize)
      their train/eval programs on a background worker after the first
      steady-state step, so every checkpoint written afterwards carries
      warm-start artifacts.  Off by default — baking duplicates each
      program's XLA compile; production fleets (and the supervisor's
      gang children) turn it on for millisecond respawns.
    - ``tracing``: enable span-based tracing (``obs.tracing``); spans add
      a device sync per step, so it's off by default.
    - ``trace_dir``: where span jsonl / Chrome-trace / ``jax.profiler``
      dumps land.
    - ``profiling``: capture a ``jax.profiler`` trace (HLO-level,
      Perfetto-viewable) around ``Trainer.fit`` into ``trace_dir``.
    - ``costmodel``: roofline cost model (``obs.costmodel``) — pull
      FLOPs/bytes from each compiled step via XLA ``cost_analysis`` and
      publish per-step MFU / HBM-utilization gauges (``tpudl_perf_*``).
      The step path itself only pays dict lookups, but the analysis is
      an AOT *duplicate* of the program's XLA compile, run once per
      program on a background worker (host CPU seconds-to-minutes for
      big models; a persistent-cache hit once the entry point has
      called ``place_compile_cache``).  On by default;
      ``DL4J_TPU_COSTMODEL=0`` disables.
    """

    debug: bool = False
    verbose: bool = False
    nan_panic: bool = False
    inf_panic: bool = False
    default_seed: int = 0
    metrics_dir: str = "runs"
    prefetch_size: int = 2
    device_feed: bool = True
    shape_bucketing: bool = True
    artifact_store: bool = True
    artifact_bake: bool = False
    profiling: bool = False
    tracing: bool = False
    trace_dir: str = "traces"
    costmodel: bool = True

    @classmethod
    def env_var_for(cls, field_name: str) -> str:
        return _ENV_PREFIX + field_name.upper()

    @classmethod
    def from_env(cls) -> "Config":
        cfg = cls()
        for f in dataclasses.fields(cls):
            raw = os.environ.get(_ENV_PREFIX + f.name.upper())
            if raw is None:
                continue
            if f.type in ("bool", bool):
                setattr(cfg, f.name, raw.lower() in ("1", "true", "yes"))
            elif f.type in ("int", int):
                setattr(cfg, f.name, int(raw))
            else:
                setattr(cfg, f.name, raw)
        return cfg


# ----------------------------------------------------------- env contract
# The static declaration of every USER-FACING ``DL4J_TPU_*`` knob — the
# variables a person (or a deployment manifest) sets, which the code
# reads without any in-tree setter.  ``Config.from_env`` reads its
# fields dynamically (``_ENV_PREFIX + field.upper()``), which no static
# analysis can see; this table is the statically-checkable face of that
# contract.  The TPU503 whole-program rule (analyze --dataflow) treats
# a read-never-set variable as an error UNLESS it is declared here, and
# the generated env-var table in docs/static_analysis.md is built from
# the same data — so adding a knob without declaring it reds the gate,
# and declaring it documents it in the same keystroke.  Internal
# launcher→child plumbing (DL4J_TPU_FLIGHT_DUMP, _WORKER_ID, …) is
# deliberately NOT declared: those must have both a setter and a reader
# in-tree, and TPU503 checks exactly that.
ENV_KNOBS: dict[str, str] = {
    # Config dataclass fields (read dynamically by Config.from_env)
    "DL4J_TPU_DEBUG": "config.debug: sd::Environment-style debug toggle",
    "DL4J_TPU_VERBOSE": "config.verbose: verbose logging toggle",
    "DL4J_TPU_NAN_PANIC": "config.nan_panic: raise on NaN step outputs",
    "DL4J_TPU_INF_PANIC": "config.inf_panic: raise on Inf step outputs",
    "DL4J_TPU_DEFAULT_SEED": "config.default_seed: global RNG seed",
    "DL4J_TPU_METRICS_DIR": "config.metrics_dir: jsonl metric stream dir",
    "DL4J_TPU_PREFETCH_SIZE": "config.prefetch_size: prefetch queue depth",
    "DL4J_TPU_DEVICE_FEED": "config.device_feed: DeviceFeeder double "
                            "buffering in Trainer.fit",
    "DL4J_TPU_SHAPE_BUCKETING": "config.shape_bucketing: pad ragged tail "
                                "batches to static bucket shapes",
    "DL4J_TPU_ARTIFACT_STORE": "config.artifact_store: warm compiled "
                               "programs from checkpoint zips",
    "DL4J_TPU_ARTIFACT_BAKE": "config.artifact_bake: background "
                              "AOT-bake of train/eval programs (the "
                              "supervisor turns it on for gang children)",
    "DL4J_TPU_PROFILING": "config.profiling: jax.profiler trace around "
                          "Trainer.fit",
    "DL4J_TPU_TRACING": "config.tracing: span-based tracing (the "
                        "launcher also turns it on for gang children)",
    "DL4J_TPU_TRACE_DIR": "config.trace_dir: span/profiler dump dir",
    "DL4J_TPU_COSTMODEL": "config.costmodel: roofline MFU/HBM gauges "
                          "from XLA cost_analysis",
    # Distributed-init knobs (parallel.launcher env fallbacks)
    "DL4J_TPU_COORDINATOR": "launcher: coordinator address fallback for "
                            "jax.distributed.initialize",
    "DL4J_TPU_NUM_PROCESSES": "launcher: process count fallback",
    "DL4J_TPU_PROCESS_ID": "launcher: this process's index fallback",
    # Observability / native knobs with no in-tree setter
    "DL4J_TPU_UI_HOST": "obs.ui_server: bind address for the metrics UI",
    "DL4J_TPU_WATCHDOG_GRACE_S": "obs.flight_recorder: extra grace "
                                 "before a fired watchdog _exits",
    "DL4J_TPU_PEAK_TFLOPS": "obs.costmodel: device peak TFLOP/s "
                            "override for MFU",
    "DL4J_TPU_PEAK_HBM_GBPS": "obs.costmodel: device peak HBM GB/s "
                              "override",
    "DL4J_TPU_NATIVE_SANITIZE": "native: pure-Python reference path for "
                                "the packbits/codec fast paths",
}

_lock = threading.Lock()
_config: Config | None = None
_policy = DTypePolicy()


def place_compile_cache() -> str:
    """Give jax's persistent compilation cache a directory and return
    it.  Entry points (``chip_smoke.py``, ``bench.py``, ``bench/*.py``)
    call this before their first compile; nothing calls it at import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, whoever runs the program
    has placed the cache and jax reads the variable itself: nothing is
    set here.  Otherwise the cache lives at ``<checkout>/.jax_cache`` —
    the directory is part of every entry's key, so it is a fixed path
    and never a temporary one."""
    # A Mosaic kernel rides inside its custom call as bytecode WITH its
    # MLIR locations, and jax's cache key strips debug info only from the
    # module around it.  With whole tracebacks in those locations the key
    # of every program that holds a Pallas kernel changes with the Python
    # call stack that first traced it — the train step of ``net.fit``
    # never hit the entry ``fit_batch`` wrote (seen on the chip, PR 22).
    # One frame, the innermost, is the same from every call stack.  (PR 22
    # switched ``jax_include_full_tracebacks_in_locations`` off instead:
    # that also moves the name stack out of the location XLA reads, and
    # every ``jax.named_scope`` was gone from the compiled program and
    # from the device trace, PR 27.)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def get_config() -> Config:
    global _config
    with _lock:
        if _config is None:
            _config = Config.from_env()
        return _config


def set_config(**kwargs: Any) -> Config:
    cfg = get_config()
    for k, v in kwargs.items():
        if not hasattr(cfg, k):
            raise AttributeError(f"unknown config key: {k}")
        setattr(cfg, k, v)
    return cfg


def dtype_policy() -> DTypePolicy:
    return _policy


def set_dtype_policy(policy: DTypePolicy) -> None:
    global _policy
    _policy = policy
