"""Profiling hooks: NaN/Inf panic; the device trace joined with the spans.

Parity with ND4J ``OpProfiler`` NAN_PANIC / INF_PANIC modes
(nd4j-api ``org/nd4j/linalg/profiler/OpProfiler.java``) and the per-op
timing the C++ graph executor records (libnd4j
``include/graph/profiling/GraphProfilingHelper``).  On TPU, per-op hooks
don't exist inside a jit region — XLA fuses everything — so the equivalents
are (a) post-step finite checks on outputs (host-side, only when enabled),
(b) ``jax.config.jax_debug_nans`` for trap-at-op granularity in debug runs,
(c) ``jax.profiler`` traces, which :func:`timeline` joins with the spans.
"""

from __future__ import annotations

import glob
import json
import os
import re
from contextlib import contextmanager
from typing import Any

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.config import get_config
from deeplearning4j_tpu.obs.tracing import get_tracer, self_intervals


class NonFiniteError(RuntimeError):
    pass


@jax.jit
def _finite_flags(leaves):
    """ONE fused device reduction over every inexact leaf: (any NaN,
    any Inf) as two scalars.  Re-traced per distinct leaf-list structure
    (cached thereafter); the alternative — a ``jnp.any`` + host ``bool``
    per leaf — costs one device→host sync per parameter tensor."""
    nan = jnp.zeros((), jnp.bool_)
    inf = jnp.zeros((), jnp.bool_)
    for leaf in leaves:
        nan = jnp.logical_or(nan, jnp.any(jnp.isnan(leaf)))
        inf = jnp.logical_or(inf, jnp.any(jnp.isinf(leaf)))
    return nan, inf


def check_finite(tree: Any, label: str = "output") -> None:
    """NAN_PANIC/INF_PANIC parity: raise when any leaf holds a
    non-finite value.  Only called by the trainer when
    ``config.nan_panic``/``inf_panic`` is set — it forces a device sync,
    so it's off by default.

    The scan is batched: all leaves reduce on device in one fused
    program and ONE (nan, inf) pair crosses to the host.  Only after a
    hit does the slow per-leaf walk run, to name the offending path."""
    cfg = get_config()
    if not (cfg.nan_panic or cfg.inf_panic):
        return
    flat = [(path, leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]
            if hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.inexact)]
    if not flat:
        return
    # explicit fence: ONE transfer for both flags — bool() on the raw
    # jit outputs would pay two hidden syncs (TPU502)
    nan_flag, inf_flag = jax.device_get(
        _finite_flags([leaf for _, leaf in flat]))
    has_nan = cfg.nan_panic and bool(nan_flag)
    has_inf = cfg.inf_panic and bool(inf_flag)
    if not (has_nan or has_inf):
        return
    # failure path only: walk leaves to anchor the error message
    for path, leaf in flat:
        if has_nan and bool(jnp.any(jnp.isnan(leaf))):
            raise NonFiniteError(f"NaN detected in {label} at {path}")
        if has_inf and bool(jnp.any(jnp.isinf(leaf))):
            raise NonFiniteError(f"Inf detected in {label} at {path}")
    raise NonFiniteError(f"non-finite value detected in {label}")


def enable_debug_nans(enable: bool = True) -> None:
    """Trap NaNs at op granularity (recompiles without fusion-hiding)."""
    jax.config.update("jax_debug_nans", enable)


@contextmanager
def trace(logdir: str):
    """``jax.profiler`` trace context with the python and the host tracer
    OFF: on a v5e the python tracer cost the loop a tenth of its rate, and
    the host tracer at any level made the runtime's layout transposes of a
    77 MB batch run twenty times slower (PERF.md section 6, "The tracer's
    cost").  What the host did comes from the program's own spans: with
    ``config.tracing`` on too they go to ``spans.jsonl`` and their join with
    the device's events (:func:`timeline`) to ``timeline.json``, beside it."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = options.host_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        if get_config().tracing:
            get_tracer().export_jsonl(os.path.join(logdir, "spans.jsonl"))
            found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                              recursive=True)
            with open(os.path.join(logdir, "timeline.json"), "w") as f:
                json.dump(timeline(max(found, key=os.path.getmtime),
                                   get_tracer().spans), f, indent=1)


def _op_names(path: str, plane_name: str) -> dict:
    """{event name: jax op name with its scopes} of one plane: the ``tf_op``
    stat of the event's *metadata*, which ``ProfileData`` does not show, so
    the wire format is read (XSpace.planes=1; XPlane.name=2, .event_metadata
    =4, .stat_metadata=5, maps of id -> {id=1, name=2, stats=5}; XStat
    .metadata_id=1, .str_value=5, .ref_value=7)."""
    from deeplearning4j_tpu.importers.onnx_wire import _fields

    def message(buf):
        return [(number, value) for number, _, value in _fields(buf)]
    with open(path, "rb") as f:
        planes = [message(v) for number, v in message(f.read()) if number == 1]
    for fields in planes:
        if dict(fields).get(2, b"").decode() != plane_name:
            continue
        entries = {no: [dict(message(v))[2] for n, v in fields if n == no]
                   for no in (4, 5)}
        stat = {d[1]: d[2].decode()
                for d in map(dict, map(message, entries[5]))}
        return {dict(meta)[2].decode():
                st[5].decode() if 5 in st else stat.get(st.get(7), "")
                for meta in map(message, entries[4])
                for st in (dict(message(v)) for n, v in meta if n == 5)
                if stat.get(st.get(1)) == "tf_op"}
    return {}


def _scope(op_name: str) -> str:
    """``jit(step)/jit(main)/transpose(jvp(res2_0_a_conv))/conv..`` ->
    ``res2_0_a_conv bwd``: the outermost scope (two where they nest),
    less jit's and autodiff's wrappers and the primitive's own name."""
    parts = [p for p in op_name.rstrip(":").split("/")[:-1]
             if not p.startswith(("jit(", "pjit("))]
    names = [n for n in (re.sub(r"\w+\(|\)", "", p) for p in parts) if n]
    if not names:
        return "(unscoped)"
    return "/".join(names[:2]) + (
        " bwd" if any("transpose(" in p for p in parts) else "")


def timeline(xplane_path: str, spans, top: int = 10,
             device_prefix: str = "/device:TPU:") -> dict:
    """Lay the program's spans over the first device's events of a profiler
    trace.  One clock: a span is Unix nanoseconds, the trace counts from its
    ``profile_start_time`` (``Task Environment`` plane).  ``gaps``: the
    ``top`` longest idle gaps of the ``XLA Ops`` line, each with the spans
    (and threads) whose own time overlaps it most; ``named_idle_share``: of
    the idle time in gaps over 1 ms, what one thread's spans cover; ``steps``:
    device time per program (``XLA Modules``); ``scopes``: by named scope."""
    profile = jax.profiler.ProfileData.from_file(xplane_path)
    origin = next(v for p in profile.planes if p.name == "Task Environment"
                  for k, v in p.stats if k == "profile_start_time")
    plane = next((p for p in profile.planes          # a CPU's trace has none
                  if p.name.startswith(device_prefix)), None)
    lines = {ln.name: sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in ln.events if e.duration_ns > 0)
             for ln in (plane.lines if plane else ())}
    ops = lines.get("XLA Ops", [])
    pieces = [(a - origin, b - origin, thread, name)
              for a, b, thread, name in self_intervals(spans)]
    gaps, busy_end = [], ops[0][1] if ops else 0
    for a, b, _ in ops:
        if a > busy_end:
            gaps.append((a - busy_end, busy_end, a))
        busy_end = max(busy_end, b)
    named, idle, covered = [], 0.0, 0.0
    for length, a, b in sorted(gaps, reverse=True)[:max(top, sum(
            g[0] > 1e6 for g in gaps))]:
        by, threads = {}, {}
        for pa, pb, thread, name in pieces:
            if pa < b and pb > a:
                t = min(pb, b) - max(pa, a)
                by[name, thread] = by.get((name, thread), 0) + t
                threads[thread] = threads.get(thread, 0) + t
        if length > 1e6:
            idle += length
            covered += min(length, max(threads.values(), default=0))
        if len(named) < top:
            named.append({"at_ms": a / 1e6, "ms": length / 1e6, "spans": [
                [*key, round(t / length, 3)] for key, t in
                sorted(by.items(), key=lambda kv: -kv[1])[:4]]})
    steps = {}
    for a, b, name in lines.get("XLA Modules", []):
        steps.setdefault(name.partition("(")[0], []).append((b - a) / 1e6)
    op_names = _op_names(xplane_path, plane.name) if ops else {}
    scopes, total = {}, sum(b - a for a, b, _ in ops) or 1
    for a, b, name in ops:
        scope = _scope(op_names.get(name, ""))
        scopes[scope] = scopes.get(scope, 0) + (b - a)
    return {"profile_start_time_ns": origin,
            "gaps": named, "idle_ms_in_gaps_over_1ms": idle / 1e6,
            "named_idle_share": covered / idle if idle else None,
            "steps": {name: {"count": len(ms), "mean_ms": sum(ms) / len(ms),
                             "max_ms": max(ms)} for name, ms in steps.items()},
            "scopes": [[scope, t / 1e6, round(t / total, 4)] for scope, t in
                       sorted(scopes.items(), key=lambda kv: -kv[1])[:top]],
            "scoped_share": (1.0 - scopes.get("(unscoped)", 0) / total
                             if ops else None)}
