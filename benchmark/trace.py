"""Reduce a profiler trace (``*.xplane.pb``) to what the benchmark reports.

``jax.profiler.ProfileData`` reads the file with nothing but jax.  On a
TPU the trace holds one plane per chip, ``/device:TPU:<n>``, whose
``XLA Ops`` line has one event per operation the chip ran, and a
``/host:CPU`` plane with one line per host thread (the python tracer's
frames on the main thread's line).

* busy: the union of the operations' intervals on a chip;
* window: from the first operation's start to the last one's end on that
  chip, so the profiler's own start-up and shut-down are outside it;
* idle share: 1 - busy / window, reported for the chip that idles most;
* ``device_ops``: the ten operation names that took most time (mean over
  the chips);
* ``idle_gaps``: the ten longest gaps on the chip that idles most, each
  named by the host event that overlaps it most (the innermost, where
  several cover it alike).
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10


def newest_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def _union(intervals: list) -> tuple:
    """(total covered, gaps) of [start, end] pairs; gaps as (length,
    start, end), in time order."""
    covered, gaps = 0.0, []
    end = None
    for a, b in sorted(intervals):
        if end is None:
            start, end = a, b
        elif a > end:
            covered += end - start
            gaps.append((a - end, end, a))
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        covered += end - start
    return covered, gaps


def _short(name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..), kind=kLoop, ..`` -> ``fusion.3
    kLoop``: the trace names an operation by its whole HLO line."""
    head, _, rest = name.partition(" = ")
    kind = rest.partition("kind=")[2].partition(",")[0] if rest else ""
    return (head.lstrip("%") + (" " + kind if kind else ""))[:120]


def _device_lines(profile, device_prefix: str):
    for plane in profile.planes:
        if not plane.name.startswith(device_prefix):
            continue
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
        events = [(e.start_ns, e.start_ns + e.duration_ns, _short(e.name))
                  for ln in ops for e in ln.events if e.duration_ns > 0]
        if events:
            yield plane.name, events


def _host_events(profile) -> list:
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend((e.start_ns, e.start_ns + e.duration_ns,
                        f"{line.name}: {e.name}")
                       for e in line.events if e.duration_ns > 0)
    return out


def _blame(gap: tuple, host: list) -> str:
    _, a, b = gap
    best, best_key = "nothing traced on the host", (0.0, 0.0)
    for s, e, name in host:
        overlap = min(e, b) - max(s, a)
        if overlap <= 0:
            continue
        key = (round(overlap / (b - a), 2), -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce(path: str, device_prefix: str = DEVICE_PREFIX) -> dict:
    """The trace at ``path`` as a dict; seconds throughout.  Raises if no
    operation ran on a device: a traced run has to drive the chip."""
    import jax
    profile = jax.profiler.ProfileData.from_file(path)
    devices = []
    for name, events in _device_lines(profile, device_prefix):
        busy, gaps = _union([(a, b) for a, b, _ in events])
        window = max(b for _, b, _ in events) - min(a for a, _, _ in events)
        by_op: dict = {}
        for a, b, op in events:
            by_op[op] = by_op.get(op, 0.0) + (b - a)
        devices.append({"name": name, "busy": busy, "window": window,
                        "gaps": gaps, "by_op": by_op})
    if not devices:
        raise ValueError(f"no device operation in {path}: planes "
                         f"{[p.name for p in profile.planes]}")
    n = len(devices)
    worst = max(devices, key=lambda d: 1.0 - d["busy"] / d["window"])
    ops: dict = {}
    for d in devices:
        for op, t in d["by_op"].items():
            ops[op] = ops.get(op, 0.0) + t / n
    host = _host_events(profile)
    longest = sorted(worst["gaps"], reverse=True)[:TOP]
    return {
        "devices": n,
        "busy_s": sum(d["busy"] for d in devices) / n / 1e9,
        "window_s": sum(d["window"] for d in devices) / n / 1e9,
        "idle_pct_worst": 100.0 * (1.0 - worst["busy"] / worst["window"]),
        "device_ops": [[op, t / 1e9] for op, t in sorted(
            ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_blame(g, host), g[0] / 1e9] for g in longest],
    }
