"""One run of one cell: set-up, the measured window, the trace, the
metrics, the comparison with the reference, the result line.

Driven by data.  A cell names a configuration and a traffic mix; the
configuration names its entry module and its reference.  Whatever belongs
to one of them sits in a file of its own that is found by name:

    configs/<config>.json       sizes, precision, optimizer, entry, reference
    traffic/<mix>.json          the batches (traffic.py draws them)
    entries/<entry>.py          drives the program's public ``fit``
    reference/<reference>.py    the plain float32 yardstick
    flops/<flops>.py            operations per unit of the rate, from shapes
    metrics/<metric>.py         one reader: ``read(obs) -> number | None``
    limits/<cell>.json          the limit of each number compared

``run.py`` looks for the chip and calls :func:`run_cell`; the tests under
``tests/`` call it directly with tiny sizes.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")     # traces; in .gitignore
TRACE_SECONDS = 4.0


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by file, so that a metric may have a
    dot in its name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"BENCHMARK.json or a configuration names "
                                f"{name!r}, and {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_metrics(benchmark: dict, cell: str, trace: bool) -> dict:
    """{name: unit} of the metrics this run reports: the cell's end-to-end
    ones, or with ``--trace 1`` its per-layer ones."""
    return {m["name"]: m["unit"] for m in benchmark["per_layer" if trace
                                                    else "end_to_end"]
            if cell in m.get("workloads", [cell])}


def require_chips(chips: int) -> list:
    """The devices, or exit non-zero: never a CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: needs a TPU, jax found platform "
                 f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell needs {chips} chip(s), jax found "
                 f"{len(devices)}")
    return devices


def _seeds(seed: int, n: int) -> list:
    import numpy as np
    return [int(s) % (2 ** 30)
            for s in np.random.SeedSequence(int(seed)).generate_state(n)]


def _registry_snapshot() -> dict:
    """Every counter and gauge of the program's registry by name; a
    histogram as its (sum, count)."""
    from deeplearning4j_tpu.obs.registry import get_registry
    registry, out = get_registry(), {}
    for name in registry.names():
        metric = registry.get(name)
        if hasattr(metric, "bucket_counts"):
            try:
                total, count = metric.sum, metric.count
            except TypeError:
                continue
            out[name] = (total() if callable(total) else total,
                         count() if callable(count) else count)
        elif hasattr(metric, "value"):
            try:
                value = metric.value
                out[name] = value() if callable(value) else value
            except TypeError:
                continue
    return out


class _CacheEvents:
    """jax's own count of persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self)

    def __call__(self, event: str, **_):
        if "/compilation_cache/" not in event:
            return
        name = event.rsplit("/", 1)[-1]
        if name == "cache_hits":
            self.hits += 1
        elif name == "cache_misses":
            self.misses += 1


class _Tracer:
    """Profile ``TRACE_SECONDS`` in the middle of the window from a timer
    thread, so that the window's own thread never waits for the profiler.
    The python tracer stays off: with it on, the host's loop ran a tenth
    slower.  The host tracer stays off too: at any level but 0 the
    runtime's layout transposes of a 77 MB batch ran some twenty times
    slower and starved the chip for seconds (my chip runs, PR 26), so idle
    gaps carry host names only once the program annotates them itself.
    Only a rehearsal that reduces the host's own plane (the CPU tests'
    ``device_prefix``) turns it on: that plane is empty without it.  One
    recording a process: a second one came back without the device's
    plane."""

    def __init__(self, seconds: float, directory: str, device_prefix: str):
        self.device_prefix = device_prefix
        self.host_tracer_level = int(device_prefix.startswith("/host:"))
        self.length = min(TRACE_SECONDS, seconds / 2.0)
        self.delay = (seconds - self.length) / 2.0
        self.directory = directory
        self.error = None
        self.thread = threading.Thread(target=self._run, name="bench-trace")

    def _run(self):
        import jax
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = self.host_tracer_level
            time.sleep(self.delay)
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            try:
                time.sleep(self.length)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:          # reported by summary()
            self.error = e

    def summary(self) -> dict:
        # by file: a plain ``import trace`` may find the standard library's
        trace_mod = load_module("", "trace")
        self.thread.join()
        if self.error is not None:
            raise self.error
        return trace_mod.reduce(trace_mod.newest_xplane(self.directory),
                                device_prefix=self.device_prefix)


def _memory_peak(devices, live_bytes: dict, compiled) -> int:
    """Peak on the fullest chip.  ``memory_stats()`` alone leaves a
    step's scratch out on this runtime (0.63 GB after a 6.8 GB step, PR
    22), so the compiled step's own analysis is added to what the process
    keeps on the device: live bytes + temporaries + outputs that alias no
    donated input."""
    step = 0
    if compiled is not None:
        ma = compiled.memory_analysis()
        if ma is not None:
            step = int(ma.temp_size_in_bytes) + max(
                0, int(ma.output_size_in_bytes) - int(ma.alias_size_in_bytes))
    peak = 0
    for device in devices:
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   live_bytes.get(device.id, 0) + step)
    return peak


def _finite(x) -> float:
    """``x`` as a python float the result line can hold: an entry may
    hand over a device or numpy scalar, and JSON has no nan or inf."""
    x = float(x)
    return x if math.isfinite(x) else 1e30


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             config: dict, mix: dict, limits: dict, metrics: dict,
             devices: list, started: float, out_dir: str = OUT_DIR,
             device_prefix: str = "/device:TPU:") -> dict:
    """Run ``cell`` and return the result line as a dict.  ``started`` is
    the ``time.perf_counter()`` of the process's start: set-up is
    everything from there to the window."""
    import jax

    import compare
    import traffic
    from deeplearning4j_tpu import config as program_config
    from deeplearning4j_tpu.obs import costmodel

    # ---- set-up: weights, batches, the program, its first steps ------------
    phases = {"imports": time.perf_counter() - started}
    last = [time.perf_counter()]

    def mark(name: str) -> None:
        now = time.perf_counter()
        phases[name], last[0] = now - last[0], now

    program_config.place_compile_cache()
    cache = _CacheEvents()
    weight_seed, data_seed, model_seed = _seeds(seed, 3)
    reference = load_module("reference", config["reference"])
    entry = load_module("entries", config["entry"]).make(config, mix)
    readers = {name: load_module("metrics", name) for name in metrics}
    weights = jax.block_until_ready(
        reference.init_weights(config, weight_seed))
    mark("weights")
    arrays = traffic.make_batches(mix, config["model"], data_seed)
    mark("batches")
    entry.build(weights, model_seed)
    batches = [entry.to_batch(a) for a in arrays]
    mark("build")
    n_first = int(mix["first_steps"])
    program = entry.first_steps(batches[:n_first])
    mark("first_steps")
    # the cost model (on by default) compiles the step a second time on a
    # background thread: wait for it here, as bench.py does, so that it
    # neither runs inside the window nor dies with the interpreter
    if not costmodel.drain(timeout_s=300):
        raise RuntimeError("cost-model analyses still queued after 300 s")
    mark("cost_model_drain")
    setup_s = time.perf_counter() - started

    # ---- the window: the public fit over a deadline-bounded iterator -------
    tracer = None
    if trace:
        shutil.rmtree(out_dir, ignore_errors=True)
        tracer = _Tracer(seconds, os.path.join(out_dir, "trace"),
                         device_prefix)
    counters_before = _registry_snapshot()
    steps_before, recompiles_before = entry.steps(), entry.recompiles()
    cycle = traffic.DeadlineCycle(batches, seconds)
    if tracer is not None:
        tracer.thread.start()
    t0 = cycle.start()
    entry.run(cycle)
    entry.wait()
    window_s = time.perf_counter() - t0
    steps = entry.steps() - steps_before
    recompiles = entry.recompiles() - recompiles_before
    counters_after = _registry_snapshot()
    summary = tracer.summary() if tracer is not None else None
    if tracer is not None:
        shutil.rmtree(out_dir, ignore_errors=True)

    # ---- memory, then free the program before the reference runs -----------
    live = {d.id: int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in devices}
    compiled = entry.lowered_step(batches[0]).compile()
    memory_peak = _memory_peak(devices, live, compiled)
    window_losses = entry.window_losses()
    del compiled
    entry.free()
    del batches

    # ---- correct: the first steps against the plain reference --------------
    wanted = reference.first_steps(config, mix, weights, arrays[:n_first],
                                   seed=model_seed)
    print("[benchmark] losses: program", program["losses"], "reference",
          wanted["losses"], file=sys.stderr)
    numbers, where = compare.gaps(program, wanted)
    correct, compared = compare.decide(numbers, limits)
    units = steps * int(mix["batch"]) * int(mix["units_per_row"])
    handed_out = cycle.handed_out
    failed = 0
    if recompiles or steps != handed_out or not all(
            math.isfinite(x) for x in window_losses):
        failed = handed_out           # the whole window is suspect
        correct = False
    obs = {
        "cell": cell["name"], "config": config, "mix": mix,
        "device_kind": devices[0].device_kind, "chips": int(cell["chips"]),
        "setup": {"seconds": setup_s, "cache_misses": cache.misses,
                  "cache_hits": cache.hits},
        "window": {"seconds": window_s, "steps": steps, "units": units,
                   "rate": units / window_s},
        "counters": {"before": counters_before, "after": counters_after},
        "trace": summary,
    }
    out_metrics = {}
    for name, reader in readers.items():
        value = reader.read(obs)
        if value is not None:       # a reader that found nothing to read
            out_metrics[name] = {"value": float(value),
                                 "unit": metrics[name]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": int(handed_out),
              "failed": int(failed), "metrics": out_metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["window"] = {"seconds": window_s, "steps": steps,
                        "recompiles": recompiles, "setup_s": setup_s,
                        "cache_hits": cache.hits,
                        "cache_misses": cache.misses,
                        "setup_phases": phases,
                        "losses_read": len(window_losses),
                        "last_loss": (_finite(window_losses[-1])
                                      if window_losses else None)}
    result["compared"] = {
        name: {"value": _finite(c["value"]), "limit": c["limit"],
               **({"leaf": where[name]} if name in where else {})}
        for name, c in compared.items()}
    return result


def report(result: dict) -> None:
    """The numbers compared beside their limits as the last lines of
    standard error; the result as the last line of standard output."""
    sys.stdout.flush()
    for name, c in result["compared"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"[benchmark] compared {name} {c['value']:.6g} limit "
              f"{c['limit']:.6g} {verdict} {c.get('leaf', '')}",
              file=sys.stderr)
    print(f"[benchmark] correct {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
