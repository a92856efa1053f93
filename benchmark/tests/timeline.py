"""The program's own timeline proven on the chip, at the cell's own size
(needs a TPU; nothing here is one of the benchmark's measurements):

    python benchmark/tests/timeline.py [--seed n] [--seconds 4] [--delay 1.0]

The operator's recipe, ``config.profiling`` and ``config.tracing`` both on,
around the cell's own program: ``resnet50_unfused`` under ``train_b128``
built by the cell's entry, warmed by its first steps, then ONE ``net.fit``
of ``--seconds`` over the deadline-bounded cycle with the loss read every
tenth step.  ``obs.profiler.trace`` records that fit with both of jax's
tracers off and writes ``spans.jsonl`` and ``timeline.json`` when it ends.
One delay of ``--delay`` seconds is injected at the program's own
``feeder.stage`` fault site a third of the way in: long enough to drain
the queue and every step the loop has dispatched ahead, so it has to show
as one device gap that the join names ``feed.wait`` on the loop's thread
with ``feed.stage`` on the producer's (as ``feed.stage>retry_attempt``:
``with_retries`` opens a span of its own around every attempt of a stage).

Prints the join (gaps, device time per step, scopes), how the gaps after
every tenth step's read are named, what the trace's planes hold (looked
at by hand once, for PERF.md), and a last line of JSON with the verdicts.
``spans.jsonl`` and ``timeline.json`` go to ``chiprun_out/timeline/``;
the trace itself is deleted.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for _path in (BENCH, ROOT):
    if _path not in sys.path:
        sys.path.insert(0, _path)

CELL = ("resnet50_unfused", "train_b128")


def metadata_stats(xplane: str, plane_name: str) -> dict:
    """{event name: {stat name: value}} of one plane's event metadata, off
    the file's wire format (``obs.profiler._op_names`` reads one stat of
    it; this is the whole of it, for the look by hand)."""
    from deeplearning4j_tpu.importers.onnx_wire import _fields

    def message(buf):
        return [(number, value) for number, _, value in _fields(buf)]
    with open(xplane, "rb") as f:
        planes = [message(v) for n, v in message(f.read()) if n == 1]
    for fields in planes:
        if dict(fields).get(2, b"").decode() != plane_name:
            continue
        names = {d[1]: d[2].decode() for d in (
            dict(message(dict(message(v))[2])) for n, v in fields if n == 5)}
        out = {}
        for meta in (message(dict(message(v))[2]) for n, v in fields
                     if n == 4):
            stats = {}
            for st in (dict(message(v)) for n, v in meta if n == 5):
                value = (st[5].decode(errors="replace") if 5 in st
                         else "ref:" + names.get(st[7], "?") if 7 in st
                         else st.get(3, st.get(4, st.get(2))))
                stats[names.get(st.get(1), "?")] = value
            out[dict(meta).get(2, b"").decode()] = stats
        return out
    return {}


def planes_by_hand(xplane: str) -> None:
    """Which planes and lines the trace holds, and where a scope shows."""
    import collections

    import jax
    from deeplearning4j_tpu.obs.profiler import _scope
    profile = jax.profiler.ProfileData.from_file(xplane)
    for plane in profile.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(f"[by hand] plane {plane.name!r}: "
              f"{[(n, c) for n, c in lines if c][:12]}")
    device = next((p for p in profile.planes
                   if p.name.startswith("/device:TPU:")), None)
    if device is None:
        return
    for line in device.lines:
        for event in list(line.events)[:2]:
            print(f"[by hand] {line.name}: {event.name[:110]!r} stats "
                  f"{[k for k, _ in event.stats]}")
    stats = metadata_stats(xplane, device.name)
    op_names = {name: st["tf_op"] for name, st in stats.items()
                if "tf_op" in st}
    print(f"[by hand] {len(stats)} event metadata on {device.name}; stats "
          f"they carry: "
          f"{collections.Counter(k for v in stats.values() for k in v)}")
    heavy = collections.Counter()
    for line in device.lines:
        if line.name == "XLA Ops":
            for event in line.events:
                heavy[event.name] += event.duration_ns
    for name, ns in heavy.most_common(6):
        shown = {k: str(v)[:90] for k, v in stats.get(name, {}).items()
                 if k not in ("shape_with_layout", "source_stack")}
        print(f"[by hand] {ns / 1e6:8.1f} ms {name.partition(' = ')[0]}: "
              f"{shown}")
    bare = collections.Counter()
    for name, ns in heavy.items():
        if _scope(op_names.get(name, "")) == "(unscoped)":
            bare[stats.get(name, {}).get("hlo_category", "?"),
                 op_names.get(name, "no tf_op")[:60]] += ns
    print(f"[by hand] unscoped time by (hlo_category, tf_op): "
          f"{[(k, round(v / 1e6, 1)) for k, v in bare.most_common(8)]}")
    copies = sorted({e.name.partition(" = ")[0].rstrip(".0123456789")
                     for ln in device.lines for e in ln.events
                     if "copy" in e.name.partition("(")[0].lower()
                     or "infeed" in e.name.lower()})
    print(f"[by hand] copy-like events on the device's plane: {copies[:12]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147483711)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--delay", type=float, default=1.0)
    args = ap.parse_args(argv)

    import harness
    import traffic
    devices = harness.require_chips(1)
    import jax
    from deeplearning4j_tpu import config as program_config
    from deeplearning4j_tpu.config import set_config
    from deeplearning4j_tpu.obs import costmodel, tracing
    from deeplearning4j_tpu.resilience import faults

    config = harness.load_json("configs", f"{CELL[0]}.json")
    mix = traffic.load_mix(CELL[1])
    program_config.place_compile_cache()
    weight_seed, data_seed, model_seed = harness._seeds(args.seed, 3)
    reference = harness.load_module("reference", config["reference"])
    entry = harness.load_module("entries", config["entry"]).make(config, mix)
    weights = jax.block_until_ready(reference.init_weights(config,
                                                           weight_seed))
    arrays = traffic.make_batches(mix, config["model"], data_seed)
    entry.build(weights, model_seed)
    batches = [entry.to_batch(a) for a in arrays]
    entry.first_steps(batches[:int(mix["first_steps"])])
    if not costmodel.drain(timeout_s=300):
        raise RuntimeError("cost-model analyses still queued after 300 s")
    print(f"[timeline] warmed on {devices[0].device_kind}", flush=True)

    out = os.path.join(ROOT, "chiprun_out", "timeline")
    shutil.rmtree(out, ignore_errors=True)
    step_s = mix["batch"] / 2573.6        # the cell's rate (PERF.md, PR 26)
    delay_at = int(args.seconds / 3 / step_s)
    steps_before = entry.steps()
    tracer = tracing.Tracer()             # this fit's spans alone
    set_config(profiling=True, tracing=True, trace_dir=out)
    try:
        with tracing.use_tracer(tracer), faults.inject(
                f"feeder.stage@{delay_at}:delay:{args.delay}"):
            cycle = traffic.DeadlineCycle(batches, args.seconds)
            t0 = cycle.start()
            entry.run(cycle)              # net.fit: writes timeline.json
            entry.wait()
            fit_s = time.perf_counter() - t0
    finally:
        set_config(profiling=False, tracing=False)
    steps = entry.steps() - steps_before
    with open(os.path.join(out, "timeline.json")) as f:
        joined = json.load(f)
    xplane = max(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    print(f"[timeline] {steps} steps in {fit_s:.3f} s (the fit includes "
          f"the profiler's start and stop), {len(tracer.spans)} spans, "
          f"trace {os.path.getsize(xplane) / 1e6:.1f} MB")
    planes_by_hand(xplane)
    for gap in joined["gaps"]:
        print(f"[timeline] gap {gap['ms']:9.3f} ms at {gap['at_ms']:9.3f}: "
              f"{gap['spans']}")
    print(f"[timeline] idle in gaps over 1 ms {joined['idle_ms_in_gaps_over_1ms']:.3f} ms, "
          f"named share {joined['named_idle_share']}")
    print(f"[timeline] device time per step {joined['steps']}")
    for scope, ms, share in joined["scopes"]:
        print(f"[timeline] scope {scope:32s} {ms:9.3f} ms {100 * share:6.2f}%")
    print(f"[timeline] scoped share {joined['scoped_share']}")

    # ---- the verdicts
    longest = joined["gaps"][0]
    # a name is qualified by its parent on the same thread:
    # "epoch>feed.wait", "feed.stage>retry_attempt" (one attempt of a stage)
    who = {(part, thread) for name, thread, share in longest["spans"]
           if share >= 0.5 for part in name.split(">")}
    loop_thread = tracer.find("step")[0].thread
    reads = sorted(s.end_ns for s in tracer.find("step.read")
                   if s.end_ns - s.start_ns > 5e6)    # the blocking reads
    origin = joined["profile_start_time_ns"]
    after_read = [g for g in joined["gaps"][1:] if any(
        abs(g["at_ms"] * 1e6 + origin - end) < 3e6 for end in reads)]
    verdict = {
        "delay_gap_ms": longest["ms"],
        "delay_named_feed_wait_on_loop": ("feed.wait", loop_thread) in who,
        "delay_named_feed_stage_on_producer":
            ("feed.stage", "tpudl-device-feeder") in who,
        "gaps_after_a_blocking_read": len(after_read),
        "their_names": [g["spans"][:3] for g in after_read[:3]],
        "all_top_gaps_named": all(g["spans"] for g in joined["gaps"]),
        "named_idle_share": joined["named_idle_share"],
        "scoped_share": joined["scoped_share"],
        "device_ms_per_step": {k: v["mean_ms"]
                               for k, v in joined["steps"].items()},
        "steps": steps, "device": devices[0].device_kind,
    }
    os.remove(xplane)
    entry.free()
    print(json.dumps(verdict), flush=True)
    ok = (verdict["delay_named_feed_wait_on_loop"]
          and verdict["delay_named_feed_stage_on_producer"]
          and verdict["all_top_gaps_named"]
          and (verdict["named_idle_share"] or 0) >= 0.9)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
