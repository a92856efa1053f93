"""The readings a cell's limits are set from and held against, on the chip
at the cell's own size (training's readings need no measured window):

    python benchmark/tests/readings.py --workload <cell> --seeds 12 --control-seeds 3

For every seed: the program's first steps through its entry (the window's
own ``fit`` and feeder) against the float32 reference: the LOWER reading
of each number compared.  For the first ``--control-seeds`` seeds also

* the control: the reference in float8 put in the program's place;
* the fault "half of the batch left out, the mean taken over the rest",
  planted in the reference put in the program's place.

"A step that returns its state unchanged" reads 1 on ``delta_gap`` by
construction and needs no run.  Each of the three is then put through
``compare.decide`` against the committed ``limits/<cell>.json``: the
verdict a run of the cell would give.  One JSON object per seed goes to
standard output and to ``chiprun_out/readings.<cell>.jsonl``.
``test_control.py`` runs the same function at a tiny size on the CPU.

A cell that is not in ``BENCHMARK.json`` (one left out because the
program is at fault) is read by naming its files: ``--config`` and
``--traffic``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for _path in (BENCH, ROOT):
    if _path not in sys.path:
        sys.path.insert(0, _path)


def verdict(numbers: dict, limits: dict) -> dict:
    """``compare.decide`` in short: correct, and the numbers over their
    limit as ``{name: [value, limit]}``."""
    import compare
    correct, compared = compare.decide(numbers, limits)
    return {"correct": correct,
            "over": {name: [c["value"], c["limit"]]
                     for name, c in compared.items()
                     if not c["value"] <= c["limit"]}}


def one_seed(config: dict, mix: dict, seed: int, *, control: bool,
             limits: dict) -> dict:
    """Gaps of the program, and with ``control`` of the float8 control and
    of the half-batch fault, against the float32 reference; each with the
    verdict ``limits`` gives it."""
    import numpy as np

    import compare
    import harness
    import traffic
    weight_seed, data_seed, model_seed = harness._seeds(seed, 3)
    reference = harness.load_module("reference", config["reference"])
    entry = harness.load_module("entries", config["entry"]).make(config, mix)
    weights = reference.init_weights(config, weight_seed)
    arrays = traffic.make_batches(mix, config["model"], data_seed)
    n_first = int(mix["first_steps"])
    entry.build(weights, model_seed)
    program = entry.first_steps([entry.to_batch(a)
                                 for a in arrays[:n_first]])
    entry.free()

    def follow(**how):
        return reference.first_steps(config, mix, weights, arrays[:n_first],
                                     seed=model_seed, **how)

    wanted = follow()
    out = {"seed": seed, "losses": {"program": program["losses"],
                                    "reference": wanted["losses"]}}
    out["program"], out["program_leaf"] = compare.gaps(program, wanted)
    per_leaf = compare.leaf_gaps(program["grad_norms"], wanted["grad_norms"],
                                 wanted["grad_norms"])
    out["program_worst_grad_leaves"] = sorted(
        per_leaf.items(), key=lambda kv: -kv[1])[:6]
    if control:
        out["control_fp8"], _ = compare.gaps(follow(precision="fp8"), wanted)
        half = np.ones((int(mix["batch"]),), np.float32)
        half[len(half) // 2:] = 0.0
        out["fault_half_batch"], _ = compare.gaps(follow(row_weights=half),
                                                  wanted)
    out["verdict"] = {who: verdict(out[who], limits)
                      for who in ("program", "control_fp8",
                                  "fault_half_batch") if who in out}
    return out


def load_cell(workload: str, config_name: str = "",
              traffic_name: str = "") -> tuple:
    """(cell, config, mix, limits) by the cell's name in ``BENCHMARK.json``,
    or by its files' names where it is not there."""
    import compare
    import harness
    import traffic
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {c["name"]: c for c in json.load(f)["workloads"]}
    cell = cells.get(workload) or {"name": workload, "config": config_name,
                                   "traffic": traffic_name, "chips": 1}
    return (cell, harness.load_json("configs", f"{cell['config']}.json"),
            traffic.load_mix(cell["traffic"]), compare.load_limits(workload))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--config", default="")
    ap.add_argument("--traffic", default="")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--tag", default="", help="suffix of the output file")
    args = ap.parse_args(argv)
    import harness
    from deeplearning4j_tpu import config as program_config
    from deeplearning4j_tpu.obs import costmodel
    cell, config, mix, limits = load_cell(args.workload, args.config,
                                          args.traffic)
    harness.require_chips(int(cell["chips"]))
    program_config.place_compile_cache()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"readings.{cell['name']}{args.tag}.jsonl"), "w") as f:
        for i in range(args.seeds):
            row = one_seed(config, mix, args.first_seed + 7919 * i,
                           control=i < args.control_seeds, limits=limits)
            costmodel.drain(timeout_s=300)
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
