"""The look at ResNet-50's worst-leaf gaps (PERF.md section 6): who sides
with the float32 reference on ``stem.beta``, ``stem.gamma`` and
``res2_0.a.w``, on the chip at the cell's own size:

    python benchmark/tests/witness.py --seeds 3

With every gamma at 1 even the program's float32 path reads 0.23-0.53 at
the worst leaf against the float32 reference, two float32 codes; with
each block's last gamma at 0.1 it reads 0.02-0.05 (chip call 6 of PR 26).
Program or reference?  For both initialisations and every seed this
reads, against the float32 reference:

* ``program``: the cell as configured (bf16 policy, the conv+BN graph);
* ``program_f32``: the same graph under the float32 policy;
* ``reference_bf16``: the reference itself with the configuration's
  precision: weights at use, every layer's output and the cotangents
  that come back through them held in bfloat16.

If the float32 program sides with the reference and the bfloat16
reference reads as the program does, the cause is the precision the
configuration states, and neither side is at fault: so it came out for
this graph (PERF.md section 6, PR 26).  One JSON object per
initialisation and seed goes to standard output and to
``chiprun_out/witness.resnet50.jsonl``.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

import readings

WATCH = ("stem.beta", "stem.gamma", "res2_0.a.w")
F32 = {"params": "float32", "compute": "float32", "activations": "float32"}
VARIANTS = {
    "program": {},
    "program_f32": {"precision": F32},
}


def _read(got: dict, wanted: dict) -> dict:
    import compare
    numbers, where = compare.gaps(got, wanted)
    grads = compare.leaf_gaps(got["grad_norms"], wanted["grad_norms"], WATCH)
    return {"grad_gap": numbers["grad_gap"], "leaf": where["grad_gap"],
            "grad_gap_median": numbers["grad_gap_median"],
            "delta_gap": numbers["delta_gap"],
            "delta_gap_median": numbers["delta_gap_median"],
            "loss1_gap": numbers["loss1_gap"],
            "grad_gap_of": {leaf: grads[leaf] for leaf in WATCH}}


def one_seed(config: dict, mix: dict, seed: int, variants=VARIANTS) -> dict:
    import harness
    import traffic
    from deeplearning4j_tpu.obs import costmodel
    weight_seed, data_seed, model_seed = harness._seeds(seed, 3)
    reference = harness.load_module("reference", config["reference"])
    weights = reference.init_weights(config, weight_seed)
    arrays = traffic.make_batches(mix, config["model"],
                                  data_seed)[:int(mix["first_steps"])]
    wanted = reference.first_steps(config, mix, weights, arrays,
                                   seed=model_seed)
    out = {"seed": seed, "last_gamma": config["init"]["last_gamma"],
           "reference_bf16": _read(reference.first_steps(
               config, mix, weights, arrays, seed=model_seed,
               precision="bf16"), wanted)}
    for name, change in variants.items():
        varied = {**config, **copy.deepcopy(change)}
        entry = harness.load_module("entries", varied["entry"]).make(
            varied, mix)
        try:
            entry.build(weights, model_seed)
            out[name] = _read(entry.first_steps(
                [entry.to_batch(a) for a in arrays]), wanted)
        except Exception as e:             # a variant that does not fit
            out[name] = {"error": f"{type(e).__name__}: {e}"[:400]}
        entry.free()
        costmodel.drain(timeout_s=300)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_300_000_003)
    args = ap.parse_args(argv)
    import harness
    import traffic
    from deeplearning4j_tpu import config as program_config
    config = harness.load_json("configs", "resnet50_unfused.json")
    mix = traffic.load_mix("train_b128")
    harness.require_chips(1)
    program_config.place_compile_cache()
    out_dir = os.path.join(readings.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "witness.resnet50.jsonl"), "w") as f:
        for last_gamma in (1.0, config["init"]["last_gamma"]):
            varied = {**config,
                      "init": {**config["init"], "last_gamma": last_gamma}}
            for i in range(args.seeds):
                row = one_seed(varied, mix, args.first_seed + 7919 * i)
                line = json.dumps(row)
                print(line, flush=True)
                f.write(line + "\n")
                f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
