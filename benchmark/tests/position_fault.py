"""The fault by position, for a cell whose batch is one row: half of the
sequence's targets left out, the mean taken over the rest.  On the chip,
at the cell's own size:

    python benchmark/tests/position_fault.py --workload joyai_llm_flash.clm_s8192_b1 --seeds 6

``readings.py``'s partial fault leaves out the rows from ``len // 2`` on,
which at batch 1 is the whole batch and reads 1 on every number.  Here
the reference, put in the program's place as ``readings.py`` puts it,
scores positions ``0 .. S // 2 - 1`` only, in every stream, against the
sound float32 reference.  Each seed's numbers go through
``compare.decide`` against the committed ``limits/<cell>.json``: the
verdict a run of the cell would give.  One JSON object per seed, also to
``chiprun_out/position_fault.<cell>.jsonl``.  ``test_cells_pr38.py`` runs
:func:`one_seed` on the tiny twin.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for _path in (HERE, BENCH, ROOT):
    if _path not in sys.path:
        sys.path.insert(0, _path)


def one_seed(config: dict, mix: dict, seed: int, *, limits: dict) -> dict:
    import numpy as np

    import compare
    import harness
    import readings
    import traffic
    weight_seed, data_seed, model_seed = harness._seeds(seed, 3)
    reference = harness.load_module("reference", config["reference"])
    weights = reference.init_weights(config, weight_seed)
    arrays = traffic.make_batches(
        mix, config["model"], data_seed)[:int(mix["first_steps"])]
    keep = np.ones((int(mix["seq"]),), np.float32)
    keep[len(keep) // 2:] = 0.0

    def follow(**how):
        return reference.first_steps(config, mix, weights, arrays,
                                     seed=model_seed, **how)

    wanted, faulty = follow(), follow(position_weights=keep)
    numbers, where = compare.gaps(faulty, wanted)
    return {"seed": seed,
            "losses": {"fault": faulty["losses"],
                       "reference": wanted["losses"]},
            "fault_half_positions": numbers, "leaf": where,
            "verdict": readings.verdict(numbers, limits)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_381_600_027)
    args = ap.parse_args(argv)
    import harness
    import readings
    from deeplearning4j_tpu import config as program_config
    cell, config, mix, limits = readings.load_cell(args.workload)
    harness.require_chips(int(cell["chips"]))
    program_config.place_compile_cache()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir,
                           f"position_fault.{cell['name']}.jsonl"), "w") as f:
        for i in range(args.seeds):
            line = json.dumps(one_seed(config, mix,
                                       args.first_seed + 7919 * i,
                                       limits=limits))
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
