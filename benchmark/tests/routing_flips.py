"""How many tokens does rounding route otherwise?  Top-k selection is
discontinuous: a token whose k-th and (k+1)-th scores lie closer than
the rounding of the activations under them goes to another expert.  On
the chip, at the cell's own size:

    python benchmark/tests/routing_flips.py --workload joyai_llm_flash.clm_s8192_b1 --seeds 3

For every seed the reference's own forward pass gives each routed
block's chosen experts in float32 and again with every matmul operand
and layer output rounded to bfloat16 (the program's precision; the
router's logits float32 on the rounded activations, as the program's)
and to float8 (the control's).  Printed: by block the share of tokens
whose chosen set differs, and the share of (token, expert) pairs that
differ.  The program itself is not asked: its routing is inside its
jitted step; the bfloat16 reference stands for it.  One JSON object per
seed, also to ``chiprun_out/routing_flips.<cell>.jsonl``.
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


def one_seed(config: dict, mix: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    import harness
    import traffic
    weight_seed, data_seed, _ = harness._seeds(seed, 3)
    reference = harness.load_module("reference", config["reference"])
    weights = reference.init_weights(config, weight_seed)
    tokens = jnp.asarray(traffic.make_batches(
        mix, config["model"], data_seed)[0]["tokens"])
    chosen = {precision: jax.jit(
        lambda w, t, p=precision: reference.routing(
            w, t, config=config, precision=p))(weights, tokens)
        for precision in ("f32", "bf16", "fp8")}
    out = {"seed": seed}
    for precision in ("bf16", "fp8"):
        for block, want in chosen["f32"].items():
            got = chosen[precision][block]
            same = (got[..., :, None] == want[..., None, :]).any(-1)
            out[f"{precision}.{block}"] = {
                "tokens_routed_otherwise": float(1.0 - same.all(-1).mean()),
                "pairs_routed_otherwise": float(1.0 - same.mean())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_381_300_021)
    args = ap.parse_args(argv)
    import harness
    import readings
    from deeplearning4j_tpu import config as program_config
    cell, config, mix, _ = readings.load_cell(args.workload)
    harness.require_chips(int(cell["chips"]))
    program_config.place_compile_cache()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir,
                           f"routing_flips.{cell['name']}.jsonl"), "w") as f:
        for i in range(args.seeds):
            line = json.dumps(one_seed(config, mix,
                                       args.first_seed + 7919 * i))
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
