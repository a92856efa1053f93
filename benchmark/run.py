"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  It finds the cell in ``BENCHMARK.json``, loads
the cell's configuration and traffic mix by name, and hands them to
``harness.run_cell``.  It needs a TPU with as many chips as the cell asks
for and exits non-zero, printing no result, without one: there is no CPU
fallback.  Earlier lines may say anything; the last line of standard
output is the result object and holds nothing else.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()        # set-up counts from the process's start

import argparse                      # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "deeplearning4j_tpu")):
        sys.exit(f"benchmark: no deeplearning4j_tpu package beside {HERE}: "
                 f"nothing to measure")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cells = {c["name"]: c for c in benchmark["workloads"]}
    if args.workload not in cells:
        sys.exit(f"benchmark: no workload {args.workload!r} in "
                 f"BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[args.workload]
    for path in (HERE, ROOT):            # the harness's modules, the program
        if path not in sys.path:
            sys.path.insert(0, path)
    import harness
    devices = harness.require_chips(int(cell["chips"]))
    print(f"[benchmark] {cell['name']} seed {args.seed} on "
          f"{len(devices)} x {devices[0].device_kind} "
          f"({devices[0].platform})", flush=True)
    configs = {c["name"]: c for c in benchmark["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    import compare
    import traffic
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), config=config,
        mix=traffic.load_mix(cell["traffic"]),
        limits=compare.load_limits(cell["name"]),
        metrics=harness.cell_metrics(benchmark, cell["name"],
                                     bool(args.trace)),
        devices=devices[:int(cell["chips"])], started=STARTED)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
