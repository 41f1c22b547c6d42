"""Read the numbers that ``correct`` compares, over many seeds, beside the
control's: the readings the limits of the cell's queue kind (for 1v1,
``benchmark/reference.py``) are set from.

    python3 -m benchmark.readings --workload elo_1v1.search \\
        --seeds 101,102,103 --seconds 10 [--out rows.jsonl]

Runs the cell's window at its own load and size once per seed, in one
process (so set-up is paid once), and prints one JSON row per seed: the
program's numbers and the control's (for 1v1 ``control_skipped``, the
same reading of the bfloat16 reference's picks for the same searchers).
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from benchmark import cells
from benchmark.run import chip_jax, run_cell


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    cell = cells.find_cell(args.workload)
    if chip_jax(cell.chips) is None:
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        run, _ = run_cell(cell, seed, args.seconds, False,
                          t_process=time.perf_counter())
        t = time.perf_counter()
        numbers = cell.kind.compare(run.data, control=True)
        row = {"workload": cell.name, "seed": seed,
               "reference_s": time.perf_counter() - t,
               "correct": not cell.kind.failures(numbers), **numbers,
               "log": run.log}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
