"""Find the highest rate a cell's mix sustains, by one sweep on the chip.

    python3 -m benchmark.sweep --workload elo_1v1.search \\
        --rates 2000,4000,8000 --seconds 5 --seed 11 [--out rows.jsonl]

Runs the cell's window once per offered rate, in one process, and prints
one JSON row per rate. A rate is sustained when at least 90% of the
window's requests were answered ``matched`` inside the window and the
backlog drained. The cells' rates are fixed in their traffic files from
such a sweep; the benchmark's own runs never search for one.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

from benchmark import cells
from benchmark.run import chip_jax, run_cell

#: Share of the window's requests that must be matched inside it.
SUSTAINED = 0.9


def row(rate: float, run, numbers: dict) -> dict:
    lat = run.match_ms
    ok = ~np.isnan(lat)
    in_window = ok & (run.due + np.where(ok, lat, 0.0) / 1e3 <= run.seconds)
    share = float(in_window.sum()) / max(1, run.attempted)
    ms = lat[ok]
    return {
        "offered_req_s": rate,
        "attempted": run.attempted,
        "matched_in_window_share": share,
        "matches_per_s": run.matches_in_window / run.seconds,
        "p50_ms": float(np.percentile(ms, 50)) if len(ms) else None,
        "p99_ms": float(np.percentile(ms, 99)) if len(ms) else None,
        "sustained": share >= SUSTAINED and run.log["drained"],
        "setup_s": run.setup_s,
        **{k: run.log[k] for k in ("drain_s", "windows", "pool_start",
                                   "pool_close", "compiles_in_window",
                                   "generator_lag_ms_mean",
                                   "generator_lag_ms_max")},
        "checks": numbers,
    }


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--seed", type=int, default=11)
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    cell = cells.find_cell(args.workload)
    if chip_jax(cell.chips) is None:
        return 2
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, traffic=dict(cell.traffic, rate=rate))
        run, numbers = run_cell(c, args.seed, args.seconds, False,
                                t_process=time.perf_counter())
        rows.append(row(rate, run, numbers))
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            for r in rows:
                f.write(json.dumps(dict(r, workload=cell.name)) + "\n")
    knee = max((r["offered_req_s"] for r in rows if r["sustained"]),
               default=None)
    print(json.dumps({"workload": cell.name, "knee_req_s": knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
