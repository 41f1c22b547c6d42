#!/usr/bin/env python
"""Pretty-print a scenario-matrix artifact (ISSUE 13).

Reads the result of ``loadgen.scenario_matrix`` saved as JSON (raw,
JSON-lines, or an artifact wrapping it under ``"parsed"``) and renders the
capacity-planning story:

- the matrix summary table — one row per cell: offered/matched/shed/
  expired, SLO attainment, admitted p99, autotuner move count;
- per cell (``--cell NAME`` or ``--full``): the telemetry-ring trajectory
  as text sparklines (stage p99, batch fill, pool size, idle fraction),
  the top attribution categories, per-tier/per-cohort splits, and the
  autotuner's knob-decision ladder.

Usage:
    python scripts/scenario_report.py /tmp/BENCH_scenarios.json
    python scripts/scenario_report.py artifact.json --cell flash-crowd
    python scripts/trace_dump.py --scenario --bench-json artifact.json
"""

from __future__ import annotations

import argparse
import json
import sys

_SPARK = "▁▂▃▄▅▆▇█"


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
        for line in text.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                doc = json.loads(line)
            except json.JSONDecodeError:
                continue
        if doc is None:
            raise SystemExit(f"{path}: no JSON object found")
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    return doc


def _spark(values: list[float]) -> str:
    vals = [v for v in values if v is not None]
    if not vals:
        return "(no data)"
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    return "".join(
        _SPARK[int((v - lo) / span * (len(_SPARK) - 1))] for v in vals)


def _series(cell: dict, prefix: str) -> list[float]:
    """One telemetry series out of the cell's trajectory tail (the first
    key matching ``prefix[`` — cells are single-queue)."""
    out: list[float] = []
    key = None
    for snap in cell.get("telemetry") or []:
        values = snap.get("values") or {}
        if key is None:
            for k in values:
                if k.startswith(prefix + "["):
                    key = k
                    break
        if key is not None and key in values:
            out.append(values[key])
    return out


def render_matrix(doc: dict, out=sys.stdout) -> None:
    cells = doc.get("scenario_matrix") or []
    if not cells:
        print("no scenario_matrix rows in this artifact "
              "(run loadgen.scenario_matrix)", file=out)
        return
    print(f"scenario matrix (seed {doc.get('scenario_seed')}, worst-cell "
          f"attainment {doc.get('value')}):", file=out)
    print(f"  {'scenario':<18} {'offered':>8} {'matched':>8} {'shed':>6} "
          f"{'expired':>7} {'slo':>7} {'p99ms':>9} {'tuner':>6}", file=out)
    for c in cells:
        if c.get("abort_reason"):
            print(f"  {c.get('scenario', '?'):<18} ABORTED "
                  f"({c['abort_reason']}): {c.get('abort_detail', '')}",
                  file=out)
            continue
        moves = (c.get("autotune") or {}).get("moves")
        print(f"  {c.get('scenario', '?'):<18} {c.get('offered', 0):>8} "
              f"{c.get('matched', 0):>8} {c.get('shed', 0):>6} "
              f"{c.get('expired', 0):>7} {c.get('slo_attainment')!s:>7} "
              f"{c.get('admitted_p99_ms')!s:>9} {moves!s:>6}", file=out)


def render_cell(cell: dict, out=sys.stdout) -> None:
    name = cell.get("scenario", "?")
    if cell.get("abort_reason"):
        print(f"{name}: ABORTED ({cell['abort_reason']}) "
              f"{cell.get('abort_detail', '')}", file=out)
        return
    print(f"cell {name} — {cell.get('duration_s')}s, "
          f"digest {str(cell.get('scenario_digest'))[:12]}…", file=out)
    for label, prefix in (("stage p99 ms", "stage_total_p99_ms"),
                          ("batch fill", "batch_fill"),
                          ("pool size", "pool_size"),
                          ("idle frac", "idle_frac")):
        series = _series(cell, prefix)
        if series:
            print(f"  {label:<14} {_spark(series)}  "
                  f"[{min(series):g} … {max(series):g}]", file=out)
    cats = sorted((cell.get("attribution") or {}).items(),
                  key=lambda kv: -(kv[1].get("share") or 0.0))[:6]
    if cats:
        print("  top attribution shares:", file=out)
        for cname, cat in cats:
            share = cat.get("share")
            print(f"    {cname:<22} {cat.get('kind', ''):<5} "
                  f"{share if share is not None else '-':>8}", file=out)
    for split in ("tiers", "cohorts"):
        rows = cell.get(split)
        if rows:
            print(f"  {split}:", file=out)
            for key, row in sorted(rows.items()):
                print(f"    {key:<14} "
                      + " ".join(f"{k}={v}" for k, v in row.items()
                                 if not isinstance(v, (dict, list))),
                      file=out)
    tune = cell.get("autotune")
    if tune:
        print(f"  autotune: {tune.get('moves')} move(s) over "
              f"{tune.get('ticks')} tick(s); knobs "
              f"{tune.get('knobs')}", file=out)
        for row in tune.get("trace") or []:
            seq, queue, knob, src, dst, reason, status = row[:7]
            print(f"    #{seq} {knob}: {src} -> {dst} [{status}] "
                  f"— {reason}", file=out)
    q = cell.get("quality")
    if q:
        print(f"  quality: {q}", file=out)


def render(doc: dict, cell_name: str = "", full: bool = False,
           out=sys.stdout) -> None:
    render_matrix(doc, out=out)
    cells = doc.get("scenario_matrix") or []
    if cell_name:
        cells = [c for c in cells if c.get("scenario") == cell_name]
        if not cells:
            raise SystemExit(f"no cell {cell_name!r} in this artifact")
    elif not full:
        return
    for cell in cells:
        print("", file=out)
        render_cell(cell, out=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("artifact", help="scenario-matrix JSON "
                                     "(loadgen.scenario_matrix's result)")
    ap.add_argument("--cell", default="",
                    help="render one cell's full story (trajectory "
                         "sparklines, attribution, autotune ladder)")
    ap.add_argument("--full", action="store_true",
                    help="render every cell's full story")
    ap.add_argument("--json", action="store_true",
                    help="re-emit the parsed matrix rows as JSON")
    args = ap.parse_args(argv)
    doc = _load(args.artifact)
    if args.json:
        print(json.dumps(doc.get("scenario_matrix", []), indent=1))
        return 0
    render(doc, cell_name=args.cell, full=args.full)
    return 0


if __name__ == "__main__":
    sys.exit(main())
