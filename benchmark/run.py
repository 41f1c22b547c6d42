"""Run one cell of the benchmark on the chip and print its result line.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Each run is one process: it loads, restores the cell's waiting pool, warms
up, measures for ``--seconds``, drains, checks every answer against the
plain reference of the configuration's queue kind, and prints one JSON object as the last line of standard
output. Progress goes to standard error; its last lines are the numbers
compared, each beside its limit. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the profiler records the window's last seconds and the
metrics are the cell's per-layer metrics, read by the files under
``benchmark/metrics/``.
"""

from __future__ import annotations

import time

#: The process's start, as near as the entry can take it.
T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from benchmark import cells  # noqa: E402

#: JAX's persistent compilation cache: one fixed directory inside the
#: checkout, so only a cell's first run there compiles.
CACHE_DIR = os.path.join(cells.ROOT, ".jax_cache")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def read_metric(name: str, run) -> float | None:
    """The value of metric ``name`` by its reader ``metrics/<name>.py``;
    None where the reader found nothing to read."""
    path = os.path.join(cells.HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


def result_line(cell, run, numbers: dict, traced: bool, device: dict,
                units: dict) -> dict:
    """The contract's result object; the numbers compared come last."""
    names = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for name in names:
        value = read_metric(name, run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    out = {
        "correct": not cell.kind.failures(numbers),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": dict(device, memory_peak_bytes=run.memory_peak_bytes),
    }
    t = run.readings.get("trace")
    if traced and t is not None:
        out["device"]["busy_s"] = t["busy_s"]
        out["device"]["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = cell.kind.report(numbers)
    return out


def run_cell(cell, seed: int, seconds: float, traced: bool,
             t_process: float = T_PROCESS):
    """One run of ``cell`` and its kind's reference numbers for it (no
    device check here: the caller decides where it may run)."""
    from benchmark.serve import drive

    run = asyncio.run(drive(cell, seed, seconds, t_process=t_process,
                            traced=traced, say=say))
    for k, v in run.log.items():
        say(f"{k}={v}")
    if traced:
        for line in run.readings.get("trace_planes", []):
            say(f"trace plane {line}")
    numbers = cell.kind.compare(run.data)
    return run, numbers


def chip_jax(chips: int):
    """JAX on the chip, with the compile cache in the checkout; None (and
    a line on standard error) when JAX finds no TPU or fewer than
    ``chips`` of them."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        say(f"needs a TPU; JAX found {devices[0].platform!r} "
            f"({devices[0].device_kind})")
        return None
    if len(devices) < chips:
        say(f"needs {chips} chips; JAX sees {len(devices)}")
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    spec = cells.benchmark_spec()
    cell = cells.find_cell(args.workload, spec=spec)
    jax = chip_jax(cell.chips)
    if jax is None:
        return 2
    try:
        import matchmaking_tpu  # noqa: F401
    except ImportError as e:
        say(f"the system under test is not in this checkout ({e})")
        return 2
    devices = jax.devices()
    say(f"{cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={devices[0].device_kind} "
        f"x{len(devices)} jax={jax.__version__}")

    run, numbers = run_cell(cell, args.seed, args.seconds,
                            bool(args.trace))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    line = result_line(cell, run, numbers, bool(args.trace), device,
                       cells.metric_units(spec))
    for name, (value, limit) in line["checks"].items():
        say(f"check {name}={value} limit={limit}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
