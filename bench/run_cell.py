#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run_cell.py --workload hrz-6m.ycsb-c-sat --seed 7 \
        --seconds 10 --trace 0

Builds the cell's server, warms it up (set-up), drives the window of
``--seconds``, compares every answer with the plain reference and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones, read from a profiler trace of the
window), ``device`` and, last, ``checks``: each number compared with its
limit.  Those also end standard error.

Exits 2 without a result line where JAX finds no TPU, or fewer chips than
the cell asks for.  JAX's persistent compilation cache lives in the
checkout (``launch/compile_cache``), so only a checkout's first run of a
cell compiles.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name, <config>.<mix>")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"run_cell: {msg}", file=sys.stderr)
    return 2


def result_line(run, checked, specs, devices, trace: bool) -> dict:
    metrics = {}
    for spec in specs:
        value = harness.reader(spec["name"])(run)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    out = {
        "correct": checked.correct,
        "attempted": checked.attempted,
        "failed": checked.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = run.trace["breakdown"]
    out["checks"] = checked.checks
    return out


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.load_benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        return fail(f"no cell {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    config_name, mix_name = harness.split_cell(args.workload)
    config = harness.load_json("configs", config_name)
    mix = harness.load_json("mixes", mix_name)
    if config["chips"] != cell["chips"]:
        return fail(f"the cell asks for {cell['chips']} chips, its configuration for {config['chips']}")

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell["chips"]:
        return fail(f"the cell asks for {cell['chips']} chips, JAX sees {len(devices)}")
    devices = devices[: cell["chips"]]

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    trace = bool(args.trace)
    run, checked = harness.execute(config, mix, args.seed, args.seconds, trace, T_START, devices)
    out = result_line(run, checked, harness.metric_specs(bench, args.workload, trace),
                      devices, trace)
    for name, c in checked.checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
