# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness entrypoint.

  fig7  -- acceleration vs Hrz (paper Fig. 7): cycle-accurate reproduction
  fig8  -- memory/utilization vs Hrz (paper Fig. 8)
  fig9  -- timing/energy proxies (paper Fig. 9, modeled; see module doc)
  engine-- real JAX engine throughput (keys/s) for all strategies x query ops
  kernel-- Pallas kernels (interpret mode off-TPU) vs jnp oracles
  moe   -- MoE dispatch drop rates: direct vs queue mapping
  roofline -- dry-run-derived three-term roofline per (arch x shape)

Run all: ``PYTHONPATH=src python -m benchmarks.run``
Subset : ``PYTHONPATH=src python -m benchmarks.run --only fig7,engine``
Quick  : ``PYTHONPATH=src python -m benchmarks.run --quick``
JSON   : add ``--json BENCH_4.json`` to also dump the rows as a schema-
         checked machine-readable artifact (what CI uploads per run;
         scripts/check_bench.py layers the hyb kernel-vs-driver
         regression gate on top of the same file).
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

# The machine-readable artifact contract (BENCH_*.json).  scripts/
# check_bench.py re-validates the same schema on the consumer side and
# layers the hyb kernel-vs-driver regression gate on top.
SCHEMA = "bench-rows/v1"


def validate_rows(records) -> None:
    """Schema-check the JSON rows before they are written anywhere.

    Every record is exactly ``{suite, name, us_per_call, derived}`` with a
    non-negative timing and a ``key=value`` ``;``-separated derived payload
    -- the shape every downstream consumer (CI gates, dashboards) parses.
    """
    if not isinstance(records, list) or not records:
        raise SystemExit("bench JSON: no rows to write")
    for r in records:
        if set(r) != {"suite", "name", "us_per_call", "derived"}:
            raise SystemExit(f"bench JSON: bad record keys {sorted(r)}")
        if not (isinstance(r["suite"], str) and r["suite"]):
            raise SystemExit(f"bench JSON: bad suite in {r}")
        if not (isinstance(r["name"], str) and r["name"]):
            raise SystemExit(f"bench JSON: bad name in {r}")
        if not isinstance(r["us_per_call"], (int, float)) or r["us_per_call"] < 0:
            raise SystemExit(f"bench JSON: bad us_per_call in {r}")
        if not isinstance(r["derived"], str):
            raise SystemExit(f"bench JSON: bad derived in {r}")
        for part in filter(None, r["derived"].split(";")):
            if "=" not in part:
                raise SystemExit(
                    f"bench JSON: derived part {part!r} is not key=value ({r})"
                )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma list of suites")
    ap.add_argument("--quick", action="store_true", help="small sizes (CI)")
    ap.add_argument("--json", default=None, help="also write rows to this JSON file")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        engine_throughput,
        fig7_acceleration,
        fig8_memory,
        fig9_resources,
        kernel_bench,
        moe_dispatch_bench,
        roofline,
    )

    suites = {
        "fig7": (
            (lambda: fig7_acceleration.run(sizes=(16384,)))
            if args.quick
            else fig7_acceleration.run
        ),
        "fig8": fig8_memory.run,
        "fig9": fig9_resources.run,
        "engine": (
            (lambda: engine_throughput.run(n_keys=(1 << 12) - 1, batch=8192, quick=True))
            if args.quick
            else engine_throughput.run
        ),
        "kernel": kernel_bench.run,
        "moe": moe_dispatch_bench.run,
        "roofline": roofline.run,
    }
    only = args.only.split(",") if args.only else list(suites)

    print("name,us_per_call,derived")
    failures = 0
    records = []
    for name in only:
        try:
            for row in suites[name]():
                print(row.csv())
                records.append(
                    {
                        "suite": name,
                        "name": row.name,
                        "us_per_call": row.us_per_call,
                        "derived": row.derived,
                    }
                )
        except Exception as e:
            failures += 1
            print(f"{name},0.0,ERROR={type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if args.json:
        if records:
            validate_rows(records)
            with open(args.json, "w") as f:
                json.dump(
                    {"schema": SCHEMA, "quick": args.quick, "rows": records},
                    f,
                    indent=1,
                )
            print(f"wrote {len(records)} rows to {args.json}", file=sys.stderr)
        elif not failures:
            raise SystemExit("bench JSON: no rows produced")
        # with failures and zero rows, fall through: the suite-failure exit
        # below is the real error, and no stale/empty artifact is written
    if failures:
        raise SystemExit(f"{failures} suite(s) failed")


if __name__ == "__main__":
    main()
