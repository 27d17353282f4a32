# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Paper-figure reproductions, as CSV rows.

  fig7  -- acceleration vs Hrz (paper Fig. 7): cycle-accurate reproduction
  fig8  -- memory/utilization vs Hrz (paper Fig. 8)
  fig9  -- timing/energy proxies (paper Fig. 9, modeled; see module doc)
  roofline -- dry-run-derived three-term roofline per (arch x shape)

These are cycle-model and analytic figures, not speed numbers: chip
numbers come only from the benchmark in ``bench/`` (``BENCHMARK.json``).

Run all: ``PYTHONPATH=src python -m benchmarks.run``
Subset : ``PYTHONPATH=src python -m benchmarks.run --only fig7,fig8``
Quick  : ``PYTHONPATH=src python -m benchmarks.run --quick``
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma list of suites")
    ap.add_argument("--quick", action="store_true", help="small sizes")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import fig7_acceleration, fig8_memory, fig9_resources, roofline

    suites = {
        "fig7": (
            (lambda: fig7_acceleration.run(sizes=(16384,)))
            if args.quick
            else fig7_acceleration.run
        ),
        "fig8": fig8_memory.run,
        "fig9": fig9_resources.run,
        "roofline": roofline.run,
    }
    only = args.only.split(",") if args.only else list(suites)

    print("name,us_per_call,derived")
    failures = 0
    for name in only:
        try:
            for row in suites[name]():
                print(row.csv())
        except Exception as e:
            failures += 1
            print(f"{name},0.0,ERROR={type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} suite(s) failed")


if __name__ == "__main__":
    main()
