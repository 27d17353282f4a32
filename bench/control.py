#!/usr/bin/env python3
"""The control run: a cell's traffic at its own size, with a deliberately
broken reference in the program's place, to show that the comparison
that decides ``correct`` fails it.

    python bench/control.py --workload hrz-6m.ycsb-c-sat \
        --seeds 11,12,13 --seconds 5

The control (``reference.CONTROLS``) is ``int16_keys``: keys held at 16
bits, the precision below the configuration's int32 keys.  Prints one JSON
line per seed with the numbers compared; each has to read as not correct.
Exits 2 where JAX finds no TPU.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, reference, traffic  # noqa: E402


def reference_server(control):
    """A ``make_server`` that puts the reference, with ``control``, in the
    program's place."""
    def make(config, devices):
        n = config["records"]
        return reference.ReferenceServer(traffic.record_keys(n), traffic.record_values(n), control)
    return make


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=lambda s: [int(x) for x in s.split(",")], required=True)
    args = ap.parse_args(argv)
    config_name, mix_name = harness.split_cell(args.workload)
    config = harness.load_json("configs", config_name)
    mix = harness.load_json("mixes", mix_name)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"control: JAX found no TPU (platform {devices[0].platform!r})", file=sys.stderr)
        return 2
    for name in reference.CONTROLS:
        for seed in args.seeds:
            _, checked = harness.execute(
                config, mix, seed, args.seconds, False, time.perf_counter(),
                devices[: config["chips"]], make_server=reference_server(name),
            )
            print(json.dumps({"control": name, "seed": seed, "correct": checked.correct,
                              "attempted": checked.attempted, "checks": checked.checks}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
