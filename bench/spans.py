#!/usr/bin/env python3
"""Break one cell's window down by the server's own spans, on the chip.

    python bench/spans.py --workload hrz-6m.ycsb-c-sat --seed 7 --seconds 50

Runs the cell as ``run_cell.py`` does and prints as the last line of
standard output one JSON object: ``run_cell.py``'s result line with the
end-to-end and the per-layer metrics side by side, the metrics that read
the server's phase counters (``SPAN_METRICS``), ``phase_ms``: every
span's host milliseconds per engine call beside ``busy_ms``, the server's
engine time per call, and ``drain_frontend_ms``: the drain's share of
``frontend_ms`` beside ``drain_spans_ms``, the spans that should account
for it.  With ``--trace 1`` the profiler and ``analysis.runtime.gc_watch``
are on around the whole run (so ``ops_per_s`` is a traced figure), and
the line adds the device metrics, ``gc_ms`` and the ten longest idle gaps
named by ``name_gap``.

``run_cell.py`` cannot report these metrics yet: its ``Run`` has no place
for the phase counters.  Exits 2 without a result line where JAX finds no
TPU.
"""

from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, run_cell  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

PROGRAM = ("bst.", "gc.")  # the server's spans and the collector's pauses
SPAN_METRICS = {
    "queue_wait_ms": "ms", "pack_ms": "ms", "unpack_ms": "ms", "gc_ms": "ms",
    "dispatch_ms": "ms", "sync_ms": "ms", "fetch_ms": "ms",
}


@dataclasses.dataclass
class SpanRun(harness.Run):
    """A ``harness.Run`` with the server's phase counters of the window."""

    phase_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    drains: int = 0
    queue_wait_s: float = 0.0
    gc_s: Optional[float] = None  # collector pauses inside a traced window


def name_gap(gap: trace_lib.Interval, host: Sequence[Tuple[str, float, float]]) -> str:
    """``trace.host_activity`` with the program's spans in the name:
    ``<harness phase>:<innermost bst.* or gc.* span>[:<innermost runtime
    event>]``, the span alone where no harness phase covers the gap.
    ``compile`` still comes first, and a gap with no program span around
    its middle keeps the name ``host_activity`` gives it."""
    plain = [h for h in host if not h[0].startswith(PROGRAM)]
    name = trace_lib.host_activity(gap, plain)
    mid = (gap[0] + gap[1]) / 2
    around = [(e - s, n) for n, s, e in host if s <= mid <= e and n != "window"]
    spans = [a for a in around if a[1].startswith(PROGRAM)]
    if name == "compile" or not spans:
        return name
    phases = [a for a in around if a[1] in trace_lib.PHASES]
    runtime = [a for a in around if a[1] not in trace_lib.PHASES and not a[1].startswith(PROGRAM)]
    parts = [min(phases)[1]] if phases else []
    parts.append(min(spans)[1])
    if runtime:
        parts.append(min(runtime)[1])
    return ":".join(parts)


def window_time(host, prefix: str, window: trace_lib.Interval) -> float:
    """Seconds of the window covered by host events named ``prefix...``."""
    covered = trace_lib.union([(s, e) for n, s, e in host if n.startswith(prefix)], *window)
    return sum(e - s for s, e in covered) / 1e9


def measure(config: dict, mix: dict, seed: int, seconds: float, t_start: float, devices,
            trace_dir: Optional[str]):
    """One run of the cell, under the profiler where ``trace_dir`` is
    given.  Returns the ``SpanRun``, with the idle gaps of a traced run
    named by ``name_gap``, and the ``Checked`` comparison."""
    from repro.analysis import runtime

    servers = []

    def make_server(config, devices):
        servers.append(harness.build_server(config, devices))
        return servers[-1]

    def execute():
        return harness.execute(config, mix, seed, seconds, False, t_start, devices, make_server)

    if trace_dir is None:
        run, checked = execute()
    else:
        with runtime.gc_watch():
            trace_lib.start(trace_dir)
            try:
                run, checked = execute()
            finally:
                trace_lib.stop()
    s = servers[0].stats
    span_run = SpanRun(**vars(run), phase_s=dict(s.phase_s), drains=s.drains,
                       queue_wait_s=s.queue_wait_s)
    if trace_dir is None:
        return span_run, checked
    devs, host = trace_lib.load(trace_dir)
    span_run.trace = trace_lib.reduce(devs, host)
    window = next((s, e) for n, s, e in host if n == "window")
    span_run.gc_s = window_time(host, "gc.", window)
    first = devs[sorted(devs)[0]]
    busy = trace_lib.union([(a, b) for _, a, b in first], *window)
    longest = sorted(trace_lib.gaps(busy, *window), key=lambda g: g[0] - g[1])[:10]
    span_run.trace["breakdown"]["idle_gaps"] = [
        [name_gap(g, host), (g[1] - g[0]) / 1e9] for g in longest
    ]
    return span_run, checked


def main(argv=None) -> int:
    args = run_cell.parse(argv)
    bench = harness.load_benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        return run_cell.fail(f"no cell {args.workload!r} in BENCHMARK.json")
    config_name, mix_name = harness.split_cell(args.workload)
    config = harness.load_json("configs", config_name)
    mix = harness.load_json("mixes", mix_name)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return run_cell.fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < config["chips"]:
        return run_cell.fail(f"the cell asks for {config['chips']} chips, JAX sees {len(devices)}")
    devices = devices[: config["chips"]]

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    trace = bool(args.trace)
    trace_dir = tempfile.mkdtemp(prefix="bench_spans_") if trace else None
    try:
        run, checked = measure(config, mix, args.seed, args.seconds, T_START, devices, trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    specs = harness.metric_specs(bench, args.workload, False)
    specs += harness.metric_specs(bench, args.workload, True)
    specs += [{"name": n, "unit": u} for n, u in SPAN_METRICS.items()]
    out = run_cell.result_line(run, checked, specs, devices, trace)
    per_call = 1e3 / max(run.engine_calls, 1)
    out["phase_ms"] = {n: t * per_call for n, t in sorted(run.phase_s.items())}
    out["busy_ms"] = run.busy_s * per_call
    out["drain_frontend_ms"] = (run.drain_s - run.busy_s) * per_call
    out["drain_spans_ms"] = sum(run.phase_s.get(n, 0.0) for n in
                                ("drain", "pack", "unpack", "fetch")) * per_call
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
