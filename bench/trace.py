"""From a profiler trace of the window to device busy time, kernel time and
the idle gaps, each gap named by what the host was doing in it.

The trace is JAX's own (``jax.profiler``), read back with
``jax.profiler.ProfileData``.  Device planes are ``/device:TPU:<n>``; their
``XLA Ops`` line holds one event per operation run.  Host spans are the
harness's ``TraceAnnotation`` phases (``PHASES``) on the ``/host:CPU``
plane, on the same clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

KERNEL = "bst_forest_search"
PHASES = ("window", "generate", "submit", "drain", "check")
OPS_LINE = "XLA Ops"
Interval = Tuple[float, float]  # nanoseconds


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no per-call Python events: they would swamp the host
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load(log_dir: str):
    """(device events per chip, host spans) of the trace under ``log_dir``.

    Device events are ``(name, start_ns, end_ns)`` of each operation;
    host spans are the same for every host event (phases and the runtime's
    own, compilation among them)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, found {len(files)}")
    data = ProfileData.from_file(files[0])
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
    return devices, host


def union(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, sorted."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _op_family(name: str) -> str:
    """The operation's family: a device event is named by its HLO text,
    ``%fusion.12 = (...) fusion(...)``; ``fusion.12`` and ``fusion.7`` are
    one family."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def host_activity(gap: Interval, host: Sequence[Tuple[str, float, float]]) -> str:
    """What the host was doing in ``gap``: ``compile`` where compilation
    covers half of it or more; else the innermost harness phase around its
    middle, joined by ``:`` to the innermost runtime event there (say
    ``drain:CommonPjRtBuffer::ToLiteral``, a device-to-host copy); else
    ``other``."""
    g0, g1 = gap
    compiling = union([(s, e) for n, s, e in host if "ompil" in n], g0, g1)
    if sum(e - s for s, e in compiling) * 2 >= g1 - g0:
        return "compile"
    mid = (g0 + g1) / 2
    around = [(e - s, n) for n, s, e in host if s <= mid <= e and n != "window"]
    phases = [a for a in around if a[1] in PHASES]
    if not phases:
        return "other"
    runtime = [a for a in around if a[1] not in PHASES]
    return min(phases)[1] + (":" + min(runtime)[1] if runtime else "")


def reduce(
    devices: Dict[str, List[Tuple[str, float, float]]],
    host: Sequence[Tuple[str, float, float]],
    window: Optional[Interval] = None,
    top: int = 10,
) -> dict:
    """Busy, kernel and idle figures of the window, averaged over chips.

    ``window`` defaults to the host's ``window`` phase span.  Returns
    seconds: ``window_s``, ``busy_s``, ``kernel_s`` and ``kernel_calls``
    (per chip, averaged), and the ``breakdown`` of the first chip:
    ``device_ops`` (the operation families that took most time) and
    ``idle_gaps`` (the longest gaps, named by ``host_activity``).
    """
    if window is None:
        spans = [(s, e) for n, s, e in host if n == "window"]
        if len(spans) != 1:
            raise RuntimeError(f"expected one 'window' span in the trace, found {len(spans)}")
        window = spans[0]
    lo, hi = window
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    busy_s = kernel_s = calls = 0.0
    for events in devices.values():
        busy_s += sum(e - s for s, e in union([(s, e) for _, s, e in events], lo, hi)) / 1e9
        kernel = [(s, e) for n, s, e in events
                  if _op_family(n) == KERNEL and s >= lo and e <= hi]
        kernel_s += sum(e - s for s, e in kernel) / 1e9
        calls += len(kernel)
    n_dev = len(devices)
    first = devices[sorted(devices)[0]]
    families: Dict[str, float] = {}
    for n, s, e in first:
        if s >= lo and e <= hi:
            families[_op_family(n)] = families.get(_op_family(n), 0.0) + (e - s) / 1e9
    busy = union([(s, e) for _, s, e in first], lo, hi)
    longest = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s / n_dev,
        "kernel_s": kernel_s / n_dev,
        "kernel_calls": calls / n_dev,
        "breakdown": {
            "device_ops": sorted(families.items(), key=lambda kv: -kv[1])[:top],
            "idle_gaps": [[host_activity(g, host), (g[1] - g[0]) / 1e9] for g in longest],
        },
    }
