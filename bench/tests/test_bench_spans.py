"""The server's spans in a profiler trace: idle gaps named by them, the
metrics that read the server's phase counters, and the spans of a real
trace of a tiny cell on the CPU."""

import gc
import sys
import tempfile
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, spans, trace  # noqa: E402

MS = 1_000_000  # ns

HOST = [
    ("window", 0, 100 * MS),
    ("submit", 0, 10 * MS),
    ("drain", 10 * MS, 60 * MS),
    ("bst.drain", 11 * MS, 59 * MS),
    ("bst.pack", 12 * MS, 20 * MS),
    ("bst.sync", 22 * MS, 40 * MS),
    ("ReadSyncFlag", 25 * MS, 38 * MS),
    ("gc.gen2", 70 * MS, 79 * MS),  # between the harness's phases
    ("drain", 80 * MS, 100 * MS),
    ("bst.drain", 80 * MS, 100 * MS),
    ("bst.dispatch", 85 * MS, 98 * MS),
    ("XlaCompile", 86 * MS, 97 * MS),
    ("unrelated", 0, 100 * MS),
]
GAPS = {
    (12 * MS, 20 * MS): "drain:bst.pack:unrelated",
    (26 * MS, 36 * MS): "drain:bst.sync:ReadSyncFlag",
    (50 * MS, 58 * MS): "drain:bst.drain:unrelated",
    (71 * MS, 78 * MS): "gc.gen2:unrelated",
    (60 * MS, 65 * MS): "other",
    (86 * MS, 96 * MS): "compile",
}


@pytest.mark.parametrize("gap", sorted(GAPS))
def test_gaps_named_by_program_spans(gap):
    assert spans.name_gap(gap, HOST) == GAPS[gap]


@pytest.mark.parametrize("gap", sorted(GAPS))
def test_a_trace_without_program_spans_keeps_its_names(gap):
    plain = [h for h in HOST if not h[0].startswith(spans.PROGRAM)]
    assert spans.name_gap(gap, plain) == trace.host_activity(gap, plain)


def _run(**kw):
    return spans.SpanRun(config={"records": 3}, mix={}, seconds=1.0, **kw)


@pytest.mark.parametrize("name", sorted(spans.SPAN_METRICS))
def test_span_readers_need_engine_calls(name):
    read = harness.reader(name)
    assert read(_run()) is None
    # a run without the counters, as the harness's own Run is, reads nothing
    assert read(harness.Run(config={}, mix={}, seconds=1.0, engine_calls=4)) is None


def test_span_readers_per_engine_call():
    run = _run(engine_calls=4, drains=2, queue_wait_s=0.01, gc_s=0.002,
               phase_s={"pack": 0.004, "unpack": 0.008, "dispatch": 0.012,
                        "sync": 0.016, "fetch": 0.02, "drain": 1.0})
    got = {n: harness.reader(n)(run) for n in spans.SPAN_METRICS}
    want = {"queue_wait_ms": 5.0, "pack_ms": 1.0, "unpack_ms": 2.0, "gc_ms": 0.5,
            "dispatch_ms": 3.0, "sync_ms": 4.0, "fetch_ms": 5.0}
    assert got == pytest.approx(want)


def test_a_real_trace_holds_the_spans_inside_the_window():
    """A profiler trace of a tiny cell on the CPU: the server's spans are
    on ``/host:CPU``, each engine call's inside a drain span, each drain
    span inside the harness's ``drain`` phase, and the collector's pauses
    are there too."""
    from bench.tests.test_bench_harness import CPU, TINY, tiny_mix
    from repro.analysis import runtime

    with tempfile.TemporaryDirectory() as d:
        with runtime.gc_watch():
            trace.start(d)
            try:
                _, checked = harness.execute(TINY, tiny_mix(), 2**31 + 5, 0.5, False,
                                             time.perf_counter(), [CPU()])
                gc.collect()
            finally:
                trace.stop()
        _, host = trace.load(d)
    assert checked.correct
    (lo, hi), = [(s, e) for n, s, e in host if n == "window"]
    inside = [(n, s, e) for n, s, e in host if n.startswith("bst.") and lo <= s and e <= hi]
    assert {n for n, _, _ in inside} == {
        "bst.drain", "bst.pack", "bst.dispatch", "bst.sync", "bst.fetch", "bst.unpack"}

    def within(name, outer):
        return all(any(s0 <= s and e <= e0 for n0, s0, e0 in host if n0 == outer)
                   for n, s, e in inside if n == name)

    assert within("bst.dispatch", "bst.drain") and within("bst.drain", "drain")
    assert any(n == "gc.gen2" for n, _, _ in host)
