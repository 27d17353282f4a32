"""The reduction from trace events to busy time, kernel time, idle share and
the named idle gaps, and the roofline counts."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import roofline, trace  # noqa: E402

MS = 1_000_000  # ns


def test_union_and_gaps():
    got = trace.union([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 0, 25)
    assert got == [(0, 3), (5, 12), (20, 25)]
    assert trace.gaps(got, 0, 28) == [(3, 5), (12, 20), (25, 28)]
    assert trace.union([(0, 1)], 2, 3) == []


def _synthetic():
    k = trace.KERNEL
    devices = {
        "/device:TPU:0": [
            ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a.1), kind=kLoop", 0 * MS, 1 * MS),
            (f"%{k}.1 = (s32[1,1,8]) custom-call(s32[1,1,8] %b.3)", 1 * MS, 4 * MS),
            ("%fusion.2 = s32[8]{0} fusion()", 3 * MS, 5 * MS),  # overlaps the kernel: busy once
            (f"%{k}.1 = (s32[1,1,8]) custom-call(s32[1,1,8] %b.3)", 10 * MS, 12 * MS),
            ("%copy.3 = s32[8]{0} copy(s32[8]{0} %c)", 50 * MS, 60 * MS),  # after the window
        ]
    }
    host = [
        ("window", 0, 20 * MS),
        ("drain", 0, 5 * MS),
        ("submit", 5 * MS, 6 * MS),
        ("drain", 6 * MS, 18 * MS),
        ("XlaCompile", 13 * MS, 18 * MS),
        ("unrelated", 0, 20 * MS),
        ("ToLiteral", 7 * MS, 8 * MS),
    ]
    return devices, host


def test_reduce_busy_kernel_idle():
    devices, host = _synthetic()
    r = trace.reduce(devices, host)
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.007)  # [0, 5) and [10, 12)
    assert r["kernel_s"] == pytest.approx(0.005)
    assert r["kernel_calls"] == 2
    ops = dict(r["breakdown"]["device_ops"])
    assert ops[trace.KERNEL] == pytest.approx(0.005) and ops["fusion"] == pytest.approx(0.003)
    assert "copy" not in ops
    gaps = r["breakdown"]["idle_gaps"]
    # the longest gap [12, 20) is half compiling; [5, 10) is drain (mid 7.5),
    # there copying to the host
    assert gaps == [["compile", pytest.approx(0.008)], ["drain:ToLiteral", pytest.approx(0.005)]]


def test_reduce_averages_over_chips():
    devices, host = _synthetic()
    devices["/device:TPU:1"] = [(trace.KERNEL, 0, 1 * MS)]
    r = trace.reduce(devices, host)
    assert r["busy_s"] == pytest.approx((0.007 + 0.001) / 2)
    assert r["kernel_s"] == pytest.approx((0.005 + 0.001) / 2)


def test_reduce_needs_a_window_and_device_work():
    devices, host = _synthetic()
    with pytest.raises(RuntimeError):
        trace.reduce(devices, [h for h in host if h[0] != "window"])
    with pytest.raises(RuntimeError):
        trace.reduce({}, host)


def test_roofline_counts():
    nbytes, ops = roofline.search_work(lanes=8192, height=22)
    assert nbytes == 8192 * (4 + 8 + 23 * 8)
    assert ops == 8192 * 23
    t, bound = roofline.least_time(8192, 22, "TPU v5 lite")
    assert bound == "bytes"
    assert t == pytest.approx(nbytes / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
