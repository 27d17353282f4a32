"""The sharded cell's two device-trace readers, on synthetic runs: the
router's ``all_to_all`` time and the kernel's roofline share with the work
spread over the chips."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, roofline, trace  # noqa: E402

SHARDED = {"records": 25165824, "chips": 4}
MS = 1_000_000  # ns


def _run(config, trace_dict=None, engine_calls=10, lanes=81920):
    return harness.Run(config=config, mix={}, seconds=1.0, device_kind="TPU v5 lite",
                       engine_calls=engine_calls, lanes=lanes, trace=trace_dict)


def _reduced(op_names):
    """A traced window of one chip whose operations are ``op_names``, one
    millisecond each, reduced as the harness reduces it."""
    events = [(f"%{name} = s32[4,1,2048]{{2,1,0}} all-to-all(s32[4,1,2048] %p)", i * MS, (i + 1) * MS)
              for i, name in enumerate(op_names)]
    return trace.reduce({"/device:TPU:0": events}, [("window", 0, 100 * MS)])


def test_a2a_pins_the_all_to_all_families():
    t = _reduced(["all_to_all.11", "all_to_all.12", "all_to_all.13", "fusion.4", "copy.2"])
    assert [f for f, _ in t["breakdown"]["device_ops"]].count("all_to_all") == 1
    # three 1 ms exchanges over 10 engine calls
    assert harness.reader("a2a_ms")(_run(SHARDED, t)) == pytest.approx(0.3)


@pytest.mark.parametrize("trace_dict", [None, "no collectives"])
def test_a2a_is_absent_without_a_trace_or_a_collective(trace_dict):
    if trace_dict is not None:
        trace_dict = _reduced(["fusion.1", "bst_forest_search.1"])
    assert harness.reader("a2a_ms")(_run(SHARDED, trace_dict)) is None


def test_shard_roofline_spreads_the_one_chip_share_over_the_chips():
    t = {"kernel_s": 0.006, "busy_s": 0.01, "window_s": 1.0}
    four = _run(SHARDED, t)
    one_chip = harness.reader("bst_forest_search_roofline")(four)
    share = harness.reader("shard_search_roofline")(four)
    assert share == pytest.approx(one_chip / 4)
    least, _ = roofline.least_time(four.lanes, 24, "TPU v5 lite")
    assert share == pytest.approx(least / 4 / 0.006 * 100)
    assert harness.reader("shard_search_roofline")(_run(SHARDED, None)) is None
