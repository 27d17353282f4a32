"""Kernel: the least time of the window's searches (``bench/roofline.py``,
over the keys the engine searched) spread over the configuration's chips,
as a share of the kernel's device time per chip.  The work is counted from
the request, not from the router's padded slots."""

from bench import roofline


def read(run):
    if run.trace is None or run.trace["kernel_s"] <= 0 or run.lanes <= 0:
        return None
    least, _ = roofline.least_time(run.lanes, run.height, run.device_kind)
    return least / run.config["chips"] / run.trace["kernel_s"] * 100.0
