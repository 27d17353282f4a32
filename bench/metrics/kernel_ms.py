"""Kernel: device time of the ``bst_forest_search`` events of the traced
window, per engine call."""


def read(run):
    if run.trace is None or run.engine_calls <= 0 or run.trace["kernel_s"] <= 0:
        return None
    return run.trace["kernel_s"] / run.engine_calls * 1e3
