"""Engine and write path: device busy time outside the kernel (the delta
fold, epilogues, ingest, compaction's merge) per engine call."""


def read(run):
    if run.trace is None or run.engine_calls <= 0:
        return None
    return (run.trace["busy_s"] - run.trace["kernel_s"]) / run.engine_calls * 1e3
