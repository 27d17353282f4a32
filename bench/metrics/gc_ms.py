"""Front end: the interpreter's garbage-collection pauses in the window
(``analysis.runtime.gc_watch``), per engine call."""


def read(run):
    gc_s = getattr(run, "gc_s", None)
    if gc_s is None or run.engine_calls <= 0:
        return None
    return gc_s / run.engine_calls * 1e3
