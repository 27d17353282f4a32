"""Engine and write path: host time fetching the results to the host and
copying them into the drain's columns (the server's ``fetch`` span), per
engine call."""


def read(run):
    phase_s = getattr(run, "phase_s", None)
    if not phase_s or run.engine_calls <= 0:
        return None
    return phase_s.get("fetch", 0.0) / run.engine_calls * 1e3
