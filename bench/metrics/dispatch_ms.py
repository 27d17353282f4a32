"""Engine and write path: host time in the engine call before the wait,
jit dispatch and the argument transfer to the device (the server's
``dispatch`` span), per engine call."""


def read(run):
    phase_s = getattr(run, "phase_s", None)
    if not phase_s or run.engine_calls <= 0:
        return None
    return phase_s.get("dispatch", 0.0) / run.engine_calls * 1e3
