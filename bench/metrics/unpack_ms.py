"""Front end: host time slicing the chunk's answers into one tuple per
request (the server's ``unpack`` span), per engine call."""


def read(run):
    phase_s = getattr(run, "phase_s", None)
    if not phase_s or run.engine_calls <= 0:
        return None
    return phase_s.get("unpack", 0.0) / run.engine_calls * 1e3
