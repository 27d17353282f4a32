"""Front end: host time grouping the drain's requests by op, concatenating
their keys and padding the chunk (the server's ``pack`` span), per engine
call."""


def read(run):
    phase_s = getattr(run, "phase_s", None)
    if not phase_s or run.engine_calls <= 0:
        return None
    return phase_s.get("pack", 0.0) / run.engine_calls * 1e3
