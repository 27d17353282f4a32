"""Engine and write path: host time waiting for the engine call's results
(``block_until_ready``, the server's ``sync`` span), per engine call."""


def read(run):
    phase_s = getattr(run, "phase_s", None)
    if not phase_s or run.engine_calls <= 0:
        return None
    return phase_s.get("sync", 0.0) / run.engine_calls * 1e3
