"""Router: device time of the ``all_to_all`` operations (the sharded
router's key and result exchanges) per engine call, on the trace's first
chip (``breakdown.device_ops``, its ten costliest operation families)."""

# XLA names a lowered ``jax.lax.all_to_all`` ``all_to_all.<n>``: the one
# family a traced run of the four-chip cell shows on a v5e.
FAMILIES = ("all_to_all",)


def read(run):
    if run.trace is None or run.engine_calls <= 0:
        return None
    seconds = [s for family, s in run.trace["breakdown"]["device_ops"] if family in FAMILIES]
    if not seconds:
        return None
    return sum(seconds) / run.engine_calls * 1e3
