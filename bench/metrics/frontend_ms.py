"""Front end: host time in ``submit()`` and ``drain()`` outside the
server's own engine timing (``ServerStats.busy_s``), per engine call: the
requests' queueing, packing into chunks and slicing of the answers."""


def read(run):
    if run.engine_calls <= 0:
        return None
    return (run.submit_s + run.drain_s - run.busy_s) / run.engine_calls * 1e3
