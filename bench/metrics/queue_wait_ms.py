"""Front end: how long a drain's oldest request waited in the queue before
the drain began (``ServerStats.queue_wait_s`` over its ``drains``)."""


def read(run):
    drains = getattr(run, "drains", 0)
    if drains <= 0:
        return None
    return run.queue_wait_s / drains * 1e3
