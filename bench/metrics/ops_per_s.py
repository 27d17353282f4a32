"""Key operations answered over the whole window, per second of it."""


def read(run):
    return run.keys_answered / run.window_s if run.window_s > 0 else None
