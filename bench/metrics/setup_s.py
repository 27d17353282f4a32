"""Set-up: from the start of the process to the window's first request --
JAX start-up, loading the records, generating the traffic, warm-up and any
compilation."""


def read(run):
    return run.setup_s
