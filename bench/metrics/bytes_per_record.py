"""Device memory at its peak (``peak_bytes_in_use`` on the fullest chip,
read after the window) per live record."""


def read(run):
    if run.memory_peak_bytes <= 0 or run.live_records <= 0:
        return None
    return run.memory_peak_bytes / run.live_records
