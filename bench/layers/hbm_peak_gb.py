"""Device: the chip's peak bytes in use over the whole run, read after the
window (gigabytes, 1e9 bytes)."""


def read(run):
    return run.memory_peak / 1e9 if run.memory_peak else None
