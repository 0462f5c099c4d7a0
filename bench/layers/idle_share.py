"""Device: the share of the profiled window in which no operation ran on
the chip (percent)."""


def read(run):
    p = run.profile
    if p is None or p.window_ns <= 0:
        return None
    return 100.0 * (1.0 - p.busy_ns / p.window_ns)
