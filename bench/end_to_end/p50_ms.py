"""Median latency over every request of the window, a failed one counted
slower than every served one (milliseconds)."""
from loadgen import percentile


def read(run):
    return 1e3 * percentile(run.window.latencies(), 50)
