"""Pallas kernels: device time of one call of the Haar kernel
(microseconds)."""
import kernels

NEEDLE = "haar"


def read(run):
    s = kernels.per_call_seconds(run, NEEDLE)
    return None if s is None else 1e6 * s
