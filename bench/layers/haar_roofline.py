"""Pallas kernels: the Haar kernel's share of its roofline (percent): the
least time the chip could take for the work the transform needs (bytes
2 N T 4, operations 4 N T, whichever bounds) over the measured device time
per call."""
import kernels
import roofline

NEEDLE = "haar"


def read(run):
    s = kernels.per_call_seconds(run, NEEDLE)
    if s is None:
        return None
    cfg = run.extra["cfg"]
    ops, nbytes = roofline.haar_work(cfg["patients"], cfg["samples"])
    return roofline.share_percent(ops, nbytes, s, run.device_kind)
