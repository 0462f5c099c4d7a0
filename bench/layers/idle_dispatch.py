"""Device: the share of the profiled window in which the chip was idle while
the host worked on an engine op (an ``engine_op`` or ``fused_segment`` span
open: dispatching it, or computing it on the host), percent.  Reads the
spans of every request, mapped onto the profile by the program's clock
anchors (``timeline``)."""
import timeline


def read(run):
    tl = timeline.load(run)
    return None if tl is None else timeline.idle_share_with(
        tl, timeline.DISPATCH)
