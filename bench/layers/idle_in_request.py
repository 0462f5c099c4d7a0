"""Device: the share of the profiled window in which the chip was idle while
at least one request was inside the program (a ``request`` span open),
percent; the rest of ``idle_share`` is idle with no request in the program.
Reads the spans of every request, mapped onto the profile by the program's
clock anchors (``timeline``), and logs the longest idle gaps labelled with
the innermost span open over each."""
import harness
import timeline


def read(run):
    tl = timeline.load(run)
    if tl is None:
        return None
    harness.log("idle_gaps", gaps=timeline.gap_labels(tl))
    return timeline.idle_share_with(tl, ("request",))
