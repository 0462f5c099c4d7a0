"""Load generator: the 95th percentile of how late a request was handed to
the request threads after its due time (milliseconds), outside the
profiler's time.  Open loop only."""
import math

import spans
from loadgen import percentile


def read(run):
    late = [o.queued - o.due for o in spans.quiet(run)
            if not math.isnan(o.queued)]
    if run.window.loop != "open" or not late:
        return None
    return 1e3 * percentile(late, 95)
