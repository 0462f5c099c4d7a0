"""Executor and fusion: the mean time per request the host spent blocked on
the device at the executor's barriers (``host_sync`` spans; milliseconds),
outside the profiler's time.  Nothing to read where the program records no
such span."""
import spans


def read(run):
    reqs = spans.traced(run)
    if not reqs or not any(spans.named(s, "host_sync") for s in reqs):
        return None
    return 1e3 * sum(sum(x["seconds"] for x in spans.named(s, "host_sync"))
                     for s in reqs) / len(reqs)
