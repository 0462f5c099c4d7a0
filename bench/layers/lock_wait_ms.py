"""Front door: the mean time per request spent waiting for the signature's
lock in ``BigDAWG.execute`` (``sig_lock_wait`` spans; milliseconds),
outside the profiler's time.  Nothing to read where the program records no
such span."""
import spans


def read(run):
    reqs = spans.traced(run)
    if not reqs or not any(spans.named(s, "sig_lock_wait") for s in reqs):
        return None
    return 1e3 * sum(sum(x["seconds"] for x in spans.named(s, "sig_lock_wait"))
                     for s in reqs) / len(reqs)
