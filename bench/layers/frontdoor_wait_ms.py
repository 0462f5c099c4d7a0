"""Front door (the request threads, QueryServer, Session): the mean time a
request spent between being handed to the request threads and being
answered that the middleware's own ``Report.seconds`` does not cover
(milliseconds), outside the profiler's time.  In the open loop it holds
the wait for a free request thread."""
import spans


def read(run):
    outs = [o for o in spans.quiet(run) if o.ok]
    if not outs:
        return None
    return 1e3 * sum(o.done - o.queued - o.report["seconds"]
                     for o in outs) / len(outs)
