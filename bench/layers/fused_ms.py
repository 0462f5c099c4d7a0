"""Executor and fusion: the mean time per request of the ``fused_segment``
spans, the whole compiled segments (milliseconds).  Nothing to read where
no request ran a fused segment."""
import spans


def read(run):
    reqs = spans.traced(run)
    if not reqs or not any(spans.named(s, "fused_segment") for s in reqs):
        return None
    return 1e3 * sum(sum(x["seconds"] for x in spans.named(s, "fused_segment"))
                     for s in reqs) / len(reqs)
