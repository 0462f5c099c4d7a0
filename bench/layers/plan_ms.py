"""Planner and plan cache: the mean self time per request of the ``plan``
and ``cache_hit`` spans (milliseconds)."""
import spans


def read(run):
    def pick(ss):
        own = spans.self_seconds(ss)
        return sum(own[s["sid"]] for s in spans.named(ss, "plan",
                                                      "cache_hit"))
    v = spans.mean_per_request(run, pick)
    return None if v is None else 1e3 * v
