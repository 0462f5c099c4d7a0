"""Engines: the mean self time per request of the ``engine_op`` spans that
ran outside fused segments (milliseconds).  Inside a fused segment the
program shares the segment's time out by predicted cost, which is not a
measurement, so those are left out."""
import spans


def read(run):
    def pick(ss):
        fused = {s["sid"] for s in spans.named(ss, "fused_segment")}
        own = spans.self_seconds(ss)
        return sum(own[s["sid"]] for s in spans.named(ss, "engine_op")
                   if s.get("parent") not in fused)
    v = spans.mean_per_request(run, pick)
    return None if v is None else 1e3 * v
