"""Casts (cast, migrator): the mean time per request of the ``cast`` spans
(milliseconds; 0 where the served plans cast nothing)."""
import spans


def read(run):
    v = spans.mean_per_request(
        run, lambda ss: sum(s["seconds"] for s in spans.named(ss, "cast")))
    return None if v is None else 1e3 * v
