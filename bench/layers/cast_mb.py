"""Casts: the mean ``Report.cast_bytes`` per request, in megabytes (1e6
bytes)."""
import spans


def read(run):
    reps = spans.served(run)
    if not reps:
        return None
    return sum(r["cast_bytes"] for r in reps) / len(reps) / 1e6
