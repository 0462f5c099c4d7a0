"""Executor and fusion: the mean ``Report.seconds`` of the window's served
requests (milliseconds)."""
import spans


def read(run):
    reps = spans.served(run)
    if not reps:
        return None
    return 1e3 * sum(r["seconds"] for r in reps) / len(reps)
