"""Engines: device time of one call of the dense engine's per-band
histograms (microseconds).  A call is one compiled program,
``core/engines.py`` ``dense_bin_hist``, whose device ops carry
``bigdawg_op="dense_bin_hist"`` among their frontend attributes, in the HLO
text a TPU trace names them by (on the CPU, their ``hlo_module`` stat is
``jit_dense_bin_hist``).  Its ops are summed over the profile and divided by
the calls, counted as the runs of its most frequent op (each op runs once a
call).  Nothing to read where the program has no such ops."""
from collections import Counter

import trace_reduce

NEEDLE = "dense_bin_hist"


def read(run):
    p = run.profile
    if p is None:
        return None
    ops = trace_reduce.matching(p.ops, NEEDLE)
    if not ops:
        return None
    calls = max(Counter(op.name for op in ops).values())
    return sum(op.dur for op in ops) / calls / 1e3
