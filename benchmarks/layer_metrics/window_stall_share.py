"""Share of the window's readings' time that lay above the median reading:
1 - readings x median / their sum.  What host stalls (a recompile, a GC
pause, a sync every N steps) cost ``tokens_per_s`` in this window."""


def reduce(trace, run):
    return run["window"]["stall_share_pct"]
