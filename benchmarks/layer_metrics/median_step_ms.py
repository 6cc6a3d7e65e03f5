"""The steady step: the median of the window's per-step readings (host
clock, one step in flight, the first two dropped).  A stall moves one
reading and not the median, so this says what the step costs when nothing
disturbs it; ``tokens_per_s`` pays for every stall."""


def reduce(trace, run):
    return 1e3 * run["window"]["median_s"]
