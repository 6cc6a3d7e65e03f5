"""Seconds of set-up spent in the warm-up calls that compiled."""


def reduce(trace, run):
    return sum(call["s"] for call in run["warmup"] if call["compiled"])
