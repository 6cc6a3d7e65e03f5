"""1 - busy union over the steady traced window, averaged over the chips."""


def reduce(trace, run):
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
