"""Device busy time per step: the union of the device-op intervals in the
steady traced window over its steps, averaged over the chips."""


def reduce(trace, run):
    return 1e3 * trace.busy_s() / trace.steps
