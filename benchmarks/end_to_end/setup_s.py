"""Process start to the start of the window: import, build, reference
check, compilation (or its cache reads) and warm-up."""


def reduce(trace, run):
    return run["setup_s"]
