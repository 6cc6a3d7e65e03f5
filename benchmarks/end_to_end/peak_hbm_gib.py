"""Whether the batch fits: the most HBM held on the fullest of the cell's
devices, in GiB, from the device allocator's statistics after the window:
the larger of the pool's peak and what is held while the step runs, pool
plus program reservation (see ``measure._peak_bytes``)."""


def reduce(trace, run):
    return run["peak_bytes"] / 2**30
