"""Device time a step under ``kda``'s inner scope ``scan`` in every pass:
the chunked delta rule (the within-chunk products, the triangular
inverse, the carry of the state across chunks, the outputs).  First
chip."""
from benchmarks.harness import inner_scopes

NAMES = ("ln", "qkv", "conv", "gate", "scan", "out_norm", "out")


def reduce(trace, run):
    return inner_scopes.ms_per_step(trace, run, "kda", ("scan",), NAMES)
