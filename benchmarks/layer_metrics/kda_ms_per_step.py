"""Device time a step under the region ``kda`` in every pass: a KDA
block's RMSNorm, its q/k/v projection, convolution, gates, the chunked
delta rule, the output norm and gate, the output projection and the
residual.  First chip.  ``inner_regions.json`` does not list the region:
its inner names are passed here."""
from benchmarks.harness import inner_scopes

NAMES = ("ln", "qkv", "conv", "gate", "scan", "out_norm", "out")


def reduce(trace, run):
    return inner_scopes.ms_per_step(trace, run, "kda", None, NAMES)
