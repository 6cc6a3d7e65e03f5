"""Device time a step under the region ``ssm`` in every pass: a Mamba-2
block's RMSNorm, input projection, convolution, SSD scan, gated group
norm, output projection and residual.  First chip."""
from benchmarks.harness import inner_scopes


def reduce(trace, run):
    return inner_scopes.ms_per_step(trace, run, "ssm")
