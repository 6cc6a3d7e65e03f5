"""Device time a step under the region ``attn`` in every pass: the
block's LayerNorm, QKV projection, attention core (the flash kernels or
XLA's attention), output projection and residual.  First chip."""
from benchmarks.harness import scopes


def reduce(trace, run):
    return scopes.ms_per_step(trace, run, regions=("attn",))
