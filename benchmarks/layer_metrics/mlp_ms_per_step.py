"""Device time a step under the region ``mlp`` in every pass: LayerNorm,
up projection + GELU, down projection, residual.  First chip."""
from benchmarks.harness import scopes


def reduce(trace, run):
    return scopes.ms_per_step(trace, run, regions=("mlp",))
