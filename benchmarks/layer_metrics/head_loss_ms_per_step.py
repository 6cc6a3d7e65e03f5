"""Device time a step under the region ``head_loss`` in every pass:
everything after the last block up to the scalar loss (final LayerNorm,
the tied / MLM / NSP heads, float32 cross-entropy).  First chip."""
from benchmarks.harness import scopes


def reduce(trace, run):
    return scopes.ms_per_step(trace, run, regions=("head_loss",))
