"""Device time a step of the backward pass: operations whose scope path
has ``transpose(`` and is not a recomputation, whatever their region
(the gradients' stacking across layers has none).  First chip."""
from benchmarks.harness import scopes


def reduce(trace, run):
    return scopes.ms_per_step(trace, run, passes=("bwd",))
