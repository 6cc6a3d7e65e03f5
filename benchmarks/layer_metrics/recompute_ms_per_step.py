"""Device time a step of the forward that ``jax.checkpoint`` runs again
in the backward pass (``rematted_computation`` in the scope path).
Nothing where the step recomputes nothing.  First chip."""
from benchmarks.harness import scopes


def reduce(trace, run):
    return scopes.ms_per_step(trace, run, passes=("recompute",))
