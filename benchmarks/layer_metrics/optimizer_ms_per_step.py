"""Device time a step under the scope ``optimizer``: gradient clip and
the functional update (``jit.apply_functional_update``).  First chip."""
from benchmarks.harness import scopes


def reduce(trace, run):
    return scopes.ms_per_step(trace, run, passes=("optimizer",))
