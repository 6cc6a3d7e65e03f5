"""Device time a step of the routed half of a SwiGLU expert layer, every
pass: ``mlp``'s inner scopes ``router`` (scores, the group limit, top-k,
gates), ``dispatch``, ``experts`` and ``combine`` (the held experts'
``W13`` and ``W2`` over the sorted rows).  First chip."""
from benchmarks.harness import inner_scopes

NAMES = ("ln", "up", "down", "router", "dispatch", "experts", "combine",
         "shared")


def reduce(trace, run):
    return inner_scopes.ms_per_step(
        trace, run, "mlp", ("router", "dispatch", "experts", "combine"),
        NAMES)
