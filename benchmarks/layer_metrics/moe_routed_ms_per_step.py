"""Device time a step of the routed half of an expert layer, every pass:
``mlp``'s inner scopes ``router`` (scores, top-k, gates), ``dispatch``,
``experts`` and ``combine`` (the held experts' two matmuls).  First
chip."""
from benchmarks.harness import inner_scopes


def reduce(trace, run):
    return inner_scopes.ms_per_step(
        trace, run, "mlp", ("router", "dispatch", "experts", "combine"))
