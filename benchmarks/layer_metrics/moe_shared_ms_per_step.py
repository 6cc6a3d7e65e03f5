"""Device time a step of what every chip of the deployment computes
alike in an expert layer, every pass: ``mlp``'s inner scopes ``shared``
(the shared expert), ``latent_down`` and ``latent_up``.  First chip."""
from benchmarks.harness import inner_scopes


def reduce(trace, run):
    return inner_scopes.ms_per_step(
        trace, run, "mlp", ("shared", "latent_down", "latent_up"))
