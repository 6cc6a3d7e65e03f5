"""Device time a step of the forward pass: operations whose scope path
has ``jvp(`` or a model region (``embed``, ``attn``, ``mlp``,
``head_loss``) and neither ``transpose(`` nor ``rematted_computation``;
AMP's casts and the layer scan's slicing are forward in no region.
First chip."""
from benchmarks.harness import scopes


def reduce(trace, run):
    return scopes.ms_per_step(trace, run, passes=("fwd",))
