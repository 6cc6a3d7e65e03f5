"""Device time of the operations whose scope path names neither a
region nor a pass, over the time of all operations (control flow left
out): what the named regions do not explain.  First chip."""
from benchmarks.harness import scopes


def reduce(trace, run):
    return scopes.unscoped_share(trace, run)
