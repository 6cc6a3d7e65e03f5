"""Median host duration of the program's span ``TrainStep.prepare`` in
the traced window: layouts, the walk over live state, the signature and
the cache lookup, before the jitted call."""
from benchmarks.harness import scopes


def reduce(trace, run):
    return scopes.host_span_ms(trace, run, "TrainStep.prepare")
