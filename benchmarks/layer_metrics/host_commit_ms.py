"""Median host duration of the program's span ``TrainStep.commit`` in the
traced window: writing the step's outputs back and every per-step hook
after the jitted call."""
from benchmarks.harness import scopes


def reduce(trace, run):
    return scopes.host_span_ms(trace, run, "TrainStep.commit")
