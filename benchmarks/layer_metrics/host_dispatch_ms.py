"""Median host duration of the ``bench.step_call`` span: what the step
object's ``__call__`` costs the host before jax has the program queued."""
import statistics

from benchmarks.harness.measure import STEP_SPAN


def reduce(trace, run):
    lo = min(w[0] for w in trace.window.values())
    spans = [e - s for name, s, e in trace.host
             if name == STEP_SPAN and s >= lo]
    return 1e3 * statistics.median(spans) if spans else None
