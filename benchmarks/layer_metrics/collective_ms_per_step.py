"""Time a step on the first chip has a collective operation (all-reduce,
reduce-scatter, all-gather, all-to-all, collective-permute) running or,
if asynchronous, between its start and its done.  Nothing on one chip."""
from benchmarks.harness import trace_reduce

COLLECTIVE_EVENT = (r"^(all-reduce|reduce-scatter|all-gather|all-to-all|"
                    r"collective-permute)")


def reduce(trace, run):
    if len(trace.chips) < 2:
        return None
    flight = trace.in_flight(trace.chips[0], COLLECTIVE_EVENT)
    return 1e3 * trace_reduce.total(flight) / trace.steps
