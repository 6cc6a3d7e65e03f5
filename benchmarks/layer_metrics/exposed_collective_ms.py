"""The part of a step's collective time on the first chip during which no
other operation runs there: communication that nothing hides."""
import re

from benchmarks.harness import trace_reduce

COLLECTIVE_EVENT = (r"^(all-reduce|reduce-scatter|all-gather|all-to-all|"
                    r"collective-permute)")


def reduce(trace, run):
    if len(trace.chips) < 2:
        return None
    chip = trace.chips[0]
    lo, hi = trace.window[chip]
    compute = [ev for ev in trace.ops[chip]
               if not re.search(COLLECTIVE_EVENT, ev[0])]
    alone = trace_reduce.subtract(
        trace.in_flight(chip, COLLECTIVE_EVENT),
        trace_reduce.clip(trace_reduce.spans_of(compute), lo, hi))
    return 1e3 * trace_reduce.total(alone) / trace.steps
