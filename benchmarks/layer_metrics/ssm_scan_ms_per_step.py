"""Device time a step under ``ssm``'s inner scope ``scan`` in every
pass: softplus of dt, the chunked SSD (within-chunk products, the
chunks' states, the carry across chunks) and the ``D x`` skip.  First
chip."""
from benchmarks.harness import inner_scopes


def reduce(trace, run):
    return inner_scopes.ms_per_step(trace, run, "ssm", ("scan",))
