"""Seconds of set-up spent tracing the step's Python into jaxprs, booked
by the program to the step's calls (``trace_s`` of
``health.compile_report()``'s ``TrainStep`` site; a nested trace counts
once).  None where the program books no such span."""


def reduce(trace, run):
    from paddle_tpu.framework import health
    return health.compile_report().get("TrainStep", {}).get("trace_s")
