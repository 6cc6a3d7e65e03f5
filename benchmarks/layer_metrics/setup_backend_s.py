"""Seconds of set-up spent in backend compiles of the step's calls,
persistent-cache reads included (``backend_s`` of
``health.compile_report()``'s ``TrainStep`` site).  None where the
program books no such span."""


def reduce(trace, run):
    from paddle_tpu.framework import health
    return health.compile_report().get("TrainStep", {}).get("backend_s")
