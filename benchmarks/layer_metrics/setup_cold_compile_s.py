"""The part of ``setup_backend_s`` that the persistent compile cache did
not serve: backend compiles in which jax reported no cache hit
(``cold_compile_s`` of ``health.compile_report()``'s ``TrainStep``
site).  On a warm cache, the programs under jax's caching threshold.
None where the program books no such span."""


def reduce(trace, run):
    from paddle_tpu.framework import health
    return health.compile_report().get("TrainStep", {}).get("cold_compile_s")
