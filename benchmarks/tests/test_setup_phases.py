"""The set-up readers (``setup_traces``, ``setup_trace_s``,
``setup_lower_s``, ``setup_backend_s``, ``setup_cold_compile_s``,
``setup_param_init_s``) read the program's numbers when they run, after
the measured window: the window compiles nothing, so what the step's
site holds then is set-up's."""
import jax

from benchmarks.harness import measure

CELL = "gpt2_345m.train_b8_s1024"
PHASES = {"setup_traces": "traces", "setup_trace_s": "trace_s",
          "setup_lower_s": "lower_s", "setup_backend_s": "backend_s",
          "setup_cold_compile_s": "cold_compile_s"}


def test_the_step_sites_phases_hold_still_over_the_window():
    from paddle_tpu.framework import health, monitor
    from paddle_tpu.parallel import get_mesh, set_mesh
    health.reset()
    monitor.reset_stat("model_init_seconds_total")
    mesh = get_mesh()
    compiles = measure.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        cell = measure.load_cell(CELL, rehearse=True)
        model, step, _, feed = measure._build(cell, 2**31 + 5,
                                              jax.devices()[:1])
        measure._warm_up(step, feed, compiles, lambda name: None)
        warm = health.compile_report()["TrainStep"]
        before = compiles.count
        measure.run_loop(step, feed, 1.0)
        assert compiles.count == before
        held = health.compile_report()["TrainStep"]
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        set_mesh(mesh)
    assert held["calls"] > warm["calls"]
    assert {k: held[k] for k in PHASES.values()} \
        == {k: warm[k] for k in PHASES.values()}
    assert warm["traces"] > 0 and warm["backend_s"] > 0
    for metric, key in PHASES.items():
        assert measure._reader("layer_metrics", metric).reduce(
            None, {}) == held[key]
    drawn = measure._reader("layer_metrics", "setup_param_init_s").reduce(
        None, {})
    assert drawn == monitor.get_stat("model_init_seconds_total") > 0
