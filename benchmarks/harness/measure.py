"""One cell, once: set-up, the measured window, the traced stretch.

Nothing here names a cell, a configuration or a metric: the cell is the
pair of data files ``BENCHMARK.json`` points at, classes and functions
are named there as ``module:attr``, and each per-layer metric is a file
under ``layer_metrics/`` found by its name.
"""
from __future__ import annotations

import gc
import glob
import importlib
import importlib.util
import json
import math
import os
import re
import shutil
import statistics
import time

from benchmarks.harness import roofline, trace_reduce

STEP_SPAN, FETCH_SPAN = "bench.step_call", "bench.fetch_loss"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DROP_READINGS = 2        # the pipeline fills during the first two
TRACED_STEPS, TRACE_SKIP = 32, 4
MAX_WARMUP_CALLS = 8
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class Refused(Exception):
    """The run cannot be a measurement (no TPU, too few chips, unknown
    cell): exit non-zero and print no result line."""


def resolve(name: str):
    """``module:attr`` -> the object; ``module`` alone -> the module."""
    module, _, attr = name.partition(":")
    obj = importlib.import_module(module)
    return getattr(obj, attr) if attr else obj


def load_cell(workload: str, rehearse: bool) -> dict:
    """The cell's entry, configuration, traffic and metric lists, all from
    ``BENCHMARK.json`` and the files it names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if traffic["chips"] != entry["chips"]:
        raise Refused(f"{workload}: BENCHMARK.json asks for "
                      f"{entry['chips']} chips, the traffic file for "
                      f"{traffic['chips']}")
    sizes = dict(config["sizes"])
    if rehearse:
        sizes.update(config["rehearse_sizes"])
        traffic.update(traffic["rehearse"])
    def of_cell(metrics):
        # the driver holds a cell to every metric that does not list its
        # cells and to those that list this one, and to no other
        return [m for m in metrics
                if workload in m.get("workloads", (workload,))]

    return {"name": workload, "chips": entry["chips"], "config": config,
            "traffic": traffic, "sizes": sizes,
            "end_to_end": of_cell(bench["end_to_end"]),
            "per_layer": of_cell(bench["per_layer"])}


class CompileCounter:
    """Counts what jax hands to the backend compiler (a persistent-cache
    hit included: it is a jit-cache miss all the same)."""

    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, **_):
        self.count += event == COMPILE_EVENT


def run_loop(step, batch, seconds: float, max_steps: float = math.inf):
    """The measuring loop: dispatch step i, then fetch the loss of step
    i-1, then take a timestamp.  One step is always queued behind the one
    that runs, as in a training loop that logs its loss one step late, so
    a host pause shorter than a step costs the device nothing."""
    from jax.profiler import TraceAnnotation

    def call():
        with TraceAnnotation(STEP_SPAN):
            return step(*batch)

    def fetch(loss):
        with TraceAnnotation(FETCH_SPAN):
            return float(loss)

    gc.collect()
    losses, stamps = [], []
    t_start = time.perf_counter()
    pending, dispatched = call(), 1
    while True:
        following = call()
        dispatched += 1
        losses.append(fetch(pending))
        stamps.append(time.perf_counter())
        pending = following
        if stamps[-1] - t_start >= seconds or dispatched >= max_steps:
            break
    losses.append(fetch(pending))
    return {"losses": losses, "stamps": stamps, "dispatched": dispatched,
            "wall_s": time.perf_counter() - t_start}


def summarize(stamps, tokens_per_step: int) -> dict:
    """Readings are the differences of consecutive timestamps, the first
    two dropped.  Their median is the steady step (a per-layer reading:
    a stall moves one reading and not the median); the end-to-end rate
    is all the window's work over all its time and is not taken here."""
    readings = [b - a for a, b in zip(stamps, stamps[1:])][DROP_READINGS:]
    if len(readings) < 4:
        raise RuntimeError(f"only {len(readings)} readings: the window is "
                           f"too short for this step")
    median = statistics.median(readings)
    q1, _, q3 = statistics.quantiles(readings, n=4)
    return {"readings": len(readings), "median_s": median, "q1_s": q1,
            "q3_s": q3, "max_s": max(readings),
            "units_per_s_at_median": tokens_per_step / median,
            "stall_share_pct": 100.0 * (
                1.0 - len(readings) * median / sum(readings))}


def _peak_bytes(device) -> int:
    """The most HBM held on ``device``, from values that were there
    together.  libtpu keeps a running program's temporaries outside the
    allocator's pool, as *reserved* bytes (PR 24: BERT-base reads 1.36 GB
    in use beside 3.60 GB reserved), so what is held while the step runs
    is the pool as the window leaves it (the step donates its state, so
    the pool is the same while it runs) plus the largest reservation.
    The pool alone may have peaked higher than that (on four chips the
    first holds 5.33 GB in its first sharded call, 2.42 GB afterwards)."""
    stats = device.memory_stats()
    return max(stats["peak_bytes_in_use"],
               stats["bytes_in_use"] + stats.get("peak_bytes_reserved", 0))


def _state_on_chips(model, step, devices, want_platform: str):
    """(d): the parameters live on the cell's devices and, across chips,
    every chip holds its own part of the optimizer state."""
    import jax
    for name, p in model.named_parameters():
        if any(d.platform != want_platform or d not in devices
               for d in p.data.devices()):
            return f"parameter {name} is on {p.data.devices()}"
    if len(devices) == 1:
        return None
    state = [a for a in jax.tree_util.tree_leaves(step._opt_states)
             if hasattr(a, "addressable_shards")]
    whole = sum(a.nbytes for a in state)
    for d in devices:
        held = sum(s.data.nbytes for a in state
                   for s in a.addressable_shards if s.device == d)
        if not 0 < held < whole:
            return (f"{d} holds {held} of {whole} optimizer-state bytes: "
                    f"not a shard")
    return None


def build_model(config: dict, sizes: dict, seed: int):
    """The configuration's model at ``sizes``, weights from the program's
    own initialiser under ``seed``."""
    kwargs = {k: sizes[v]
              for k, v in config["model_kwargs_from_sizes"].items()}
    kwargs.update(config["model_kwargs"])
    kwargs[config["seed_kwarg"]] = seed
    return resolve(config["model"])(resolve(config["model_config"])(
        **kwargs))


class Feed:
    """The cell's resident batches in turn: every unpacking, as in
    ``step(*feed)``, hands the next one, round and round; with one batch,
    the same one every time."""

    def __init__(self, batches: list):
        self.batches, self.turn = batches, 0

    def __iter__(self):
        batch = self.batches[self.turn % len(self.batches)]
        self.turn += 1
        return iter(batch)


def _build(cell: dict, seed: int, devices):
    """Model, optimizer and step exactly as the configuration and traffic
    files say, on the cell's mesh; weights from the program's own
    initialiser under ``seed``; the traffic file's ``resident_batches``
    (one, where it names none) from ``seed`` in one draw, resident on the
    device (the input pipeline is bypassed on purpose).  Returns the
    first batch's arrays, which the reference reads, and the feed."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    import paddle_tpu as paddle
    from paddle_tpu.parallel import make_mesh, set_mesh
    config, traffic, sizes = cell["config"], cell["traffic"], cell["sizes"]
    mesh = make_mesh(dict(traffic["mesh"]), devices=devices)
    set_mesh(mesh)
    paddle.seed(seed % 2**31)
    model = build_model(config, sizes, seed)
    optimizer = resolve(config["optimizer"]["class"])(
        parameters=model.parameters(), **config["optimizer"]["kwargs"])
    step_kwargs = dict(traffic["step"]["kwargs"], **config["precision"])
    if "mesh_kwarg" in traffic["step"]:
        step_kwargs[traffic["step"]["mesh_kwarg"]] = mesh
    step = resolve(traffic["step"]["class"])(
        model, resolve(config["loss"]), optimizer, **step_kwargs)
    rows, count = traffic["batch"], traffic.get("resident_batches", 1)
    drawn = resolve(config["inputs"])(seed, rows * count, traffic["seq"],
                                      sizes)
    on_mesh = NamedSharding(mesh, PartitionSpec(tuple(traffic["batch_axes"])))
    batches = [[paddle.to_tensor(jax.device_put(a[i:i + rows], on_mesh))
                for a in drawn] for i in range(0, rows * count, rows)]
    jax.block_until_ready([t.data for batch in batches for t in batch])
    return model, step, tuple(a[:rows] for a in drawn), Feed(batches)


def _warm_up(step, batch, compiles, phase) -> list:
    """Call the step until two consecutive calls hand nothing to the
    compiler.  Only the cell's own shapes are warmed."""
    warmup, quiet = [], 0
    while quiet < 2:
        if len(warmup) >= MAX_WARMUP_CALLS:
            raise RuntimeError(f"the step still compiles after "
                               f"{len(warmup)} calls: {warmup}")
        before, t = compiles.count, time.perf_counter()
        loss = float(step(*batch))
        warmup.append({"s": round(time.perf_counter() - t, 3),
                       "compiled": compiles.count > before, "loss": loss})
        quiet = 0 if warmup[-1]["compiled"] else quiet + 1
        phase(("first_call", "second_call")[len(warmup) - 1]
              if len(warmup) <= 2 else f"warmup_call_{len(warmup)}")
    return warmup


def measure(workload: str, seed: int, seconds: float, trace: bool,
            rehearse: bool, t0: float, say) -> dict:
    """Run the cell and return the result object of the contract."""
    cell = load_cell(workload, rehearse)
    config, traffic, sizes = cell["config"], cell["traffic"], cell["sizes"]
    phases, mark = {}, [t0]

    def phase(name):
        now = time.perf_counter()
        phases[name] = round(now - mark[0], 3)
        mark[0] = now

    import jax

    import paddle_tpu as paddle

    platform = "cpu" if rehearse else "tpu"
    if jax.default_backend() != platform:
        raise Refused(f"jax.default_backend() is {jax.default_backend()!r}, "
                      f"not {platform!r} (JAX_PLATFORMS="
                      f"{os.environ.get('JAX_PLATFORMS')!r}): this "
                      f"benchmark measures the chip and nothing else")
    if len(jax.devices()) < cell["chips"]:
        raise Refused(f"{workload} needs {cell['chips']} chips, jax sees "
                      f"{len(jax.devices())}")
    devices = jax.devices()[:cell["chips"]]
    kind = devices[0].device_kind
    # an unknown device is an error, early; a rehearsal has no peaks
    peak = None if rehearse else roofline.peaks(kind)
    if not rehearse:
        cache = paddle.device.use_compile_cache()
        entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        say(f"compile cache: {cache} ({entries} entries at start)")
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    phase("import")

    model, step, arrays, feed = _build(cell, seed, devices)
    tokens_per_step = traffic["batch"] * traffic["seq"]
    phase("build")

    reference = resolve(config["reference"])
    params = {n: p.data for n, p in model.named_parameters()}
    prepared = {}
    if "router_bias" in config:
        # what a checkpoint of such a router brings and a fresh draw
        # lacks: solved on the reference's one walk, which goes on to
        # the loss under it; the model gets it as a checkpoint would
        reference_loss, solved, routing, prepared = resolve(
            config["router_bias"])(reference, params, arrays, sizes,
                                   traffic["reference_block"])
        model.set_state_dict(solved)
        say("routing: " + json.dumps(routing))
    else:
        reference_loss = reference.loss(params, arrays, sizes,
                                        traffic["reference_block"])
    del params
    phase("reference")

    warmup = _warm_up(step, feed, compiles, phase)
    misplaced = _state_on_chips(model, step, devices, platform)

    compiles_before = compiles.count
    setup_s = time.perf_counter() - t0
    window = run_loop(step, feed, seconds)
    compiled_in_window = compiles.count - compiles_before
    peak_bytes = 0 if rehearse else max(map(_peak_bytes, devices))
    if not rehearse:
        say("memory: " + json.dumps(devices[0].memory_stats()))
    summary = summarize(window["stamps"], tokens_per_step)
    # the end-to-end rate: all the work of the window over all its time
    units_per_s = window["dispatched"] * tokens_per_step / window["wall_s"]

    first, losses = warmup[0]["loss"], window["losses"]
    rel = abs(first - reference_loss) / abs(reference_loss)
    failed = sum(not math.isfinite(x) for x in losses)
    # each number compared beside its limit: `correct`, the `correct:`
    # line and the result's `compared` all come from here.  A limit is
    # the largest value that holds, but the last loss's, which is strict:
    # on one resident batch a step that returns its state unchanged reads
    # exactly 1 there; a cell fed fresh batches reads 1 give or take the
    # batches' own difference, and names a limit under that
    compared = {
        "first_loss_rel_diff": (rel, reference.TOLERANCE_REL),
        **prepared,
        "last_loss_over_first": (
            losses[-1] / first,
            traffic.get("last_loss_over_first_limit", 1.0)),
        "nonfinite_losses": (failed, 0),
        "compiled_in_window": (compiled_in_window, 0),
        "state_off_its_chips": (int(misplaced is not None), 0)}
    checks = {name: (value < limit if name == "last_loss_over_first"
                     else value <= limit)
              for name, (value, limit) in compared.items()}
    say("set-up: " + json.dumps({
        "setup_s": round(setup_s, 3), "phases": phases, "warmup": warmup}))
    say("window: " + json.dumps({
        **{k: (round(v, 6) if isinstance(v, float) else v)
           for k, v in summary.items()},
        "dispatched": window["dispatched"],
        "wall_s": round(window["wall_s"], 4),
        "units_over_wall_per_s": round(units_per_s, 2),
        "at_median_over_wall": round(
            summary["units_per_s_at_median"] / units_per_s, 5)}))
    say("correct: " + json.dumps({
        **checks, "first_loss": first, "reference_loss": reference_loss,
        "rel_diff": float(f"{rel:.3g}"),
        "tolerance_rel": reference.TOLERANCE_REL, "last_loss": losses[-1],
        "misplaced": misplaced}))

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": all(checks.values()),
              "attempted": window["dispatched"], "failed": failed,
              "metrics": {}, "device": device}
    run = {"sizes": sizes, "traffic": traffic,
           "tokens_per_step": tokens_per_step, "peak": peak,
           "reference": reference, "setup_s": setup_s, "warmup": warmup,
           "window": summary, "units_per_s": units_per_s,
           "peak_bytes": peak_bytes, "say": say}
    tr = None
    if trace:
        tr = _traced_stretch(step, feed, workload, say)
        first_chip = tr.chips[0]
        lo, hi = tr.window[first_chip]
        if not rehearse:
            device["busy_s"], device["window_s"] = tr.busy_s(), tr.window_s()
        result["breakdown"] = {
            "device_ops": _top(trace_reduce.sum_by_name(
                tr.ops[first_chip], lo, hi)),
            "idle_gaps": _top(trace_reduce.attribute_gaps(
                trace_reduce.idle_gaps(tr.busy(first_chip), lo, hi),
                tr.host, tr.programs[first_chip]))}
    folder, metrics = (("layer_metrics", cell["per_layer"]) if trace
                       else ("end_to_end", cell["end_to_end"]))
    for m in metrics:
        value = _reader(folder, m["name"]).reduce(tr, run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}
    # each number compared beside its limit, last in the line
    result["compared"] = {
        name: {"value": value, "limit": limit, "holds": checks[name]}
        for name, (value, limit) in compared.items()}
    if rehearse:
        # a CPU number is never printed under the name of a device metric
        say("rehearsal on the CPU, not device numbers: "
            + json.dumps(result["metrics"]))
        counted = {m["name"] for m in metrics
                   if m["source"] == "program_counter"}
        result["metrics"] = {k: v for k, v in result["metrics"].items()
                             if k in counted}
    return result


def _top(sums: dict) -> list:
    return [[k, v] for k, v in sorted(sums.items(),
                                      key=lambda kv: -kv[1])[:10]]


def _reader(folder: str, name: str):
    """The metric's own file, found by the metric's name."""
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{folder}." + re.sub(r"\W", "_", name),
        os.path.join(BENCH_DIR, folder, name + ".py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


def _traced_stretch(step, batch, workload: str, say):
    """The same loop for a few tens of steps under the profiler, reduced
    to a ``Trace``.  The trace stays in the checkout; only the reduction
    is printed."""
    import jax
    trace_dir = os.path.join(ROOT, ".bench_trace", workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        run_loop(step, batch, math.inf, TRACED_STEPS)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    tr = trace_reduce.load(found[0], (STEP_SPAN, FETCH_SPAN), STEP_SPAN,
                           TRACE_SKIP)
    say("trace: " + json.dumps({"structure": tr.structure,
                                "steps": tr.steps, "window": tr.window}))
    if not tr.chips:
        raise RuntimeError("the trace holds no device operation")
    chip = tr.chips[0]
    say("trace, asynchronous operations in flight on the first chip: "
        + json.dumps(_top(trace_reduce.sum_by_name(
            tr.async_ops.get(chip, []), *tr.window[chip]))[:5]))
    return tr
