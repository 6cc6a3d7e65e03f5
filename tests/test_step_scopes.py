"""The names inside the train step: ``jax.named_scope`` regions in the
compiled step's ``op_name``s, the step's own host spans in a bare
``jax.profiler`` trace, one record per interval, and the compile count
that sees jax's own retrace (profiler/__init__.py lists the names).
"""
import glob
import json
import re

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.framework import health, monitor
from paddle_tpu.framework.observability import tracer
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (GPT, BailingHybrid, Bert, NemotronH,
                               bailing_hybrid_loss, bailing_hybrid_tiny,
                               bert_pretrain_loss, bert_tiny, gpt_loss,
                               gpt_tiny, nemotron_h_loss, nemotron_h_tiny)
from paddle_tpu.parallel import (ShardedTrainStep, get_mesh, make_mesh,
                                 set_mesh)

MODEL_REGIONS = ("embed", "attn", "mlp", "head_loss")
INNER = {"attn": ("ln", "qkv", "core", "out"), "mlp": ("ln", "up", "down")}
# the typed-block model: its expert layer under ``mlp``, and a region of
# its own for the Mamba-2 layer
NEMOTRON = "nemotron_h_tiny-remat"
INNER_NEMOTRON = {
    "attn": INNER["attn"],
    "mlp": ("ln", "router", "latent_down", "dispatch", "experts", "combine",
            "latent_up", "shared"),
    "ssm": ("ln", "in_proj", "conv", "scan", "gate_norm", "out")}
# KDA linear attention beside latent attention: its own region ``kda``,
# the latent attention under ``attn``, dense and SwiGLU experts under
# ``mlp``
BAILING = "bailing_hybrid_tiny-remat"
INNER_BAILING = {
    "attn": INNER["attn"],
    "mlp": ("ln", "up", "down", "router", "dispatch", "experts", "combine",
            "shared"),
    "kda": ("ln", "qkv", "conv", "gate", "scan", "out_norm", "out")}
OWN_REGION = {NEMOTRON: "ssm", BAILING: "kda"}
CHILDREN = ("TrainStep.prepare", "TrainStep.launch", "TrainStep.commit")
# instructions of the compiled step (tiny sizes, CPU) whose op_name is
# under no region: the layer scan's slicing, AMP casts, the gradients'
# stacking.  What was reached (0.244, 0.176, 0.225) with a fifth of
# room; dropping ``mlp`` alone takes gpt_tiny to 0.43
UNSCOPED_LIMIT = {"gpt_tiny-TrainStep": 0.29, "bert_tiny-remat": 0.21,
                  "gpt_tiny-ShardedTrainStep-zero1-dp2": 0.27,
                  # per-type stacks sliced once a layer, AMP casts, the
                  # gradients put back into their stacks: 0.135
                  NEMOTRON: 0.165,
                  # stacks sliced a layer, AMP casts, the gradients put
                  # back: 0.081, with a fifth of room
                  BAILING: 0.097}


def _gpt_batch(rng):
    ids = rng.integers(0, 256, (4, 32))
    return [ids, ids]


def _bert_batch(rng):
    ids = rng.integers(0, 256, (4, 32))
    labels = np.where(rng.random((4, 32)) < 0.15, ids, -100)
    return [ids, labels, rng.integers(0, 2, (4,))]


def _build(case):
    """(step, batch) of one case on its own mesh, as the benchmark's
    cells build theirs; the mesh is global state and is put back by the
    ``built`` fixture."""
    rng = np.random.default_rng(0)
    paddle.seed(0)
    sharded = case.startswith("gpt_tiny-ShardedTrainStep")
    chips = 2 if sharded else 1
    mesh = set_mesh(make_mesh({"dp": chips}, devices=jax.devices()[:chips]))
    cls, kwargs = TrainStep, {}
    if sharded:
        cls, kwargs = ShardedTrainStep, {"mesh": mesh, "sharding_stage": 1}
    if case == "bert_tiny-remat":
        model, loss = Bert(bert_tiny(remat=True)), bert_pretrain_loss
        arrays = _bert_batch(rng)
    elif case == NEMOTRON:
        model, loss = NemotronH(nemotron_h_tiny(remat=True)), nemotron_h_loss
        arrays = _gpt_batch(rng)
    elif case == BAILING:
        model = BailingHybrid(bailing_hybrid_tiny(remat=True))
        loss, arrays = bailing_hybrid_loss, _gpt_batch(rng)
    else:
        model, loss = GPT(gpt_tiny(remat=False)), gpt_loss
        arrays = _gpt_batch(rng)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = cls(model, loss, opt, amp_level="O2", **kwargs)
    return step, [paddle.to_tensor(a) for a in arrays]


@pytest.fixture(scope="module", params=sorted(UNSCOPED_LIMIT))
def built(request):
    mesh = get_mesh()
    try:
        yield (request.param, *_build(request.param))
    finally:
        set_mesh(mesh)


def _passes_of(path):
    """(pass, tokens) of one op_name path, as PERF.md section 3 reads
    it: a transform is a name followed by ``(``."""
    tokens = [t for t in re.split(r"[/()]", path) if t]
    if "rematted_computation" in tokens:
        return "recompute", tokens
    if "transpose(" in path:
        return "bwd", tokens
    return "fwd", tokens


def test_regions_in_the_compiled_step(built):
    case, step, batch = built
    assert np.isfinite(float(step(*batch)))
    paths = [p for p in re.findall(r'op_name="([^"]*)"',
                                   step.compiled_text())
             if p.startswith("jit(")]
    seen = {}                                  # (pass, region) -> inner
    outside = 0
    for path in paths:
        which, tokens = _passes_of(path)
        region = next((t for t in tokens if t in MODEL_REGIONS
                       + ("ssm", "kda", "optimizer")), None)
        outside += region is None
        if region is not None:
            seen.setdefault((which, region), set()).update(tokens)
    for region in MODEL_REGIONS:
        assert ("fwd", region) in seen and ("bwd", region) in seen, region
    for region, inner in {NEMOTRON: INNER_NEMOTRON,
                          BAILING: INNER_BAILING}.get(case, INNER).items():
        # post-LN BERT has no LayerNorm before the projections, but an
        # ``ln`` after each block all the same
        assert set(inner) <= seen[("fwd", region)], (region, inner)
        assert set(inner) <= seen[("bwd", region)], (region, inner)
    for own in ("ssm", "kda"):
        assert (("fwd", own) in seen) == (OWN_REGION.get(case) == own)
    assert ("fwd", "optimizer") in seen          # no jvp, no transpose
    assert ("bwd", "optimizer") not in seen
    remat = case in ("bert_tiny-remat", NEMOTRON, BAILING)
    for region in ("attn", "mlp"):
        assert (("recompute", region) in seen) == remat, region
    assert ("recompute", "head_loss") not in seen
    share = outside / len(paths)
    assert share < UNSCOPED_LIMIT[case], (share, outside, len(paths))


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("TrainStep"):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return {k: sorted(v, key=lambda ev: ev[0]) for k, v in out.items()}


def test_a_bare_jax_trace_holds_the_step_and_its_three_children(
        built, tmp_path):
    _, step, batch = built
    float(step(*batch))                          # compile outside
    first = int(step.optimizer._global_step)
    assert not profiler.is_profiling()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # the python tracer costs 10 s
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            loss = step(*batch)
        float(loss)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    assert set(events) == {"TrainStep", *CHILDREN}
    assert all(len(v) == 3 for v in events.values()), {
        k: len(v) for k, v in events.items()}
    for i, (lo, hi, stats) in enumerate(events["TrainStep"]):
        assert int(stats["step"]) == first + i
        inside = [events[name][i][:2] for name in CHILDREN]
        assert lo <= inside[0][0] and inside[-1][1] <= hi
        # prepare, launch, commit: one after the other
        assert all(a[1] <= b[0] for a, b in zip(inside, inside[1:]))


def test_one_interval_one_record(built, tmp_path):
    """JSONL tracer and paddle's profiler both on: the step reaches each
    once (``Span.__enter__`` used to open a second profiler row)."""
    _, step, batch = built
    float(step(*batch))
    tracer.enable(str(tmp_path / "spans"), label="scopes")
    profiler.start_profiler("CPU")
    try:
        float(step(*batch))
        rows = {name: agg[0] for name, agg in profiler._events.items()}
    finally:
        profiler.stop_profiler(profile_path=str(tmp_path / "chrome.json"))
        path = tracer.path()
        tracer.disable()
    assert rows.get("TrainStep") == 1 and "train.step" not in rows
    assert all(rows.get(name) == 1 for name in CHILDREN), rows
    with open(path) as f:
        spans = [rec["name"] for rec in map(json.loads, f)
                 if rec.get("kind") == "span"]
    assert spans.count("train.step") == 1 and "TrainStep" not in spans


def test_jit_compiles_total_sees_jaxs_own_retrace():
    """The benchmark's ``setup_compiles`` = 2: the second call of a
    process hits ``TrainStep._cache`` and compiles all the same, because
    an input's sharding changed.  ``jit_compiles_total`` counts it."""
    monitor.reset_all_stats()
    health.reset()
    paddle.seed(0)
    net = paddle.nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    step = TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _, **kw: compiled.append(event)
        if event == health.BACKEND_COMPILE_EVENT else None)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 4)).astype(np.float32)
    y = rng.standard_normal((8, 2)).astype(np.float32)
    step(paddle.to_tensor(x), paddle.to_tensor(y))
    step(paddle.to_tensor(x), paddle.to_tensor(y))
    assert monitor.get_stat("jit_compiles_total") == 1      # miss: once
    before = len(compiled)
    rows = NamedSharding(make_mesh({"dp": 2}, devices=jax.devices()[:2]),
                         PartitionSpec("dp"))
    step(paddle.to_tensor(jax.device_put(x, rows)),
         paddle.to_tensor(jax.device_put(y, rows)))
    assert len(step._cache) == 1                 # our signature: a hit
    assert len(compiled) - before >= 1           # jax compiled anyway
    assert monitor.get_stat("jit_compiles_total") == 1 + len(compiled) \
        - before
    report = health.compile_report()["TrainStep"]
    assert report["last_cause"] == "jax_retrace"
    assert report["calls"] == 3 and report["compiles"] == 2
    assert monitor.get_stat("jit_cache_hits_total") == 2


# ---------------------------------------------------------------------------
# set-up inside the program: compile phases booked to step calls, and the
# parameters' draw
# ---------------------------------------------------------------------------

def _linear_step(seed=0):
    paddle.seed(seed)
    net = paddle.nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    return TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)


def _linear_batch():
    rng = np.random.default_rng(0)
    return (paddle.to_tensor(rng.standard_normal((8, 4)).astype(np.float32)),
            paddle.to_tensor(rng.standard_normal((8, 2)).astype(np.float32)))


class _Spans:
    """jax's compile-phase spans while open, as (event, start, end)."""

    def __enter__(self):
        self.spans = []
        self._fn = lambda event, a, b, **_: self.spans.append((event, a, b))
        jax.monitoring.register_event_time_span_listener(self._fn)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self._fn)
        return False

    def of(self, event):
        return [(a, b) for e, a, b in self.spans if e == event]


def _union(intervals):
    total, end = 0.0, -np.inf
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def test_nested_traces_are_each_counted_and_their_time_once():
    health.reset()
    with health.step_call("nested"):
        pass                                  # the listener is in place

    @jax.jit
    def inner_a(x):
        return jax.numpy.sin(x) * 2.0

    @jax.jit
    def inner_b(x):
        return jax.numpy.cos(x) + 1.0

    @jax.jit
    def outer(x):
        return inner_a(x) + inner_b(x)

    with _Spans() as seen, health.step_call("nested"):
        outer(np.ones(5, np.float32)).block_until_ready()
    traces = seen.of(health.TRACE_EVENT)
    report = health.compile_report()["nested"]
    assert len(traces) >= 3 and report["traces"] == len(traces)
    # the outer trace holds both inner ones: their time counts once
    assert report["trace_s"] == pytest.approx(_union(traces), abs=2e-6)
    assert report["trace_s"] < sum(b - a for a, b in traces)
    assert report["lower_s"] > 0 and report["backend_s"] > 0
    # no persistent cache here: every backend compile is cold
    assert report["cold_compile_s"] == report["backend_s"]


def test_compiles_outside_a_step_call_are_booked_to_no_step_site():
    health.reset()
    monitor.reset_stat("jit_traces_total")
    step = _linear_step()
    x, y = _linear_batch()
    step(x, y)
    booked = health.compile_report()["TrainStep"]
    assert booked["traces"] > 0
    with _Spans() as seen:
        jax.jit(lambda v: v * 3.0 + 1.0)(np.ones(11, np.float32))
        (jax.numpy.ones((13, 3)) * 7.0).block_until_ready()   # eager
    assert seen.of(health.TRACE_EVENT) and seen.of(
        health.BACKEND_COMPILE_EVENT)
    report = health.compile_report()
    assert report["TrainStep"] == booked
    assert all(site["traces"] == 0 for name, site in report.items()
               if name != "TrainStep")
    assert monitor.get_stat("jit_traces_total") == booked["traces"]


def test_a_second_step_on_the_same_shapes_reads_the_persistent_cache(
        tmp_path):
    from jax._src import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        x, y = _linear_batch()
        readings = []
        for seed in (0, 1):
            health.reset()
            step = _linear_step(seed)
            step(x, y)
            step(x, y)
            readings.append(health.compile_report()["TrainStep"])
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    first, second = readings
    assert first["cold_compile_s"] > 0
    assert second["backend_s"] > 0 and second["cold_compile_s"] == 0
    assert second["traces"] > 0


@pytest.mark.parametrize("build", [
    lambda: GPT(gpt_tiny()), lambda: Bert(bert_tiny()),
    lambda: NemotronH(nemotron_h_tiny()),
    lambda: BailingHybrid(bailing_hybrid_tiny())],
    ids=["GPT", "Bert", "NemotronH", "BailingHybrid"])
def test_model_init_is_one_span_a_model_and_its_seconds_a_counter(
        build, tmp_path):
    before = monitor.get_stat("model_init_seconds_total")
    profiler.start_profiler("CPU")
    try:
        build()
        build()
        calls, total = profiler._events["model.init"][:2]
    finally:
        profiler.stop_profiler(profile_path=str(tmp_path / "chrome.json"))
    assert calls == 2
    counted = monitor.get_stat("model_init_seconds_total") - before
    # the counter's clock stops a moment after the span's
    assert total <= counted <= total + 0.05


def test_compile_phases_nest_under_the_steps_spans(tmp_path):
    health.reset()
    step = _linear_step(2)
    x, y = _linear_batch()
    path = str(tmp_path / "chrome.json")
    profiler.start_profiler("CPU")
    try:
        step(x, y)
    finally:
        profiler.stop_profiler(profile_path=path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    spans = [(e["ts"], e["ts"] + e["dur"]) for e in events
             if e["name"] in CHILDREN]
    rows = [e for e in events if e["name"].startswith("jit.")]
    names = {e["name"] for e in rows}
    assert {"jit.trace", "jit.lower", "jit.backend_compile"} <= names
    assert all(e["args"]["fun_name"] for e in rows
               if e["name"] != "jit.compile")
    slack = 50.0                  # us: two clocks read one after the other
    for e in rows:
        assert any(a - slack <= e["ts"] and e["ts"] + e["dur"] <= b + slack
                   for a, b in spans), e


def test_train_step_ms_is_one_calls_start_to_the_next():
    import time
    hist = monitor.get_histogram("train_step_ms")
    hist.reset()
    step = _linear_step(3)
    x, y = _linear_batch()
    step(x, y)
    assert hist.count == 0           # the first call observes nothing
    time.sleep(0.2)
    step(x, y)
    assert hist.count == 1 and hist.max >= 200.0
