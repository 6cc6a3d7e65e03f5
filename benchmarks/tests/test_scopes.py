"""The reading of named regions and host spans (``harness/scopes.py``):
the path classifier on hand-worked paths, the protobuf reading and the
sums on a small synthetic trace, the guard against another run's file,
and the rehearsal printing the new metrics of a cell and no other."""
import json
import os

import pytest

from benchmarks.harness import measure, scopes, trace_reduce
from benchmarks.tests import rehearsal

ROOT = measure.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NEW = ("fwd_ms_per_step", "bwd_ms_per_step", "recompute_ms_per_step",
       "optimizer_ms_per_step", "attn_ms_per_step", "mlp_ms_per_step",
       "head_loss_ms_per_step", "unscoped_device_share", "host_prepare_ms",
       "host_commit_ms")
LAYER = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"


@pytest.mark.parametrize("path, want", [
    # jax 0.9.0, CPU, scan + checkpoint (ISSUE 25)
    ("jit(step)/jvp()/while/body/closed_call/attn/qkv/dot_general",
     ("fwd", "attn")),
    (LAYER + "rematted_computation/mlp/dot_general", ("recompute", "mlp")),
    (LAYER + "mlp/up/dot_general", ("bwd", "mlp")),
    # a scope entered outside any scan shows inside the transform
    ("jit(step)/jvp(head_loss)/reduce_sum", ("fwd", "head_loss")),
    ("jit(step)/transpose(jvp(head_loss))/mul", ("bwd", "head_loss")),
    ("jit(step)/jvp(embed)/jit(_var)/sub", ("fwd", "embed")),
    # the flash kernels: Mosaic calls under attn/core, through per_device
    ("jit(step)/jvp()/attn/core/shard_map/pallas_call", ("fwd", "attn")),
    ("jit(step)/transpose(jvp())/attn/core/shard_map/pallas_call",
     ("bwd", "attn")),
    # the primitive `transpose` is not the backward pass
    ("jit(step)/jvp()/while/body/closed_call/attn/qkv/transpose",
     ("fwd", "attn")),
    ("jit(step)/optimizer/transpose", ("optimizer", "optimizer")),
    ("jit(step)/optimizer/mul", ("optimizer", "optimizer")),
    ("jit(step)/grad_exchange/psum_scatter",
     ("grad_exchange", "grad_exchange")),
    # a collective the partitioner inserted carries the scope of the
    # operation it was inserted for
    (LAYER + "attn/qkv/dot_general", ("bwd", "attn")),
    # a pass without a region: the gradients' stacking across layers
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice",
     ("bwd", None)),
    # AMP's casts before the first region, the scan's slicing: forward
    ("jit(step)/jvp()/convert_element_type", ("fwd", None)),
    ("jit(step)/jvp()/while/body/dynamic_slice", ("fwd", None)),
    # neither: what XLA made itself (a copy-done has no path at all)
    ("reduce_sum", (None, None)),
    ("", (None, None)), (None, (None, None))])
def test_classify(path, want):
    assert scopes.classify(path) == want


# -- a synthetic file: protobuf wire format by hand ---------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _ld(number, payload):
    """One length-delimited field."""
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _instruction(name, opcode, path):
    return _ld(2, _ld(1, name) + _ld(2, opcode) + _varint(3 << 3) + _varint(9)
               + (_ld(7, _ld(1, "ignored") + _ld(2, path)) if path else b""))


def _space(programs):
    """An XSpace with a device plane and ``/host:metadata``, whose event
    metadata carry one ``Hlo Proto`` a program."""
    metadata = b""
    for i, (program, instructions) in enumerate(programs.items()):
        module = _ld(1, "jit_step") + _ld(3, _ld(1, "main") + b"".join(
            _instruction(*ins) for ins in instructions))
        stat = _varint(1 << 3) + _varint(1) + _ld(6, _ld(1, module))
        event = _varint(1 << 3) + _varint(i) + _ld(2, program) + _ld(5, stat)
        metadata += _ld(4, _varint(1 << 3) + _varint(i) + _ld(2, event))
    return (_ld(1, _ld(2, "/device:TPU:0") + _ld(4, b""))
            + _ld(1, _ld(2, "/host:metadata") + metadata))


PROGRAMS = {
    "jit_step(7)": [
        ("fusion.1", "fusion", "jit(step)/jvp()/attn/qkv/dot_general"),
        ("fusion.2", "fusion", "jit(step)/transpose(jvp())/attn/qkv/mul"),
        ("fusion.3", "fusion", LAYER + "rematted_computation/mlp/tanh"),
        ("fusion.4", "fusion", "jit(step)/optimizer/sub"),
        ("all-reduce.1", "all-reduce", "jit(step)/grad_exchange/psum"),
        ("copy.1", "copy", ""),
        ("while.1", "while", "jit(step)/jvp()/while"),
        ("flash_fwd", "custom-call", "jit(step)/jvp()/attn/core/pallas_call"),
    ],
    # the same instruction name in another program, another scope
    "jit_other(9)": [("fusion.1", "fusion", "jit(other)/mlp/add")]}


def test_hlo_paths_reads_every_program():
    paths = scopes.hlo_paths(_space(PROGRAMS))
    assert set(paths) == set(PROGRAMS)
    assert paths["jit_step(7)"]["fusion.3"] == (
        "fusion", LAYER + "rematted_computation/mlp/tanh")
    assert paths["jit_step(7)"]["copy.1"] == ("copy", "")
    assert paths["jit_other(9)"]["fusion.1"][1] == "jit(other)/mlp/add"


def test_reduce_ops_by_hand():
    """Two steps in [0, 20] ms.  A step, on the TPU's naming (an event
    is named by its HLO text): fusion.1 2.0 (fwd attn), flash_fwd 1.0
    (fwd attn), fusion.2 3.0 (bwd attn), fusion.3 1.5 (recompute mlp),
    fusion.4 0.5 (optimizer), all-reduce.1 0.25 (grad_exchange), copy.1
    0.75 (unscoped), and while.1 enclosing 4.0 (control flow: left
    out).  Sum 9.0 a step.  An operation that starts before the window
    counts for the part inside; one of another program has its own
    scope; one of no known program is unscoped."""
    ms = 1e-3
    paths = scopes.hlo_paths(_space(PROGRAMS))

    def text(name, opcode):
        return f"%{name} = bf16[8]{{0}} {opcode}(bf16[8]{{0}} %p)"

    ops = []
    for t in (0.0, 10.0):
        for name, opcode, start, dur in [
                ("fusion.1", "fusion", 0.0, 2.0),
                ("flash_fwd", "custom-call", 2.0, 1.0),
                ("fusion.2", "fusion", 3.0, 3.0),
                ("while.1", "while", 3.0, 4.0),
                ("fusion.3", "fusion", 6.0, 1.5),
                ("fusion.4", "fusion", 7.5, 0.5),
                ("all-reduce.1", "all-reduce", 8.0, 0.25),
                ("copy.1", "copy", 8.25, 0.75)]:
            ops.append(("jit_step(7)", name, text(name, opcode),
                        (t + start) * ms, (t + start + dur) * ms))
    ops.append(("jit_step(7)", "fusion.4", text("fusion.4", "fusion"),
                -1.0 * ms, 1.0 * ms))                # 1.0 of 2.0 inside
    ops.append(("jit_other(9)", "fusion.1", text("fusion.1", "fusion"),
                9.0 * ms, 9.5 * ms))                 # fwd mlp, 0.5
    ops.append(("", "fusion.1", text("fusion.1", "fusion"),
                19.0 * ms, 19.5 * ms))               # unscoped, 0.5
    out = scopes.reduce_ops(ops, paths, 0.0, 20.0 * ms, steps=2)
    by = {k: v / ms for k, v in out["by"].items()}
    assert by == pytest.approx({
        ("fwd", "attn"): 3.0, ("bwd", "attn"): 3.0,
        ("recompute", "mlp"): 1.5, ("optimizer", "optimizer"): 1.0,
        ("grad_exchange", "grad_exchange"): 0.25, ("fwd", "mlp"): 0.25,
        ("unscoped", None): 1.0})
    assert out["total"] / ms == pytest.approx(10.0) and out["scoped"]
    (label, scope), seconds = out["top"][0]
    assert (label, scope) == ("fusion_bf16_8",
                              "jit(step)/transpose(jvp())/attn/qkv/mul")
    assert seconds / ms == pytest.approx(3.0)

    trace = trace_reduce.Trace()
    trace.scope_table = out                  # as table() keeps it
    run = {"say": print}
    assert scopes.ms_per_step(trace, run, passes=("fwd",)) \
        == pytest.approx(3.25)
    assert scopes.ms_per_step(trace, run, regions=("attn",)) \
        == pytest.approx(6.0)
    assert scopes.ms_per_step(trace, run, regions=("head_loss",)) is None
    assert scopes.unscoped_share(trace, run) == pytest.approx(10.0)
    # a program without a single named region (the parent of PR 25)
    # gives no metric rather than "all of it unscoped"
    bare = scopes.reduce_ops(ops, {}, 0.0, 20.0 * ms, steps=2)
    assert not bare["scoped"] and bare["total"] / ms == pytest.approx(10.0)
    trace.scope_table = bare
    assert scopes.unscoped_share(trace, run) is None
    assert scopes.ms_per_step(trace, run, passes=("bwd",)) is None


# -- the guard: only this run's file ------------------------------------------

def _traced(trace_dir):
    """A real trace of three tiny steps, reduced as the harness does."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    @jax.jit
    def step(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ x)

    x = step(jnp.ones((64, 64)))
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(4):
            with TraceAnnotation(measure.STEP_SPAN), \
                    TraceAnnotation("TrainStep.prepare"):
                # fenced, so that a step's operations lie in its span
                x = step(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return trace_reduce.load(path, (measure.STEP_SPAN,), measure.STEP_SPAN,
                             1)


def test_another_runs_file_is_refused(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    said = []
    run = {"say": said.append}
    assert scopes.table(trace_reduce.Trace(), run) is None
    assert "no .xplane.pb" in said[-1]
    mine = _traced(str(tmp_path / ".bench_trace" / "cell_a"))
    got = scopes.table(mine, run)
    assert got["scoped"] and said[-1].startswith("scopes: {")
    assert scopes.ms_per_step(mine, run, regions=("mlp",)) > 0
    assert len(got["host"]["TrainStep.prepare"]) == 3   # one skipped
    assert scopes.host_span_ms(mine, run, "TrainStep.prepare") > 0
    assert scopes.host_span_ms(mine, run, "TrainStep.commit") is None
    # a later run writes its own file: the older Trace no longer
    # matches the newest file, and is not read against it
    _traced(str(tmp_path / ".bench_trace" / "cell_b"))
    del mine.scope_table
    mine.structure["/host:CPU"]["python"] += 1
    assert scopes.table(mine, run) is None
    assert "not this run's trace" in said[-1]


# -- the command itself, rehearsed --------------------------------------------

@pytest.mark.parametrize("cell", ["gpt2_345m.train_b8_s1024",
                                  "bert_base.pretrain_b128_s128"])
def test_rehearsal_prints_the_new_metrics_of_the_cell_and_no_other(cell):
    out = rehearsal.run(ROOT, cell, 2147483659, 1)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    said = json.loads(next(ln for ln in lines if ln.startswith(
        "rehearsal on the CPU")).split(": ", 1)[1])
    want = {m["name"] for m in BENCH["per_layer"] if m["name"] in NEW
            and cell in m.get("workloads", [cell])}
    assert len(want) == (10 if cell.startswith("bert_base") else 9)
    assert {name for name in said if name in NEW} == want
    value = {name: said[name]["value"] for name in want}
    table = json.loads(next(ln for ln in lines if ln.startswith(
        "scopes: {"))[len("scopes: "):])
    # the passes and what no name explains add up to all operations
    passes = sum(value.get(name, 0.0) for name in (
        "fwd_ms_per_step", "bwd_ms_per_step", "recompute_ms_per_step",
        "optimizer_ms_per_step"))
    unscoped = value["unscoped_device_share"] / 100 \
        * table["all_operations_ms"]
    assert passes + unscoped == pytest.approx(table["all_operations_ms"],
                                              rel=1e-3)
    spans = table["host_spans_median_ms_and_count"]
    assert set(spans) == set(scopes.HOST_SPANS)
    assert len({count for _, count in spans.values()}) == 1
    assert value["host_prepare_ms"] + value["host_commit_ms"] \
        <= said["host_dispatch_ms"]["value"]
    # a rehearsal prints no device number under a metric's name
    assert not set(json.loads(lines[-1])["metrics"]) & set(NEW)
