"""The harness on the CPU at tiny sizes (``run.py --rehearse``), the
arithmetic of the window, the references against the program's models,
and ``BENCHMARK.json`` against the driver's rules."""
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import measure, roofline
from benchmarks.tests import rehearsal, rules

ROOT = measure.ROOT
BENCH = rules.load(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]


# -- BENCHMARK.json against the contract -------------------------------------

def test_benchmark_json_keeps_the_drivers_rules():
    rules.drivers_rules(BENCH, ROOT)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files_and_imports(cell):
    rules.cell_resolves(cell)


# -- the window's arithmetic --------------------------------------------------

def test_one_tenfold_outlier_leaves_the_median_step_unmoved():
    step, n = 0.150, 100
    stamps = list(np.arange(n + 3) * step)
    clean = measure.summarize(stamps, tokens_per_step=16384)
    stalled = list(stamps)
    for i in range(50, len(stalled)):
        stalled[i] += 9 * step                   # one reading of 1.5 s
    hit = measure.summarize(stalled, tokens_per_step=16384)
    assert clean["readings"] == hit["readings"] == n
    assert hit["max_s"] == pytest.approx(10 * step)
    assert hit["median_s"] == pytest.approx(clean["median_s"]) \
        == pytest.approx(step)
    # tokens over wall, the end-to-end rate, loses 8.3% to it
    assert hit["stall_share_pct"] == pytest.approx(100 * 9 / 109)
    assert clean["stall_share_pct"] == pytest.approx(0.0, abs=1e-9)


def test_the_rate_is_all_the_work_over_all_the_time():
    import time
    calls = []

    def step():
        calls.append(time.perf_counter())
        time.sleep(0.2 if len(calls) == 5 else 0.01)     # one stall
        return 1.0

    window = measure.run_loop(step, (), seconds=0.5)
    assert window["dispatched"] == len(calls) == len(window["losses"])
    # every call's time is inside the wall, the stall too
    assert window["wall_s"] >= 0.2 + 0.01 * (len(calls) - 1)
    assert window["wall_s"] >= window["stamps"][-1] - calls[0]
    assert window["dispatched"] / window["wall_s"] < 0.75 / 0.01


def test_the_first_two_readings_are_dropped():
    stamps = [0.0, 5.0, 9.0] + [9.0 + 0.1 * i for i in range(1, 9)]
    out = measure.summarize(stamps, tokens_per_step=10)
    assert out["readings"] == 8 and out["max_s"] == pytest.approx(0.1)


# -- peaks and FLOP counts ----------------------------------------------------

def test_unknown_device_kind_raises():
    assert roofline.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("TPU v4", "_source", ""):
        with pytest.raises(KeyError):
            roofline.peaks(kind)


def test_least_time_names_its_bound():
    peak = roofline.peaks("TPU v5 lite")
    assert roofline.least_time(197e12, 1.0, peak) == (1.0, "compute")
    assert roofline.least_time(1.0, 819e9, peak) == (1.0, "memory")


def test_flops_per_token_against_a_hand_count():
    gpt = importlib.import_module("benchmarks.reference.gpt2_345m")
    sizes = {"n_layer": 2, "n_embd": 8, "n_inner": 32, "vocab_size": 100}
    # a layer: qkv 8x24 + proj 8x8 + fc 8x32 + out 32x8 = 768 weights;
    # two layers 1536, head 100x8 = 800: 2336 x 6 = 14016; causal
    # attention 2 layers x 6 x S=16 x 8 = 1536
    assert gpt.flops_per_token(sizes, 16) == 14016 + 1536
    bert = importlib.import_module("benchmarks.reference.bert_base")
    sizes = {"num_hidden_layers": 2, "hidden_size": 8,
             "intermediate_size": 32, "vocab_size": 100}
    # as above plus the MLM transform 8x8 = 64: (1536 + 64 + 800) x 6 =
    # 14400; full attention 2 x 12 x 16 x 8 = 3072
    assert bert.flops_per_token(sizes, 16) == 14400 + 3072


@pytest.mark.parametrize("causal, flops", [(True, 215040), (False, 430080)])
def test_flash_flops_and_bytes_against_a_hand_count(causal, flops):
    reader = measure._reader("layer_metrics", "flash_roofline")
    # one matmul: 2 x (2 x 3) x 16 x 16 x 4 = 12288, causal 6144; 7 of
    # them in 5 layers = 430080, causal 215040.  A tensor: 2 x 3 x 16 x 4
    # x 2 B = 768 B, 12 passes = 9216; log-sum-exp 2 x 3 x 16 x 4 B = 384,
    # written once and read once = 768; (9216 + 768) x 5 = 49920
    assert reader.flash_flops_and_bytes(2, 3, 16, 4, causal, 5) \
        == (flops, 49920)


def test_bert_s512_flash_count_against_a_hand_count():
    """The non-causal call of ``bert_base.pretrain_b32_s512``: 12 layers
    of (32, 12, 512, 64), nothing halved."""
    bert = importlib.import_module("benchmarks.reference.bert_base")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "bert_base.json")) as f:
        sizes = json.load(f)["sizes"]
    shape = bert.attention_shape(sizes, 32, 512)
    assert shape == (32, 12, 512, 64, False, 12)
    reader = measure._reader("layer_metrics", "flash_roofline")
    flops, nbytes = reader.flash_flops_and_bytes(*shape)
    # a matmul 2 x 32 x 12 x 512 x 512 x 64 = 12,884,901,888; x 7 x 12
    assert flops == 12884901888 * 84 == 1082331758592
    # a tensor 32 x 12 x 512 x 64 x 2 B = 25,165,824; lse 786,432
    assert nbytes == 12 * (12 * 25165824 + 2 * 786432)
    # the reader on a trace whose three kernels took 28.97 ms a step (the
    # cell's, PR 31): 1.0823e12 / 197e12 = 5.494 ms, bound by compute
    class Trace:
        chips = [0]

        def per_step(self, chip, pattern):
            return 28.97e-3

    said = []
    share = reader.reduce(Trace(), {
        "reference": bert, "sizes": sizes, "say": said.append,
        "traffic": {"batch": 32, "seq": 512},
        "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}})
    assert share == pytest.approx(100 * 5.494 / 28.97, rel=1e-3)
    assert "bound by compute" in said[0]
    # and the cells the metric is read in include those whose step runs
    # the kernels: both GPT-2 cells and, since PR 31, BERT at S = 512;
    # every cell listed has a reference that gives the calls' shape
    rules.flash_lists(BENCH, ROOT, (
        "gpt2_345m.train_b8_s1024", "gpt2_345m.zero1_dp4_b32_s1024",
        "bert_base.pretrain_b32_s512"))


# -- the references against the program's models, float32, tiny ---------------

TINY = [("gpt2_345m", 4, 32), ("bert_base", 4, 32)]


def _program_loss_and_reference(config_name, batch, seq):
    import paddle_tpu as paddle
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    sizes = {**config["sizes"], **config["rehearse_sizes"]}
    model = measure.build_model(config, sizes, seed=5)
    arrays = measure.resolve(config["inputs"])(11, batch, seq, sizes)
    got = float(measure.resolve(config["loss"])(
        model, *[paddle.to_tensor(a) for a in arrays]))
    params = {n: p.data for n, p in model.named_parameters()}
    return (got, measure.resolve(config["reference"]), params, arrays,
            sizes)


@pytest.mark.parametrize("config_name, batch, seq", TINY)
def test_reference_agrees_with_the_model_in_float32(config_name, batch, seq):
    got, reference, params, arrays, sizes = _program_loss_and_reference(
        config_name, batch, seq)
    for block in (batch, 1):
        want = reference.loss(params, arrays, sizes, block)
        assert got == pytest.approx(want, rel=2e-5), (block, got, want)


def _one_layer_short(reference, params, monkeypatch):
    return {k: (v[:-1] if k in reference._LAYER else v)
            for k, v in params.items()}


def _no_causal_mask(reference, params, monkeypatch):
    import jax.numpy as jnp
    masked = reference._layer

    def unmasked(x, p, n_head, eps):         # loss() traces it afresh
        with monkeypatch.context() as m:
            m.setattr(reference.jnp, "tril", jnp.ones_like)
            return masked(x, p, n_head, eps)

    monkeypatch.setattr(reference, "_layer", unmasked)
    return params


@pytest.mark.parametrize("config_name, fault, seen", [
    ("gpt2_345m", _one_layer_short, True),
    ("gpt2_345m", _no_causal_mask, True),
    # post-LN: the last LayerNorm gives the logits the same statistics
    # whatever came before, and at initialisation the mean loss depends
    # on little else: PERF.md section 7, first open question
    ("bert_base", _one_layer_short, False)])
def test_what_the_reference_check_can_tell_apart(config_name, fault, seen,
                                                 monkeypatch):
    """The first loss against ``TOLERANCE_REL`` at the tiny size: the
    faithful reference lands well inside; a reference that differs from
    the model by ``fault`` lands outside if the check can see it."""
    got, reference, params, arrays, sizes = _program_loss_and_reference(
        config_name, 4, 32)

    def off_by(loss):
        return abs(got - loss) / abs(loss)

    assert off_by(reference.loss(params, arrays, sizes, 4)) \
        < reference.TOLERANCE_REL / 2
    faulty = off_by(reference.loss(fault(reference, params, monkeypatch),
                                   arrays, sizes, 4))
    assert (faulty > 2 * reference.TOLERANCE_REL) == seen, faulty
    assert seen or faulty < reference.TOLERANCE_REL


# -- the command itself, rehearsed --------------------------------------------

def _rehearsal(cell, trace):
    out = rehearsal.run(ROOT, cell, 3000000019, trace)
    assert out.returncode == 0, out.stderr[-3000:]
    return out


def _rehearse(cell, trace):
    return _rehearsal(cell, trace).stdout.strip().splitlines()


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_result_line(cell):
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    lines = _rehearse(cell, trace=1)
    result = json.loads(lines[-1])
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "compared"]
    for pair in result["compared"].values():
        assert set(pair) == {"value", "limit", "holds"} and pair["holds"]
    assert result["compared"]["first_loss_rel_diff"]["value"] \
        <= result["compared"]["first_loss_rel_diff"]["limit"]
    assert result["compared"]["last_loss_over_first"]["value"] < 1.0
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 10
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    # a rehearsal prints counts only: no device number under a metric's name
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert result["metrics"]["setup_compiles"]["value"] >= 1
    for name, value in result["metrics"].items():
        assert per_layer[name]["source"] == "program_counter"
        assert set(value) == {"value", "unit"}
    # before that filter, every metric the cell is held to and no other:
    # one without a ``workloads`` list is read in every cell
    said = json.loads(next(ln for ln in lines if ln.startswith(
        "rehearsal on the CPU")).split(": ", 1)[1])
    listed = {m["name"] for m in BENCH["per_layer"] if "workloads" in m}
    # (a share of a peak has no value here: a rehearsal has no peaks)
    assert set(per_layer) - listed - {"mfu"} <= set(said)
    assert set(said) <= {m["name"] for m in BENCH["per_layer"]
                         if cell in m.get("workloads", [cell])}
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(result["breakdown"]["device_ops"]) <= 10
    earlier = "\n".join(lines[:-1])
    for tag in ("set-up: ", "window: ", "correct: ", "trace: "):
        assert tag in earlier
    window = json.loads(next(ln for ln in lines if ln.startswith(
        "window: "))[len("window: "):])
    assert {"readings", "median_s", "q1_s", "q3_s", "max_s",
            "units_over_wall_per_s"} <= set(window)


def test_untraced_rehearsal_and_refusals():
    out = _rehearsal(CELLS[0], trace=0)
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    # the same numbers beside their limits end standard error
    last = out.stderr.strip().splitlines()[-len(result["compared"]):]
    assert [ln.split()[:2] for ln in last] == [
        ["compared:", name] for name in result["compared"]]
    assert result["metrics"] == {}           # all three are device numbers
    # without --rehearse there is no TPU here: no result line, exit != 0
    for args in (["--workload", CELLS[0]], ["--workload", "no.such_cell",
                                            "--rehearse"]):
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
             *args, "--seed", "1", "--seconds", "1", "--trace", "0"],
            capture_output=True, text=True, timeout=600, cwd=ROOT)
        assert out.returncode != 0 and "refused" in out.stderr
        assert not any(ln.startswith("{") for ln in out.stdout.splitlines())
