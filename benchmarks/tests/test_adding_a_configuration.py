"""A configuration is added by new files and new entries alone (ISSUE 38).

A copy of this tree's ``benchmarks/`` and ``BENCHMARK.json`` gains a probe
expert configuration: the hybrid's file under a new name, naming the
solve, with a reference of its own that gives its attention calls'
shape; one cell of it, appended last;
one per-layer metric, appended last, whose reader reads a block that
``inner_regions.json`` does not list through inner names of its own; and
the probe cell in the flash metrics' lists.  No file of the copy is
edited but ``BENCHMARK.json``, and that only gains entries.  In the copy,
in a process of its own: the rules of ``rules.py`` hold, ``load_cell``
resolves every cell, the new reader reads a hand-made path table and
raises nothing where the program names no such block, and the probe cell
rehearses ``correct`` with its solved bias.  (The probe keeps the
hybrid's plain top-k: under ``n_group`` 2 its reference would route
otherwise than its solve counts, which ``rules.router_is_the_references``
refuses; ``test_router_bias.py`` shows it.)"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import measure
from benchmarks.inputs import balanced_router_bias as brb
from benchmarks.tests import rules

ROOT = measure.ROOT
HYBRID = "nemotron3_super_120b"
HYBRID_CELL = HYBRID + ".train_b1_s4096"
HYBRID_METRICS = ("ssm_ms_per_step", "ssm_scan_ms_per_step",
                  "moe_routed_ms_per_step", "moe_shared_ms_per_step",
                  "moe_rows_computed_per_token")
FLASH_CELLS = ("gpt2_345m.train_b8_s1024", "gpt2_345m.zero1_dp4_b32_s1024",
               "bert_base.pretrain_b32_s512")
PROBE = "contract_test_expert"
PROBE_CELL = PROBE + ".train_b1_s4096"
PROBE_METRIC = "contract_test_kda_scan_ms_per_step"

PROBE_REFERENCE = '''"""The hybrid's reference, and its attention calls' shape."""
from benchmarks.reference.nemotron3_super_120b import (  # noqa: F401
    TOLERANCE_REL, flops_per_token, loss)


def attention_shape(sizes, batch, seq):
    return (batch, sizes["num_attention_heads"], seq, sizes["head_dim"],
            True, sizes["hybrid_override_pattern"].count("*"))
'''
PROBE_READER = '''"""Device time a step under ``scan`` of a block ``kda``, which
inner_regions.json does not list."""
from benchmarks.harness import inner_scopes

NAMES = ("ln", "qkv", "conv", "gate", "scan", "out")


def reduce(trace, run):
    return inner_scopes.ms_per_step(trace, run, "kda", ("scan",), NAMES)
'''
# run in the copy: its own harness, its own rules
CHECK = '''
import json, os, sys
from benchmarks.harness import measure
from benchmarks.tests import rules
hybrid_cell, hybrid_metrics, flash_cells, probe_cell, probe_metric = \\
    json.loads(sys.argv[1])
assert measure.ROOT == os.getcwd(), measure.ROOT
bench = rules.load(measure.ROOT)
rules.drivers_rules(bench, measure.ROOT)
routed = rules.solves_follow_the_router(bench, measure.ROOT)
rules.metrics_read_in(bench, hybrid_metrics, [hybrid_cell], "tokens_per_s",
                      "model")
rules.metrics_read_in(bench, [probe_metric], [probe_cell], "tokens_per_s",
                      "model")
rules.flash_lists(bench, measure.ROOT, flash_cells)
loaded = {w["name"]: rules.cell_resolves(w["name"])
          for w in bench["workloads"]}


class Trace:
    def __init__(self, rows):
        self.inner_scope_rows = rows


rows = [("jit(step)/jvp(kda)/scan/closed_call/while/body/mul", 1e-3),
        ("jit(step)/transpose(jvp(jvp()))/checkpoint/kda/scan/exp", 2e-3),
        ("jit(step)/jvp(kda)/conv/neg", 4e-3),
        ("jit(step)/jvp(ssm)/scan/mul", 8e-3)]
reader = measure._reader("layer_metrics", probe_metric)
print(json.dumps({
    "routed": sorted(routed), "reading": reader.reduce(Trace(rows), {}),
    "absent": reader.reduce(Trace(rows[3:]), {}),
    "per_layer": {c: [m["name"] for m in cell["per_layer"]]
                  for c, cell in loaded.items()}}))
'''


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The copy, with the probe added by new files and new entries."""
    root = str(tmp_path_factory.mktemp("added"))
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"),
               os.path.join(root, "paddle_tpu"))
    bench = rules.load(ROOT)
    entry = next(c for c in bench["configs"] if c["name"] == HYBRID)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config["reference"] = "benchmarks.reference." + PROBE
    files = {
        "benchmarks/configs/" + PROBE + ".json": json.dumps(config,
                                                            indent=2),
        "benchmarks/reference/" + PROBE + ".py": PROBE_REFERENCE,
        "benchmarks/layer_metrics/" + PROBE_METRIC + ".py": PROBE_READER}
    for name, text in files.items():
        assert not os.path.exists(os.path.join(root, name)), name
        with open(os.path.join(root, name), "w") as f:
            f.write(text)
    bench["configs"].append({
        **entry, "name": PROBE, "file": "benchmarks/configs/" + PROBE
        + ".json", "why": "the hybrid under a name of its own: a probe"})
    bench["workloads"].append({
        "name": PROBE_CELL, "config": PROBE, "traffic": "train_b1_s4096",
        "chips": 1, "why": "the hybrid's traffic on the probe"})
    bench["per_layer"].append({
        "name": PROBE_METRIC, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model", "moves": "tokens_per_s",
        "workloads": [PROBE_CELL]})
    for m in bench["per_layer"]:
        if m["name"] in rules.FLASH:
            m["workloads"].append(PROBE_CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


def _in(tree, *args):
    out = subprocess.run([sys.executable, *args], capture_output=True,
                         text=True, timeout=600, cwd=tree,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.strip().splitlines()


def test_the_rules_hold_and_every_cell_resolves_in_the_copy(tree):
    said = json.loads(_in(tree, "-c", CHECK, json.dumps([
        HYBRID_CELL, HYBRID_METRICS, FLASH_CELLS, PROBE_CELL,
        PROBE_METRIC]))[-1])
    assert {HYBRID, PROBE} <= set(said["routed"])
    assert PROBE_METRIC in said["per_layer"][PROBE_CELL]
    assert all(PROBE_METRIC not in names
               for cell, names in said["per_layer"].items()
               if cell != PROBE_CELL)
    # the block's own scan, every pass, and nothing where it is absent
    assert said["reading"] == pytest.approx(3.0)
    assert said["absent"] is None


def test_the_probe_cell_rehearses_correct_with_its_solved_bias(tree):
    lines = _in(tree, os.path.join("benchmarks", "run.py"), "--workload",
                PROBE_CELL, "--seed", "2147483693", "--seconds", "1",
                "--trace", "1", "--rehearse")
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    off = result["compared"]["router_load_off_mean"]
    assert off["holds"] and off["value"] <= off["limit"]
    routing = json.loads(next(ln for ln in lines if ln.startswith(
        "routing: "))[len("routing: "):])
    assert all(0 < layer["iterations"] < brb.CAP
               for layer in routing["layers"])
    # the program names no block ``kda``: its reader gives nothing, and
    # the run goes on
    said = json.loads(next(ln for ln in lines if ln.startswith(
        "rehearsal on the CPU")).split(": ", 1)[1])
    assert PROBE_METRIC not in said and "mlp_ms_per_step" in said
