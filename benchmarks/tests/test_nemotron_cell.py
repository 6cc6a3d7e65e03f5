"""What PR 27 added to the yardstick: the reader of inner scopes on a
hand-made path table, the five new readers in a CPU rehearsal of the new
cell, ``flops_per_token`` against a hand count, and the configuration
file against its own statement of the cut.  Since PR 38: a region that
``inner_regions.json`` does not list, read through the names its caller
gives, and one known to neither, which reads None."""
import importlib
import json
import os

import pytest

from benchmarks.harness import inner_scopes, measure
from benchmarks.tests import rehearsal, rules

ROOT = measure.ROOT
CELL = "nemotron3_super_120b.train_b1_s4096"
NEW = ("ssm_ms_per_step", "ssm_scan_ms_per_step", "moe_routed_ms_per_step",
       "moe_shared_ms_per_step", "moe_rows_computed_per_token")
BENCH = rules.load(ROOT)
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nemotron3_super_120b.json")) as f:
    CONFIG = json.load(f)


# -- the reader of inner scopes -----------------------------------------------

ROWS = [   # (scope path, seconds a step), as jax writes the paths
    ("jit(step)/jvp(ssm)/scan/bnlgk,bngrpk->bnlgrp/dot_general", 1.0),
    ("jit(step)/jvp(ssm)/scan/closed_call/while/body/mul", 2.0),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/ssm/"
     "conv/jit(silu)/neg", 4.0),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/ssm/out/dot_general", 8.0),
    ("jit(step)/jvp(ssm)/add", 16.0),                    # the residual
    ("jit(step)/jvp(mlp)/router/...d,ed->...e/dot_general", 32.0),
    # a primitive named like another region's inner scope is none
    ("jit(step)/jvp(mlp)/router/scan/while/body/add", 64.0),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/mlp/shared/square", 128.0),
    ("jit(step)/jvp(mlp)/up/dot_general", 256.0),        # a dense MLP
    ("jit(step)/jvp(attn)/out/dot_general", 512.0),
    ("jit(step)/optimizer/mul", 1024.0),
    ("", 2048.0)]


def test_inner_scope_is_the_first_listed_token_after_the_region():
    assert inner_scopes.inner_of(ROWS[0][0], "ssm") == "scan"
    assert inner_scopes.inner_of(ROWS[2][0], "ssm") == "conv"
    assert inner_scopes.inner_of(ROWS[4][0], "ssm") == ""
    assert inner_scopes.inner_of(ROWS[6][0], "mlp") == "router"
    assert inner_scopes.inner_of(ROWS[6][0], "ssm") is None
    assert inner_scopes.inner_of(ROWS[9][0], "ssm") is None
    assert inner_scopes.inner_of("", "mlp") is None
    assert inner_scopes.inner_of(None, "mlp") is None


def test_sums_over_a_hand_made_path_table():
    assert inner_scopes.sum_inner(ROWS, "ssm") == 31.0
    assert inner_scopes.sum_inner(ROWS, "ssm", ("scan",)) == 3.0
    assert inner_scopes.sum_inner(ROWS, "ssm", ("conv", "out")) == 12.0
    assert inner_scopes.sum_inner(
        ROWS, "mlp", ("router", "dispatch", "experts", "combine")) == 96.0
    assert inner_scopes.sum_inner(
        ROWS, "mlp", ("shared", "latent_down", "latent_up")) == 128.0
    assert inner_scopes.sum_inner(ROWS, "mlp") == 480.0
    assert inner_scopes.sum_inner(ROWS[8:], "ssm") == 0.0


# a block the file does not list, with the inner names its reader passes
KDA = ("ln", "qkv", "conv", "gate", "scan", "out")
KDA_ROWS = [
    ("jit(step)/jvp(kda)/scan/closed_call/while/body/mul", 1.0),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/kda/"
     "conv/jit(silu)/neg", 2.0),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/kda/gate/exp", 4.0),
    ("jit(step)/jvp(kda)/add", 8.0),                     # the residual
    # a primitive named like another region's inner scope is none
    ("jit(step)/jvp(mlp)/router/scan/while/body/add", 16.0)]


def test_a_region_the_file_lacks_is_read_through_the_callers_names():
    assert "kda" not in inner_scopes.INNER
    rows = ROWS + KDA_ROWS
    assert inner_scopes.inner_of(KDA_ROWS[0][0], "kda", KDA) == "scan"
    assert inner_scopes.inner_of(KDA_ROWS[3][0], "kda", KDA) == ""
    assert inner_scopes.inner_of(KDA_ROWS[4][0], "kda", KDA) is None
    assert inner_scopes.sum_inner(rows, "kda", None, KDA) == 15.0
    assert inner_scopes.sum_inner(rows, "kda", ("scan",), KDA) == 1.0
    assert inner_scopes.sum_inner(rows, "kda", ("conv", "gate"), KDA) == 6.0
    assert inner_scopes.sum_inner(ROWS, "kda", None, KDA) == 0.0
    # known to neither the file nor the caller: None, and nothing raised
    assert inner_scopes.inner_of(KDA_ROWS[0][0], "kda") is None
    assert inner_scopes.sum_inner(rows, "kda") is None
    assert inner_scopes.sum_inner(rows, "kda", ("scan",)) is None

    class Trace:
        inner_scope_rows = rows

    assert inner_scopes.ms_per_step(Trace(), {}, "kda") is None
    assert inner_scopes.ms_per_step(Trace(), {}, "kda", ("gate",), KDA) \
        == 4000.0
    # the regions the file lists read as before, and a caller's names
    # take the place of the file's
    assert inner_scopes.sum_inner(rows, "ssm", ("scan",)) == 3.0
    assert inner_scopes.sum_inner(rows, "mlp", None) == 480.0 + 16.0
    assert inner_scopes.sum_inner(rows, "ssm", ("scan",), ("conv",)) == 0.0


def test_readers_return_nothing_where_the_program_names_no_such_scope():
    """As on the parent of PR 27: no ``ssm`` region, no counters."""
    class Trace:
        inner_scope_rows = ROWS[8:]

    for name in NEW[:2]:
        assert measure._reader("layer_metrics", name).reduce(
            Trace(), {}) is None
    Trace.inner_scope_rows = None                     # no trace file
    for name in NEW[:4]:
        assert measure._reader("layer_metrics", name).reduce(
            Trace(), {}) is None


# -- the new cell's metrics, rehearsed ----------------------------------------

def test_the_five_new_readers_return_a_number_in_the_rehearsal():
    out = rehearsal.run(ROOT, CELL, 4000000007, 1)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    said = json.loads(next(ln for ln in lines if ln.startswith(
        "rehearsal on the CPU")).split(": ", 1)[1])
    for name in NEW:
        assert said[name]["value"] > 0, name
    assert said["ssm_scan_ms_per_step"]["value"] \
        < said["ssm_ms_per_step"]["value"]
    assert said["moe_routed_ms_per_step"]["value"] \
        + said["moe_shared_ms_per_step"]["value"] \
        < said["mlp_ms_per_step"]["value"]
    # the dense mask: every held expert for every token
    held = CONFIG["rehearse_sizes"]["n_routed_experts"]
    assert said["moe_rows_computed_per_token"]["value"] == held
    # the one count among them is the only one a rehearsal may print
    result = json.loads(lines[-1])
    assert set(NEW) & set(result["metrics"]) == {NEW[4]}


def test_the_new_metrics_list_the_new_cell_alone():
    rules.metrics_read_in(BENCH, NEW, [CELL], "tokens_per_s", "model")


# -- flops_per_token ----------------------------------------------------------

def test_flops_per_token_against_a_hand_count_at_the_rehearsal_sizes():
    reference = importlib.import_module(
        "benchmarks.reference.nemotron3_super_120b")
    sizes = {**CONFIG["sizes"], **CONFIG["rehearse_sizes"]}
    # M: in_proj 64 x (32 + 32 + 2 x 16 + 4 = 100) + out 32 x 64 = 8448;
    #    SSD (16 + 32) x 64 / 2 within a chunk + 2 x 32 x 16 states = 2560
    # *: q, o 2 x 64 x 64 + k, v 2 x 64 x 16 = 10240
    # E: router 8 x 64 + latent 2 x 64 x 32 + 2 x 4 / 8 = 1 expert of
    #    2 x 32 x 48 + shared 2 x 64 x 96 = 512 + 4096 + 3072 + 12288
    # two of each, head 64 x 256: 22016 + 20480 + 39936 + 16384 = 98816
    # x 6 = 592896; causal attention 2 x 6 x 256 x 64 = 196608
    assert reference.flops_per_token(sizes, 256) == 592896 + 196608
    # and at the cell's sizes: about 2.54 GFLOP of weights a token
    full = reference.flops_per_token(CONFIG["sizes"], 4096)
    attention = 6.0 * 4096 * 512
    assert full - attention == pytest.approx(6 * 424.6e6, rel=2e-3)


# -- the configuration file ---------------------------------------------------

PUBLISHED_WIDTHS = {
    "hidden_size": 4096, "mamba_head_dim": 64, "ssm_state_size": 128,
    "conv_kernel": 4, "chunk_size": 128, "head_dim": 128,
    "moe_latent_size": 1024, "moe_intermediate_size": 2688,
    "moe_shared_expert_intermediate_size": 5376, "num_experts_per_tok": 22,
    "routed_scaling_factor": 5, "norm_topk_prob": True, "expand": 2,
    "intermediate_size": 2688}


def test_the_configuration_states_its_cut_and_keeps_every_width():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "nemotron3_super_120b")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"] == list(CONFIG["published"])
    for key, value in PUBLISHED_WIDTHS.items():
        assert CONFIG[key] == value and key not in CONFIG["reduced"], key
    sizes = CONFIG["sizes"]
    assert sizes["router_width"] == CONFIG["published"]["n_routed_experts"]
    # what is run is what the file states at its top level
    for key, value in sizes.items():
        if key in CONFIG:
            assert CONFIG[key] == value, key
    for key in CONFIG["reduced"]:
        assert CONFIG[key] != CONFIG["published"][key], key
    assert CONFIG["hybrid_override_pattern"] == \
        CONFIG["published"]["hybrid_override_pattern"][:11]
    assert len(CONFIG["hybrid_override_pattern"]) == \
        CONFIG["num_hidden_layers"]
    # the floors of the model-configs guide, section 4
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= CONFIG["published"]["vocab_size"]
    assert {"deployment", "assumed", "sizing"} <= set(CONFIG)
    assert CONFIG["model_kwargs"]["remat"] is True
