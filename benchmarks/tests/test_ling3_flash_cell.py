"""The Ling-3.0-flash cell (``ling3_flash.train_b1_s8192``): its three
per-layer metrics list it alone, their readers on a hand-made path table
and where the program names no ``kda`` block, ``flops_per_token`` against
a hand count, and the configuration file against its own statement of
the cut."""
import importlib
import json
import os

import pytest

from benchmarks.harness import measure
from benchmarks.tests import rules

ROOT = measure.ROOT
CELL = "ling3_flash.train_b1_s8192"
NEW = ("kda_ms_per_step", "kda_scan_ms_per_step", "routed_swiglu_ms_per_step")
BENCH = rules.load(ROOT)
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "ling3_flash.json")) as f:
    CONFIG = json.load(f)


def test_the_new_metrics_list_the_new_cell_alone():
    rules.metrics_read_in(BENCH, NEW, [CELL], "tokens_per_s", "model")


def test_the_cell_reads_recomputation_and_the_flash_kernels_time():
    """Its per-layer checkpoints recompute, and MLA runs the flash
    kernels; ``flash_roofline`` would count the zero-padded columns of v
    as work, and waits for kernels that take a width of v apart."""
    listed = {m["name"]: m.get("workloads") for m in BENCH["per_layer"]}
    assert CELL in listed["recompute_ms_per_step"]
    assert CELL in listed["flash_ms_per_step"]
    assert CELL not in listed["flash_roofline"]
    reference = importlib.import_module("benchmarks.reference.ling3_flash")
    assert reference.attention_shape(CONFIG["sizes"], 1, 8192) \
        == (1, 4, 8192, 192, True, 1)
    sizes = {**CONFIG["sizes"], **CONFIG["rehearse_sizes"]}
    assert reference.attention_shape(sizes, 1, 256) \
        == (1, 2, 256, 24, True, 1)


def test_the_reference_in_bf16_computes_in_bf16():
    """The control of ``TOLERANCE_REL`` (``tools/ling3_check.py``): the
    same walk with every array in bfloat16, the KDA state too."""
    import jax.numpy as jnp
    import numpy as np
    reference = importlib.import_module("benchmarks.reference.ling3_flash")
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((32, 2, 16)), jnp.bfloat16)
               for _ in range(3))
    log_alpha = jnp.full((32, 2, 16), -0.1, jnp.bfloat16)
    beta = jnp.full((32, 2), 0.5, jnp.bfloat16)
    assert reference.kda_recurrence(q, k, v, log_alpha, beta).dtype \
        == jnp.bfloat16
    assert reference._rotate(q, 6e6).dtype == jnp.bfloat16
    marks = reference.expert_choice(
        jnp.asarray(rng.random((8, 16)), jnp.bfloat16), 4,
        {"n_group": 4, "topk_group": 2})
    assert marks.dtype == jnp.bfloat16
    assert float(marks.sum()) == 32


ROWS = [   # (scope path, seconds a step), as jax writes the paths
    ("jit(step)/jvp(kda)/scan/while/body/dot_general", 1.0),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/kda/"
     "scan/exp", 2.0),
    ("jit(step)/jvp(kda)/conv/neg", 4.0),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/kda/out_norm/mul", 8.0),
    ("jit(step)/jvp(kda)/add", 16.0),                    # the residual
    ("jit(step)/jvp(mlp)/router/top_k", 32.0),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/mlp/experts/group_rows",
     64.0),
    ("jit(step)/jvp(mlp)/shared/dot_general", 128.0),
    ("jit(step)/jvp(mlp)/up/dot_general", 256.0),        # the dense MLP
    ("jit(step)/jvp(attn)/core/flash_fwd", 512.0)]


class _Trace:
    def __init__(self, rows):
        self.inner_scope_rows = rows


def test_the_readers_on_a_hand_made_path_table():
    read = {name: measure._reader("layer_metrics", name).reduce(
        _Trace(ROWS), {}) for name in NEW}
    assert read == {"kda_ms_per_step": 31000.0,
                    "kda_scan_ms_per_step": 3000.0,
                    "routed_swiglu_ms_per_step": 96000.0}


def test_the_readers_return_nothing_where_the_program_names_no_kda():
    """As on the parent of this cell: no ``kda`` region, no trace file."""
    for name in NEW[:2]:
        assert measure._reader("layer_metrics", name).reduce(
            _Trace(ROWS[5:]), {}) is None
    for name in NEW:
        assert measure._reader("layer_metrics", name).reduce(
            _Trace(None), {}) is None


def test_flops_per_token_against_a_hand_count_at_the_rehearsal_sizes():
    reference = importlib.import_module("benchmarks.reference.ling3_flash")
    sizes = {**CONFIG["sizes"], **CONFIG["rehearse_sizes"]}
    # layers: 0 KDA + dense, 1-2 KDA + experts, 3 MLA + experts
    # KDA: qkv 64 x 96 + alpha, gate 2 x 64 x 32 + beta 64 x 2 + out
    #      32 x 64 = 12416; chunks of 32, 2 heads of 16: 2 x (16 x (3 x
    #      16 + 2 x 16) + 3 x 16 x 16) = 4096; three: 49536
    # MLA: q 64 x 48 + kv_a 64 x 40 + kv_b 32 x 64 + gate 64 x 2 + o 32
    #      x 64 = 9856; causal core 256 / 2 x 2 x (24 + 16) = 10240
    # dense: 3 x 64 x 96 = 18432
    # experts: router 16 x 64 + 4 x 4 / 16 = 1 expert of 3 x 64 x 48 +
    #      shared 3 x 64 x 48 = 19456; three: 58368
    # head 64 x 256 = 16384: 162816 x 6
    assert reference.flops_per_token(sizes, 256) == 976896
    # and at the cell's sizes: about 1.18 GFLOP of weights and KDA chunk
    # work a token, and the causal MLA core's 6 x 8192 / 2 x 4 x 448
    full = reference.flops_per_token(CONFIG["sizes"], 8192)
    assert full - 6.0 * 4096 * 4 * 448 == pytest.approx(6 * 197.27e6,
                                                        rel=1e-3)


PUBLISHED_WIDTHS = {
    "hidden_size": 2560, "head_dim": 128, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "qk_head_dim": 192,
    "v_head_dim": 128, "intermediate_size": 6144, "moe_intermediate_size": 768,
    "moe_shared_expert_intermediate_size": 768, "num_experts_per_tok": 8,
    "n_group": 8, "topk_group": 4, "routed_scaling_factor": 2.5,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5, "layer_group_size": 6,
    "rope_theta": 6000000, "rms_norm_eps": 1e-06, "norm_topk_prob": True}


def test_the_configuration_states_its_cut_and_keeps_every_width():
    entry = next(c for c in BENCH["configs"] if c["name"] == "ling3_flash")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"] == list(CONFIG["published"])
    for key, value in PUBLISHED_WIDTHS.items():
        assert CONFIG[key] == value and key not in CONFIG["reduced"], key
    for key in CONFIG["reduced"]:
        assert CONFIG[key] != CONFIG["published"][key], key
    sizes = CONFIG["sizes"]
    for key, value in sizes.items():
        if key in CONFIG:
            assert CONFIG[key] == value, key
    assert sizes["router_width"] == CONFIG["published"]["num_experts"]
    assert sizes["n_routed_experts"] == CONFIG["num_experts"]
    # the floors of the model-configs guide, section 4
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= CONFIG["published"]["vocab_size"]
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert {"deployment", "assumed", "sizing"} <= set(CONFIG)
    assert CONFIG["model_kwargs"]["remat"] is True
    rules.router_is_the_references("ling3_flash", CONFIG)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "train_b1_s8192.json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seq"], traffic["resident_batches"]) \
        == (1, 8192, 256)
