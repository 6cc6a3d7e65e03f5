"""What ``BENCHMARK.json`` and the files it names keep, as functions of
the benchmark's dict and of the tree that holds it: the tests hold this
tree to them, and ``test_adding_a_configuration.py`` a copy of it with a
configuration added by new files and new entries alone.  Each raises
``AssertionError`` at the first rule broken; none names a configuration
or a cell, or counts on a place in a list."""
import glob
import json
import os
import re

import jax.numpy as jnp
import numpy as np

from benchmarks.harness import measure

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# where the rule that moves a router's bias is published (DeepSeek-V3
# technical report, 2.1.2, "Auxiliary-Loss-Free Load Balancing")
RULE_SOURCES = ("DeepSeek-V3", "auxiliary-loss-free")
# the rate at which a solved bias was shown to keep the loads inside a
# row tile to the end of a run (PERF.md section 6, PR 33)
SOLVE_HELD_AT_RATE = 1e-4
FLASH = ("flash_ms_per_step", "flash_roofline")


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def configurations(bench: dict, root: str) -> dict:
    """{name: the configuration file's contents}."""
    named = {}
    for entry in bench["configs"]:
        with open(os.path.join(root, entry["file"])) as f:
            named[entry["name"]] = json.load(f)
    return named


def drivers_rules(bench: dict, root: str) -> None:
    """The contract's limits on ``BENCHMARK.json`` and on the names of
    the files under its ``paths``."""
    cells = [w["name"] for w in bench["workloads"]]
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60 s, 24 x 180 s to compile,
    # 1200 s spare, inside 43200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert 1 <= len(c["why"]) <= 200
        assert c["file"].startswith("benchmarks/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 4)
    for dirpath, _, files in os.walk(os.path.join(root, "benchmarks")):
        if "__pycache__" in dirpath:
            continue
        for name in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", name), (dirpath, name)


def solves_follow_the_router(bench: dict, root: str) -> set:
    """A configuration names ``router_bias`` exactly when its ``sizes``
    route over experts (they name ``num_experts_per_tok``); the key
    resolves to a callable solve, ``assumed.router_bias`` cites where the
    rule is published, and such a configuration trains no faster than the
    rate at which a solved bias was shown to hold, and its reference
    makes the choice its router states (``router_is_the_references``).
    Every file under ``benchmarks/configs`` is some configuration's.
    Returns the names of the configurations that route."""
    named = configurations(bench, root)
    routed = {name for name, config in named.items()
              if "num_experts_per_tok" in config["sizes"]}
    assert {name for name, config in named.items()
            if "router_bias" in config} == routed
    for name in routed:
        config = named[name]
        assert callable(measure.resolve(config["router_bias"])), name
        cited = config["assumed"]["router_bias"]
        assert any(source in cited for source in RULE_SOURCES), name
        assert config["optimizer"]["kwargs"]["learning_rate"] \
            <= SOLVE_HELD_AT_RATE, name
        router_is_the_references(name, config)
    files = sorted(os.path.basename(p) for p in glob.glob(
        os.path.join(root, "benchmarks", "configs", "*.json")))
    assert files == sorted(os.path.basename(e["file"])
                           for e in bench["configs"])
    return routed


def router_is_the_references(name: str, config: dict,
                             reference=None) -> None:
    """A configuration whose router keeps a token to ``topk_group`` of
    ``n_group`` expert groups (``n_group`` above 1 in ``sizes``, or where
    they name none in the published numbers at the top level) resolves a
    reference that makes that choice itself, ``expert_choice(biased,
    top_k, sizes)``: the solve counts loads through it, and without it
    would balance the plain top-k, a rule neither side runs.  On seeded
    scores at the configuration's sizes it marks ``top_k`` experts a
    token, from at most ``topk_group`` groups of consecutive experts."""
    sizes = config["sizes"]
    n_group = sizes.get("n_group", config.get("n_group", 1))
    if n_group == 1:
        return
    reference = reference or measure.resolve(config["reference"])
    choice = getattr(reference, "expert_choice", None)
    assert callable(choice), (name, "routes by groups; its reference "
                              "names no expert_choice")
    topk_group = sizes.get("topk_group", config.get("topk_group", 1))
    experts, top_k = sizes["router_width"], sizes["num_experts_per_tok"]
    scores = 1 / (1 + np.exp(-np.random.default_rng(0).standard_normal(
        (64, experts))))
    marks = np.asarray(choice(jnp.asarray(scores, jnp.float32), top_k,
                              sizes=sizes))
    assert marks.shape == scores.shape, name
    assert set(np.unique(marks)) <= {0, 1}, name
    assert (marks.sum(-1) == top_k).all(), name
    per = experts // n_group
    assert all(len(set(np.flatnonzero(row) // per)) <= topk_group
               for row in marks), (name, "chooses outside its groups")


def metrics_read_in(bench: dict, names, cells, moves: str,
                    layer: str) -> None:
    """Each of ``names`` is a per-layer metric that lists exactly
    ``cells``, moves ``moves`` and belongs to ``layer``; where it stands
    in the list is no rule."""
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in names:
        assert name in per_layer, name
        assert per_layer[name]["workloads"] == list(cells), name
        assert per_layer[name]["moves"] == moves, name
        assert per_layer[name]["layer"] == layer, name


def flash_lists(bench: dict, root: str, cells) -> None:
    """The flash metrics list at least ``cells``, and every cell they list
    has a reference that gives its attention calls' shape, from which
    ``flash_roofline`` counts the kernels' operations and bytes."""
    config_of = {w["name"]: w["config"] for w in bench["workloads"]}
    named = configurations(bench, root)
    for name in FLASH:
        listed = next(m for m in bench["per_layer"]
                      if m["name"] == name)["workloads"]
        assert set(cells) <= set(listed), name
        for cell in listed:
            reference = measure.resolve(named[config_of[cell]]["reference"])
            assert callable(getattr(reference, "attention_shape", None)), \
                (name, cell)


def cell_resolves(cell: str) -> dict:
    """``load_cell`` finds the cell's files, everything they name as
    ``module:attr`` imports, and each of the cell's metrics has a reader
    under its name.  Returns the loaded cell."""
    loaded = measure.load_cell(cell, rehearse=False)
    config, traffic = loaded["config"], loaded["traffic"]
    for name in (config["model"], config["model_config"], config["loss"],
                 config["inputs"], config["optimizer"]["class"],
                 traffic["step"]["class"]):
        assert callable(measure.resolve(name)), name
    reference = measure.resolve(config["reference"])
    assert callable(reference.loss) and callable(reference.flops_per_token)
    assert 0 < reference.TOLERANCE_REL <= 5e-4
    assert loaded["end_to_end"] and loaded["per_layer"]
    for folder in ("end_to_end", "per_layer"):
        for m in loaded[folder]:
            reader = measure._reader(
                {"per_layer": "layer_metrics"}.get(folder, folder),
                m["name"])
            assert callable(reader.reduce), m["name"]
    assert traffic["batch"] % traffic["chips"] == 0
    return loaded
