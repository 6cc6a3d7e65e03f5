"""The arrows of the architecture, written down.

The main path is ``benchmarks/run.py`` -> ``jit.TrainStep`` /
``parallel.sharded.ShardedTrainStep`` -> ``models/*`` ->
``nn/functional/*`` -> ``ops/pallas/*``.  Each of its modules may import
the ``paddle_tpu.framework`` modules listed for it below and none of the
planes beside the path (the run ledger, the collector, blame, incident
capture, the parameter server, the elastic agent, ``tools/``).  A PR that
wires a plane into the step has to edit this file, in view of its
reviewer.
"""
import ast
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FORBIDDEN = ("tools", "bench", "paddle_tpu.distributed.ps",
             "paddle_tpu.distributed.elastic",
             "paddle_tpu.framework.collector",
             "paddle_tpu.framework.runlog",
             "paddle_tpu.framework.blame",
             "paddle_tpu.framework.incident")

STEP = {"flags", "monitor", "health", "numerics", "observability"}
# jit/__init__.py is also the package's ``paddle.jit`` namespace: the
# static analysis entry (analyze), jit.save / jit.load (crypto, io) and
# the re-export of resilient.ResilientTrainStep, which is safety code (the
# NaN skip-and-restore wrapper around a step), not a plane
JIT = STEP | {"analysis", "crypto", "io", "resilient"}
LEAF = {"flags", "monitor"}

MAIN_PATH = {
    "paddle_tpu/jit/__init__.py": JIT,
    "paddle_tpu/parallel/sharded.py": STEP,
    "paddle_tpu/models/gpt.py": LEAF,
    "paddle_tpu/models/bert.py": LEAF,
    "paddle_tpu/models/nemotron_h.py": LEAF,
    "paddle_tpu/models/bailing_hybrid.py": LEAF,
    "paddle_tpu/nn/functional/moe.py": LEAF,
    "paddle_tpu/nn/functional/ssm.py": LEAF,
    "paddle_tpu/nn/functional/kda.py": LEAF,
    "paddle_tpu/nn/functional/rotary.py": LEAF,
    "paddle_tpu/ops/pallas/flash_attention.py": LEAF,
    "paddle_tpu/ops/pallas/grouped_matmul.py": LEAF,
    "paddle_tpu/ops/pallas/kda_carry.py": LEAF,
    "paddle_tpu/ops/pallas/common.py": LEAF,
}


def imported_modules(source: str, rel_path: str) -> set:
    """Every module name the source imports, function-level imports
    included; ``from a import b`` yields both ``a`` and ``a.b`` (``b`` may
    be a submodule), relative imports are resolved against ``rel_path``."""
    package = rel_path[:-len(".py")].split("/")[:-1]
    found = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package[:len(package) - (node.level - 1)]
                base = ".".join(up + ([base] if base else []))
            found.add(base)
            found.update(f"{base}.{alias.name}" for alias in node.names)
    return found


def violations(source: str, rel_path: str, allowed: set) -> list:
    bad = []
    for mod in sorted(imported_modules(source, rel_path)):
        if any(mod == f or mod.startswith(f + ".") for f in FORBIDDEN):
            bad.append(f"{mod}: a plane beside the main path")
        elif mod.startswith("paddle_tpu.framework.") and \
                mod.split(".")[2] not in allowed:
            bad.append(f"{mod}: not among {sorted(allowed)}")
    return bad


def _read(rel_path):
    with open(os.path.join(REPO, *rel_path.split("/"))) as f:
        return f.read()


@pytest.mark.parametrize("rel_path", sorted(MAIN_PATH))
def test_main_path_imports_no_plane(rel_path):
    assert violations(_read(rel_path), rel_path, MAIN_PATH[rel_path]) == []


@pytest.mark.parametrize("line", [
    "from paddle_tpu.framework import collector",
    "from paddle_tpu.framework.runlog import RunLedger",
    "import tools.perf_report",
    "from ..distributed.ps import PSTrainStep",
])
def test_the_check_sees_a_plane_wired_into_the_step(line):
    rel_path = "paddle_tpu/jit/__init__.py"
    source = f"{_read(rel_path)}\ndef _wired():\n    {line}\n"
    assert violations(source, rel_path, MAIN_PATH[rel_path])


def test_a_train_step_imports_no_framework_module():
    # what ``import paddle_tpu`` loads of the framework is all a step
    # needs: building and calling one adds nothing to the process
    code = """
import sys
import numpy as np
import paddle_tpu as paddle
before = {m for m in sys.modules if m.startswith("paddle_tpu.framework")}
net = paddle.nn.Linear(4, 2)
opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
step = paddle.jit.TrainStep(
    net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
x = paddle.to_tensor(np.ones((2, 4), np.float32))
y = paddle.to_tensor(np.ones((2, 2), np.float32))
step(x, y)
step(x, y)
after = {m for m in sys.modules if m.startswith("paddle_tpu.framework")}
assert after == before, sorted(after - before)
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
