#!/usr/bin/env python
"""API signature freeze — the compat surface as a checked-in spec.

Reference roles: tools/print_signatures.py (walk a module tree, print
every public callable's argspec in sorted order) + paddle/fluid/API.spec
(the frozen file a CI diff guards).  An API change here must come with a
deliberate regeneration:

    python tools/print_signatures.py --update        # rewrite API.spec
    python tools/print_signatures.py --check         # exit 1 on drift

``tests/test_api_spec.py`` runs the check in the suite, so signature
drift — a renamed kwarg, a dropped default, a vanished fluid alias —
fails tests instead of silently breaking user code.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# signature walking never needs the accelerator; pin CPU before the
# paddle_tpu import so the tool runs even while a trainer holds the chip
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

SPEC_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "API.spec")

# The modules whose public names form the frozen surface.  Kept explicit —
# a new module must be added here (and the spec regenerated) to be guarded.
MODULES = [
    "paddle_tpu",
    "paddle_tpu.nn",
    "paddle_tpu.nn.functional",
    "paddle_tpu.nn.initializer",
    "paddle_tpu.optimizer",
    "paddle_tpu.optimizer.lr",
    "paddle_tpu.tensor",
    "paddle_tpu.io",
    "paddle_tpu.io.pipeline",
    "paddle_tpu.amp",
    "paddle_tpu.autograd",
    "paddle_tpu.jit",
    "paddle_tpu.static",
    "paddle_tpu.static.nn",
    "paddle_tpu.metric",
    "paddle_tpu.distribution",
    "paddle_tpu.distributed",
    "paddle_tpu.distributed.elastic",
    "paddle_tpu.distributed.checkpoint",
    "paddle_tpu.distributed.durable",
    "paddle_tpu.distributed.wire",
    "paddle_tpu.distributed.ps",
    "paddle_tpu.distributed.ps.service",
    "paddle_tpu.distributed.fleet",
    "paddle_tpu.vision",
    "paddle_tpu.vision.models",
    "paddle_tpu.vision.transforms",
    "paddle_tpu.vision.ops",
    "paddle_tpu.text",
    "paddle_tpu.hapi",
    "paddle_tpu.inference",
    "paddle_tpu.quantization",
    "paddle_tpu.profiler",
    "paddle_tpu.onnx",
    "paddle_tpu.regularizer",
    "paddle_tpu.parallel.zero",
    "paddle_tpu.parallel.ring",
    "paddle_tpu.parallel.dp_meta",
    "paddle_tpu.framework.flags",
    "paddle_tpu.framework.crypto",
    "paddle_tpu.framework.monitor",
    "paddle_tpu.framework.observability",
    "paddle_tpu.framework.blame",
    "paddle_tpu.framework.health",
    "paddle_tpu.framework.numerics",
    "paddle_tpu.framework.runlog",
    "paddle_tpu.framework.collector",
    "paddle_tpu.framework.incident",
    "paddle_tpu.framework.locks",
    "paddle_tpu.framework.analysis.concurrency",
    "paddle_tpu.framework.analysis.collectives",
    "paddle_tpu.framework.analysis.pallas_kernels",
    "paddle_tpu.ops.pallas.verify",
    "paddle_tpu.parallel.parity",
    "paddle_tpu.distributed.fleet.metrics",
    "paddle_tpu.distributed.fleet.utils.fs",
    "paddle_tpu.utils.cpp_extension",
    "paddle_tpu.reader",
    "paddle_tpu.device",
    "paddle_tpu.version",
    "paddle_tpu.sysconfig",
    "paddle_tpu.incubate",
    "paddle_tpu.dataset",
    "paddle_tpu.dataset.common",
    "paddle_tpu.dataset.mnist",
    "paddle_tpu.fluid",
    "paddle_tpu.fluid.layers",
    "paddle_tpu.fluid.dygraph",
    "paddle_tpu.fluid.initializer",
    "paddle_tpu.fluid.io",
    "paddle_tpu.fluid.optimizer",
    "paddle_tpu.incubate.optimizer",
    "paddle_tpu.utils",
]


def _sig_of(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(<unresolvable>)"


def _collect() -> dict:
    entries = {}
    for modname in MODULES:
        mod = importlib.import_module(modname)
        public = getattr(mod, "__all__", None)
        if public is None:
            public = [n for n in dir(mod) if not n.startswith("_")]
        for name in public:
            obj = getattr(mod, name, None)
            if obj is None:
                entries[f"{modname}.{name}"] = "MISSING-FROM-MODULE"
                continue
            if inspect.ismodule(obj):
                continue
            path = f"{modname}.{name}"
            if inspect.isclass(obj):
                entries[path] = "class" + _sig_of(obj)
                for mname, meth in sorted(vars(obj).items()):
                    if mname.startswith("_"):
                        continue
                    if callable(meth) or isinstance(
                            meth, (staticmethod, classmethod)):
                        fn = meth.__func__ if isinstance(
                            meth, (staticmethod, classmethod)) else meth
                        if callable(fn):
                            entries[f"{path}.{mname}"] = _sig_of(fn)
            elif callable(obj):
                entries[path] = _sig_of(obj)
            else:
                entries[path] = f"value:{type(obj).__name__}"
    return entries


def render() -> str:
    entries = _collect()
    lines = [f"{k} {v}" for k, v in sorted(entries.items())]
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--update", action="store_true",
                    help="regenerate API.spec")
    ap.add_argument("--check", action="store_true",
                    help="diff current surface against API.spec")
    a = ap.parse_args(argv)
    text = render()
    if a.update:
        with open(SPEC_PATH, "w") as f:
            f.write(text)
        print(f"wrote {SPEC_PATH} ({len(text.splitlines())} entries)")
        return 0
    if a.check:
        if not os.path.exists(SPEC_PATH):
            print("API.spec missing — run --update first", file=sys.stderr)
            return 1
        with open(SPEC_PATH) as f:
            frozen = f.read()
        if frozen == text:
            return 0
        import difflib
        diff = difflib.unified_diff(
            frozen.splitlines(), text.splitlines(),
            fromfile="API.spec (frozen)", tofile="current surface",
            lineterm="")
        for line in list(diff)[:80]:
            print(line, file=sys.stderr)
        print("\nAPI surface drifted from API.spec. If intentional, run\n"
              "  python tools/print_signatures.py --update",
              file=sys.stderr)
        return 1
    sys.stdout.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
