#!/usr/bin/env python3
"""NemotronH on the chip against its plain reference, at the benchmark
cell's sizes: the final hidden state before the head and the loss, the
program as ``jit.TrainStep`` computes them (AMP O2, bf16; the step's own
``functional_loss_call``) against ``benchmarks/reference/
nemotron3_super_120b.py`` (float32, highest matmul precision, sequential
recurrence).  Then the same with one float32 island of the program
lowered to bf16 at a time (the router's scores, the scan's decays and
state, the softmax-CE), to show which comparison sees it.

    chiprun --timeout 1800 -- python tools/nemotron_check.py --seeds 3

Prints one JSON line a (seed, variant) and, last, the worst of each.  The
benchmark's ``correct`` holds the first loss to ``TOLERANCE_REL``; this
tool is the builder's, for PERF.md (it is not part of the benchmark).
``--rehearse`` runs the tiny sizes on the CPU.
"""
import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "nemotron3_super_120b.train_b1_s4096"
VARIANTS = {"as_configured": {},
            "router_scores_bf16": {"router": True},
            "ssm_state_bf16": {"state": True},
            "softmax_ce_bf16": {"ce": True}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147483700)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from benchmarks.harness import measure
    from paddle_tpu.jit import functional_loss_call
    from paddle_tpu.models import nemotron_h, routing_load
    from paddle_tpu.nn.functional import moe, ssm
    from paddle_tpu.parallel import make_mesh, set_mesh

    if not args.rehearse and jax.default_backend() != "tpu":
        print("tools/nemotron_check.py: no TPU", file=sys.stderr)
        return 1
    cell = measure.load_cell(CELL, args.rehearse)
    config, traffic, sizes = cell["config"], cell["traffic"], cell["sizes"]
    reference = measure.resolve(config["reference"])
    set_mesh(make_mesh(dict(traffic["mesh"]), devices=jax.devices()[:1]))
    if not args.rehearse:
        paddle.device.use_compile_cache()

    def program(model, ids, what):
        """The hidden state or the loss as the step's forward gives it."""
        fn = {"hidden": lambda m, i, _: m(i, features_only=True),
              "loss": measure.resolve(config["loss"])}[what]
        params = {n: p._data for n, p in model.named_parameters()}
        buffers = {n: b._data for n, b in model.named_buffers()}
        call = jax.jit(lambda p, b, i: functional_loss_call(
            model, fn, p, b, jax.random.PRNGKey(0), [i, i], amp=True,
            amp_dtype=jnp.bfloat16)[0])
        return np.asarray(call(params, buffers, ids), np.float64)

    def set_islands(router=False, state=False, ce=False):
        moe._ROUTER_DTYPE = jnp.bfloat16 if router else jnp.float32
        ssm._STATE_DTYPE = jnp.bfloat16 if state else jnp.float32
        nemotron_h._CE_DTYPE = jnp.bfloat16 if ce else jnp.float32

    worst = {}
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        model = measure.build_model(config, sizes, seed)
        arrays = measure.resolve(config["inputs"])(
            seed, traffic["batch"], traffic["seq"], sizes)
        ids = jnp.asarray(arrays[0])
        params = {n: p._data for n, p in model.named_parameters()}
        with jax.default_matmul_precision("highest"):
            p32 = reference._float32(params, sizes)
            want_h = np.stack([np.asarray(jax.jit(functools.partial(
                reference.hidden, sizes=sizes))(p32, row), np.float64)
                for row in ids])
        want_loss = reference.loss(params, arrays, sizes,
                                   traffic["reference_block"])
        load = routing_load(model, ids)
        print(json.dumps({"seed": seed, "routing_load_max": int(load.max()),
                          "routing_load_mean": float(load.mean()),
                          "routing_load_min": int(load.min()),
                          "reference_loss": want_loss}), flush=True)
        for name, islands in VARIANTS.items():
            set_islands(**islands)
            try:
                got_h = program(model, ids, "hidden")
                got_loss = float(program(model, ids, "loss"))
            finally:
                set_islands()
            diff = got_h - want_h
            per_token = (np.linalg.norm(diff, axis=-1)
                         / np.linalg.norm(want_h, axis=-1))
            row = {"hidden_rel_frobenius": float(
                       np.linalg.norm(diff) / np.linalg.norm(want_h)),
                   "hidden_rel_worst_token": float(per_token.max()),
                   "hidden_max_abs": float(np.abs(diff).max()),
                   "loss_rel": abs(got_loss - want_loss) / abs(want_loss)}
            print(json.dumps({"seed": seed, "variant": name, **row,
                              "loss": got_loss}), flush=True)
            for key, value in row.items():
                worst.setdefault(name, {}).setdefault(key, 0.0)
                worst[name][key] = max(worst[name][key], value)
        del model, params, p32
    print(json.dumps({"worst_over_seeds": worst,
                      "tolerance_rel": reference.TOLERANCE_REL,
                      "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
