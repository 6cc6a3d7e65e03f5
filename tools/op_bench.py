#!/usr/bin/env python
"""Config-driven per-op benchmark harness + regression gate.

Reference roles:
  * paddle/fluid/operators/benchmark/op_tester.cc:67 — replay one op from
    an OpTesterConfig (shapes/dtypes/attrs), time repeated runs;
  * tools/test_op_benchmark.sh + tools/check_op_benchmark_result.py — the
    CI gate comparing op timings against a stored baseline.

Usage:
    python tools/op_bench.py                         # built-in suite
    python tools/op_bench.py --config cfg.json       # custom ops
    python tools/op_bench.py --save base.json        # record baseline
    python tools/op_bench.py --compare base.json --threshold 0.15
        # exit 1 if any op is >15% slower than the baseline

Config entries: {"name", "op" (dotted path under paddle_tpu),
"args" ([{shape, dtype, low?, high?} or scalar]), "kwargs"?, "grad"?}.
Timings use a device->host fetch as the execution fence.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

import numpy as np

import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUILTIN_SUITE = [
    {"name": "matmul_1k", "op": "paddle_tpu.matmul",
     "args": [{"shape": [1024, 1024], "dtype": "float32"},
              {"shape": [1024, 1024], "dtype": "float32"}]},
    {"name": "softmax_8kx1k", "op": "paddle_tpu.nn.functional.softmax",
     "args": [{"shape": [8192, 1024], "dtype": "float32"}]},
    {"name": "layer_norm", "op": "paddle_tpu.nn.functional.layer_norm",
     "args": [{"shape": [4096, 1024], "dtype": "float32"}],
     "kwargs": {"normalized_shape": [1024]}},
    {"name": "conv2d_64", "op": "paddle_tpu.nn.functional.conv2d",
     "args": [{"shape": [8, 64, 56, 56], "dtype": "float32"},
              {"shape": [64, 64, 3, 3], "dtype": "float32"}],
     "kwargs": {"padding": 1}},
    {"name": "embedding_bag", "op": "paddle_tpu.nn.functional.embedding_bag",
     "args": [{"shape": [512, 64], "dtype": "int64", "low": 0,
               "high": 30000},
              {"shape": [30000, 128], "dtype": "float32"}],
     "kwargs": {"mode": "mean"}},
    {"name": "reduce_sum_16m", "op": "paddle_tpu.sum",
     "args": [{"shape": [4096, 4096], "dtype": "float32"}]},
]


# PS transport microbench suite (--ps-transport): an in-process PsServer
# + PsClient over localhost TCP, per wire dtype.  ``wire_mb`` (measured
# bytes on the wire per op, from the client's TransportStats) is the
# gated metric — byte counts are deterministic, so the compare gate can
# hold the line on transport bytes with a tight threshold while the
# wall-clock ms stays informational (localhost TCP timing is too noisy
# to gate).  Names here are registered with the compare gate's key
# validation like the builtin ops.
PS_TRANSPORT_SUITE = [
    {"name": "ps_pull_8kx64_f32", "kind": "pull", "wire": "f32"},
    {"name": "ps_pull_8kx64_bf16", "kind": "pull", "wire": "bf16"},
    {"name": "ps_pull_8kx64_int8", "kind": "pull", "wire": "int8"},
    {"name": "ps_push_8kx64_f32", "kind": "push", "wire": "f32"},
    {"name": "ps_push_8kx64_bf16", "kind": "push", "wire": "bf16"},
    {"name": "ps_push_pull_8kx64_bf16", "kind": "push_pull",
     "wire": "bf16"},
]


def ps_transport_bench(repeats=3):
    """Measure wire bytes + round-trip time for each PS_TRANSPORT_SUITE
    entry against an in-process server.  Device-independent (host numpy
    + TCP), so records carry device 'host' and gate everywhere."""
    from paddle_tpu.distributed.ps import HostEmbeddingTable
    from paddle_tpu.distributed.ps.service import PsClient, PsServer

    n_ids, dim, rows = 8192, 64, 65536
    srv = PsServer({"emb": HostEmbeddingTable(
        rows, dim, optimizer="sgd", learning_rate=0.0)}, port=0)
    srv.start()
    results = []
    try:
        rng = np.random.default_rng(0)
        ids = rng.integers(0, rows, size=(n_ids,)).astype(np.int64)
        grads = rng.standard_normal((n_ids, dim)).astype(np.float32)
        for cfg in PS_TRANSPORT_SUITE:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype=cfg["wire"])
            ops = {
                "pull": lambda: c.pull("emb", ids),
                "push": lambda: c.push("emb", ids, grads),
                "push_pull": lambda: c.push_pull("emb", ids, grads, ids),
            }
            run = ops[cfg["kind"]]
            run()                            # warm (incl. hello handshake)
            best = None
            s0 = c.transport_stats()
            for _ in range(repeats):
                t0 = time.perf_counter()
                run()
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            s1 = c.transport_stats()
            wire_mb = ((s1["bytes_sent"] - s0["bytes_sent"]) +
                       (s1["bytes_recv"] - s0["bytes_recv"])) \
                / repeats / 1e6
            c.bye()
            r = {"name": cfg["name"], "op": f"ps.{cfg['kind']}",
                 "ms": round(best * 1e3, 3), "wire_mb": round(wire_mb, 5),
                 "device": "host"}
            results.append(r)
            print(json.dumps(r), flush=True)
    finally:
        srv.shutdown()
    return results


# ZeRO collective byte suite (--zero-collectives): the sharded-update
# train step's reduce-scatter / all-gather legs per wire dtype, on a
# fixed ~1M-param MLP at dp=2.  ``wire_mb`` is ANALYTIC (ShardedUpdate-
# TrainStep.collective_wire_bytes — exact payload accounting per leg,
# deterministic across hosts), so the compare gate holds the line on
# collective bytes with a tight threshold; ``ms`` is the measured full
# fused-step wall clock (identical for the rs/ag records of one wire —
# the legs are not separable on the host) and stays informational.
ZERO_COLLECTIVES_SUITE = [
    {"name": "zero_rs_mlp1m_f32", "leg": "reduce_scatter", "wire": "f32"},
    {"name": "zero_rs_mlp1m_bf16", "leg": "reduce_scatter",
     "wire": "bf16"},
    {"name": "zero_rs_mlp1m_int8", "leg": "reduce_scatter",
     "wire": "int8"},
    {"name": "zero_ag_mlp1m_f32", "leg": "all_gather", "wire": "f32"},
    {"name": "zero_ag_mlp1m_bf16", "leg": "all_gather", "wire": "bf16"},
    {"name": "zero_ag_mlp1m_int8", "leg": "all_gather", "wire": "int8"},
]


def zero_collectives_bench(repeats=3):
    """One sharded-update step per wire dtype on a dp=2 CPU/accelerator
    mesh; emits a record per (leg, wire) with the analytic per-replica
    wire MB (gated) and the measured step ms (informational)."""
    # dp=2 needs >= 2 devices; on a CPU host force a virtual mesh
    # BEFORE jax initializes (a no-op for non-CPU backends)
    if "jax" not in sys.modules:
        xf = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in xf:
            os.environ["XLA_FLAGS"] = (
                xf + " --xla_force_host_platform_device_count=8").strip()
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import optimizer
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.zero import ShardedUpdateTrainStep
    if len(jax.devices()) < 2:
        raise RuntimeError(
            "--zero-collectives needs >= 2 devices for a dp=2 mesh "
            "(CPU hosts get a virtual mesh automatically unless jax "
            "was already initialized single-device)")
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])

    def loss_fn(m, x, y):
        return ((m(x) - y) ** 2).mean()

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((8, 512)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((8, 512)).astype(np.float32))
    results = []
    by_wire = {}
    for wire in ("f32", "bf16", "int8"):
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(512, 1024), nn.ReLU(),
                              nn.Linear(1024, 512))
        opt = optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                 parameters=model.parameters())
        step = ShardedUpdateTrainStep(model, loss_fn, opt, mesh=mesh,
                                      wire_dtype=wire)
        step(x, y)                       # warm (compile)
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            loss = step(x, y)
            np.asarray(loss._data)       # execution fence
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        by_wire[wire] = (best, step.collective_wire_bytes())
    for cfg in ZERO_COLLECTIVES_SUITE:
        best, bytes_ = by_wire[cfg["wire"]]
        r = {"name": cfg["name"], "op": f"zero.{cfg['leg']}",
             "ms": round(best * 1e3, 3),
             "wire_mb": round(bytes_[cfg["leg"]] / 1e6, 5),
             "device": "host"}
        results.append(r)
        print(json.dumps(r), flush=True)
    return results


# Fused ring collective suite (--ring-collectives): the same ~1M-param
# MLP step at dp=2 with the quantized ring engaged (parallel/ring.py),
# one record per (leg, wire) across every collective wire including the
# packed int4 codec.  ``wire_mb`` is ANALYTIC and DETERMINISTIC (the
# ring moves the same (dp-1) encoded chunks per leg per replica as the
# unfused exchange — ShardedUpdateTrainStep.collective_wire_bytes), so
# the compare gate holds the line on encoded bytes; ``ms`` is the
# measured fused-step wall clock and stays informational.  The bench
# additionally gates the CODEC RATIOS in-function: each quantized
# wire's per-leg bytes must stay under its analytic ceiling relative to
# f32 (bf16 0.51x, int8 0.26x, int4 0.14x — the acceptance bars; the
# real ratios at chunk=256 are 0.500x / 0.2539x / 0.1289x).
RING_COLLECTIVES_SUITE = [
    {"name": "ring_rs_mlp1m_f32", "leg": "reduce_scatter", "wire": "f32"},
    {"name": "ring_rs_mlp1m_bf16", "leg": "reduce_scatter",
     "wire": "bf16"},
    {"name": "ring_rs_mlp1m_int8", "leg": "reduce_scatter",
     "wire": "int8"},
    {"name": "ring_rs_mlp1m_int4", "leg": "reduce_scatter",
     "wire": "int4"},
    {"name": "ring_ag_mlp1m_f32", "leg": "all_gather", "wire": "f32"},
    {"name": "ring_ag_mlp1m_bf16", "leg": "all_gather", "wire": "bf16"},
    {"name": "ring_ag_mlp1m_int8", "leg": "all_gather", "wire": "int8"},
    {"name": "ring_ag_mlp1m_int4", "leg": "all_gather", "wire": "int4"},
]

# per-leg wire-byte ceiling vs the f32 leg (analytic, chunk=256)
RING_WIRE_RATIO_MAX = {"bf16": 0.51, "int8": 0.26, "int4": 0.14}


def ring_collectives_bench(repeats=3):
    """One ring-enabled sharded-update step per wire dtype on a dp=2
    mesh; emits a record per (leg, wire) with the analytic per-replica
    wire MB (gated vs baseline AND vs the f32 leg's ratio ceiling) and
    the measured step ms (informational)."""
    if "jax" not in sys.modules:
        xf = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in xf:
            os.environ["XLA_FLAGS"] = (
                xf + " --xla_force_host_platform_device_count=8").strip()
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import optimizer
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.zero import ShardedUpdateTrainStep
    if len(jax.devices()) < 2:
        raise RuntimeError(
            "--ring-collectives needs >= 2 devices for a dp=2 mesh "
            "(CPU hosts get a virtual mesh automatically unless jax "
            "was already initialized single-device)")
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])

    def loss_fn(m, x, y):
        return ((m(x) - y) ** 2).mean()

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((8, 512)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((8, 512)).astype(np.float32))
    results = []
    by_wire = {}
    for wire in ("f32", "bf16", "int8", "int4"):
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(512, 1024), nn.ReLU(),
                              nn.Linear(1024, 512))
        opt = optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                 parameters=model.parameters())
        step = ShardedUpdateTrainStep(model, loss_fn, opt, mesh=mesh,
                                      wire_dtype=wire, ring=True)
        step(x, y)                       # warm (compile)
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            loss = step(x, y)
            np.asarray(loss._data)       # execution fence
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        by_wire[wire] = (best, step.collective_wire_bytes())
    # in-function ratio gate: the codec must actually shrink the wire
    for wire, cap in RING_WIRE_RATIO_MAX.items():
        for leg in ("reduce_scatter", "all_gather"):
            ratio = by_wire[wire][1][leg] / by_wire["f32"][1][leg]
            if ratio > cap:
                raise RuntimeError(
                    f"ring {wire} {leg} wire bytes are {ratio:.4f}x of "
                    f"the f32 leg (ceiling {cap}x) — the codec stopped "
                    "compressing; check wire.py wire_nbytes")
    for cfg in RING_COLLECTIVES_SUITE:
        best, bytes_ = by_wire[cfg["wire"]]
        r = {"name": cfg["name"], "op": f"ring.{cfg['leg']}",
             "ms": round(best * 1e3, 3),
             "wire_mb": round(bytes_[cfg["leg"]] / 1e6, 5),
             "device": "host"}
        results.append(r)
        print(json.dumps(r), flush=True)
    return results


# Replica-parity probe suite (--parity-probe): the runtime half of the
# distributed-semantics plane on the same ~1M-param MLP at dp=2.  The
# contract gated here: ARMED, the probe's amortized cost at the default
# cadence stays under 2% of a step (in-function gate; the probe's own
# per-invocation ms is recorded, and its ANALYTIC wire bytes — one
# uint32 hash per leaf through a psum ring — gate deterministically
# against the baseline); DISARMED, the probe adds exactly zero — zero
# probe invocations, zero compiled probe programs, zero step-cache
# churn.  "Exactly zero" is structural, so the disarmed leg is an
# in-function gate (a record still prints and reaches the ledger for
# cross-run step-time series); only the armed record enters the
# baseline compare — its wall clock may not carry a <30% threshold on
# a noisy CPU host, and the thresholds file is held to <30% by
# tests/test_op_bench_gate.py.
PARITY_PROBE_SUITE = [
    {"name": "parity_probe_mlp1m_armed"},
]


def parity_probe_bench(repeats=3, steps=10):
    if "jax" not in sys.modules:
        xf = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in xf:
            os.environ["XLA_FLAGS"] = (
                xf + " --xla_force_host_platform_device_count=8").strip()
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import optimizer
    from paddle_tpu.framework import monitor
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.parity import ParityProbe, _state_tree
    from paddle_tpu.parallel.zero import ShardedUpdateTrainStep
    if len(jax.devices()) < 2:
        raise RuntimeError(
            "--parity-probe needs >= 2 devices for a dp=2 mesh")
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])

    def loss_fn(m, x, y):
        return ((m(x) - y) ** 2).mean()

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((8, 512)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((8, 512)).astype(np.float32))
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(512, 1024), nn.ReLU(),
                          nn.Linear(1024, 512))
    opt = optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                             parameters=model.parameters())
    step = ShardedUpdateTrainStep(model, loss_fn, opt, mesh=mesh,
                                  wire_dtype="f32")
    saved = get_flags(["replica_parity", "replica_parity_every"])
    results = []
    try:
        # -- disarmed: the step must be byte-identical to the seed ----
        set_flags({"replica_parity": False})
        monitor.reset_all_stats()
        step(x, y)                              # warm (compile)
        fns_before = set(step._fns)
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = step(x, y)
            np.asarray(loss._data)
            dt = (time.perf_counter() - t0) / steps
            best = dt if best is None else min(best, dt)
        if monitor.get_stat("parity_checks_total"):
            raise RuntimeError("disarmed probe ran a check")
        if getattr(step, "_parity_probe", None) is not None:
            raise RuntimeError("disarmed probe attached state")
        if set(step._fns) != fns_before:
            raise RuntimeError("disarmed probe changed the step cache")
        step_ms = best * 1e3
        r = {"name": "parity_probe_mlp1m_disarmed",
             "ms": round(step_ms, 3), "probe_calls": 0,
             "device": "host"}
        results.append(r)
        print(json.dumps(r), flush=True)

        # -- armed: per-invocation probe cost + analytic wire ---------
        set_flags({"replica_parity": True})
        every = int(get_flags("replica_parity_every")
                    ["replica_parity_every"])
        probe = ParityProbe(mesh=mesh, every=1)
        tree = _state_tree(step)
        rec = probe.observe(tree)               # warm (compile)
        if rec is None or not rec.ok():
            raise RuntimeError("armed probe found divergence on a "
                               "healthy step (or probed nothing)")
        n_leaves = len(rec.names)
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(steps):
                out = probe.observe(tree)
            _ = out.divergent_leaves()          # host fetch fence
            dt = (time.perf_counter() - t0) / steps
            best = dt if best is None else min(best, dt)
        probe_ms = best * 1e3
        overhead_pct = (probe_ms / every) / step_ms * 100.0
        if overhead_pct > 2.0:
            raise RuntimeError(
                f"armed parity probe costs {overhead_pct:.2f}% of a "
                f"step at the default cadence (every={every}) — the "
                "2% budget is the flag's promise")
        # analytic wire: one uint32 hash per leaf through a psum ring
        dp = 2
        wire_mb = 2.0 * (dp - 1) / dp * 4 * n_leaves / 1e6
        r = {"name": "parity_probe_mlp1m_armed",
             "ms": round(probe_ms, 3),
             "wire_mb": round(wire_mb, 6),
             "overhead_pct": round(overhead_pct, 3),
             "leaves": n_leaves, "device": "host"}
        results.append(r)
        print(json.dumps(r), flush=True)
    finally:
        set_flags(saved)
    return results


def _resolve(path: str):
    mod, _, attr = path.rpartition(".")
    obj = importlib.import_module(mod)
    return getattr(obj, attr)


def _make_arg(spec, rng):
    import paddle_tpu as paddle
    if not isinstance(spec, dict):
        return spec
    dtype = spec.get("dtype", "float32")
    shape = spec["shape"]
    if np.issubdtype(np.dtype(dtype), np.integer):
        arr = rng.integers(spec.get("low", 0), spec.get("high", 100),
                           size=shape).astype(dtype)
    else:
        arr = rng.standard_normal(shape).astype(dtype)
    return paddle.to_tensor(arr)


def _sync(out):
    from paddle_tpu.core import Tensor
    if isinstance(out, (list, tuple)):
        out = out[0]
    arr = out._data if isinstance(out, Tensor) else out
    np.asarray(arr)


_MANY_CACHE: dict = {}
_SCAN_LEN_CACHE: dict = {}


def run_one(cfg, iters=10, repeats=3):
    """Dispatch-free op timing via a two-length scan difference.

    The op is chained ``L`` times through one jitted lax.scan (a real
    data dependency links iterations), dispatched once.  A single
    amortized timing still carries the host's dispatch + fetch cost;
    timing a short scan and a long scan and dividing the delta by the
    iteration difference cancels it exactly.  The long length is calibrated per op to ~1 s of device
    time and cached, as are the compiled scans; min-of-``repeats``
    strips residual jitter.  Baseline and CI gate share this estimator.
    Warmup needs no knob: each compiled scan gets one untimed call.

    ``iters`` sets the short length (and the calibration probe);
    regression detection quality depends on the long leg, so the
    default is fine almost always."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import Tensor

    fn = _resolve(cfg["op"])
    name = cfg.get("name", cfg["op"])
    # cache on the full config, not the name: a custom --config suite may
    # repeat an op with different args/kwargs under the same default name
    ckey = json.dumps(cfg, sort_keys=True, default=str)
    rng = np.random.default_rng(0)
    args = [_make_arg(a, rng) for a in cfg.get("args", [])]
    kwargs = cfg.get("kwargs", {})
    arrs = [a._data if isinstance(a, Tensor) else a for a in args]
    was_t = [isinstance(a, Tensor) for a in args]
    # chain the carry through the first float operand: a `* 0` dependency
    # is constant-folded and the op hoisted out of the scan (measured:
    # embedding_bag "ran" in 8.8 us); a sub-ulp runtime value is not.
    # The carry itself stays float32 UNCONDITIONALLY: an int or fp16
    # carry would turn the 1e-30 scale into a foldable constant zero
    # (int truncation / fp16 underflow at trace time) and resurrect the
    # hoisting for int-only/fp16 --config suites — the cast to the
    # operand dtype happens only at the `xs[ci] + c` use site, where the
    # carry is a runtime value XLA cannot fold
    ci = next((i for i, a in enumerate(arrs)
               if jnp.issubdtype(a.dtype, jnp.floating)), 0)

    def core(*xs):
        targs = [Tensor(x) if t else x for x, t in zip(xs, was_t)]
        out = fn(*targs, **kwargs)
        if isinstance(out, (list, tuple)):
            out = out[0]
        return out._data if isinstance(out, Tensor) else out

    def many_of(length):
        key = (ckey, length)
        got = _MANY_CACHE.get(key)
        if got is not None:
            return got

        @jax.jit
        def many(*xs):
            def body(c, _):
                mod = list(xs)
                mod[ci] = xs[ci] + c.astype(xs[ci].dtype)
                out = core(*mod)
                dep = out.mean().astype(jnp.float32) * \
                    jnp.asarray(1e-30, jnp.float32)
                return c + dep, None
            c, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), None,
                                length=length)
            return c

        _MANY_CACHE[key] = many
        return many

    def timed(many, reps):
        out = many(*arrs)                    # compile + device warm
        np.asarray(jax.device_get(out))
        best = None
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            out = many(*arrs)
            np.asarray(jax.device_get(out))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    l_small = max(4, iters)
    t_small = timed(many_of(l_small), repeats)
    l_big = _SCAN_LEN_CACHE.get(ckey)
    if l_big is None:
        l_probe = l_small + 512
        t_probe = timed(many_of(l_probe), 2)
        per_iter = max((t_probe - t_small) / (l_probe - l_small), 1e-8)
        # ~1 s of device time on the long leg, so host dispatch jitter
        # is a small share of the difference
        l_big = l_small + int(min(max(1.0 / per_iter, 64), 400_000))
        _SCAN_LEN_CACHE[ckey] = l_big
    # a later call with a larger l_small than the cached calibration must
    # not collapse the difference leg
    l_big = max(l_big, l_small + 64)
    t_big = timed(many_of(l_big), repeats)
    dt = (t_big - t_small) / (l_big - l_small)
    if dt <= 0.0:
        # jitter swamped the difference leg (possible for very cheap ops
        # whose calibrated long leg hit the scan cap): recalibrate once
        # with a doubled difference before giving up
        l_big = l_small + 2 * (l_big - l_small)
        _SCAN_LEN_CACHE[ckey] = l_big
        t_big = timed(many_of(l_big), repeats)
        dt = (t_big - t_small) / (l_big - l_small)
    if dt <= 0.0:
        # a recorded 0.0 ms would poison any baseline it lands in (the
        # compare gate divides by it) — refuse to report a measurement
        return {"name": name, "op": cfg["op"],
                "error": "non-positive scan-difference timing after "
                         f"recalibration (t_small={t_small:.6f}s, "
                         f"t_big={t_big:.6f}s, scan_len={l_big}); "
                         "refusing to record 0.0 ms",
                "device": jax.default_backend()}
    return {"name": name, "op": cfg["op"], "ms": round(dt * 1e3, 5),
            "scan_len": l_big, "device": jax.default_backend()}


def eager_vs_jit_bench(iters=30, batch=64):
    """Quantify eager dispatch overhead: a LeNet fwd+bwd+SGD step timed
    (a) eager with the compiled (fwd,vjp) dispatch cache off,
    (b) eager with it on (the core.ops fast-path role,
        reference pybind/op_function_generator.cc), and
    (c) fully captured as one XLA computation (jit.TrainStep).
    """
    import paddle_tpu as paddle
    from paddle_tpu import jit
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.vision.models import LeNet

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((batch, 1, 28, 28))
                         .astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, size=(batch,)).astype(np.int64))

    def loss_fn(model, xb, yb):
        import paddle_tpu.nn.functional as F
        return F.cross_entropy(model(xb), yb)

    def eager_step(model, opt):
        loss = loss_fn(model, x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    results = {}
    for mode in ("eager_nocache", "eager_cached", "trainstep_jit"):
        model = LeNet()
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters())
        set_flags({"eager_op_jit_cache": mode != "eager_nocache"})
        if mode == "trainstep_jit":
            step = jit.TrainStep(model, loss_fn, opt)
            run = lambda: step(x, y)                       # noqa: E731
        else:
            run = lambda: eager_step(model, opt)           # noqa: E731
        for _ in range(5):
            loss = run()
        _sync(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = run()
        _sync(loss)
        results[mode] = (time.perf_counter() - t0) / iters * 1e3
    set_flags({"eager_op_jit_cache": True})
    out = {"name": "lenet_step_dispatch", "batch": batch,
           "eager_nocache_ms": round(results["eager_nocache"], 3),
           "eager_cached_ms": round(results["eager_cached"], 3),
           "trainstep_jit_ms": round(results["trainstep_jit"], 3),
           "cache_speedup": round(
               results["eager_nocache"] / results["eager_cached"], 2),
           "jit_speedup_vs_eager": round(
               results["eager_nocache"] / results["trainstep_jit"], 2)}
    print(json.dumps(out), flush=True)
    return out


def eager_transformer_bench(iters=20, batch=8, seq=128, d_model=256):
    """Eager dispatch-cache effectiveness on a transformer block (round-4
    verdict item 9: LeNet alone doesn't show whether the ~9x transfers
    to attention-heavy eager code).  Times a TransformerEncoderLayer
    fwd+bwd+SGD eager step with the (fwd,vjp) cache off vs on, and
    reports the monitor hit/miss/uncacheable counters."""
    import paddle_tpu as paddle
    from paddle_tpu.framework import monitor
    from paddle_tpu.framework.flags import flag, set_flags

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((batch, seq, d_model))
                         .astype(np.float32))

    def eager_step(model, opt):
        out = model(x)
        loss = (out * out).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    results = {}
    stats = {}
    prior = flag("eager_op_jit_cache")
    try:
        for mode in ("nocache", "cached"):
            paddle.seed(0)
            model = paddle.nn.TransformerEncoderLayer(
                d_model=d_model, nhead=4, dim_feedforward=4 * d_model)
            opt = paddle.optimizer.SGD(learning_rate=0.01,
                                       parameters=model.parameters())
            set_flags({"eager_op_jit_cache": mode == "cached"})
            monitor.reset_all_stats()
            for _ in range(3):
                loss = eager_step(model, opt)
            _sync(loss)
            t0 = time.perf_counter()
            for _ in range(iters):
                loss = eager_step(model, opt)
            _sync(loss)
            results[mode] = (time.perf_counter() - t0) / iters * 1e3
            stats[mode] = {k: v for k, v in monitor.all_stats().items()
                           if k.startswith("eager_cache")}
    finally:
        set_flags({"eager_op_jit_cache": prior})
    s = stats["cached"]
    total = sum(s.values()) or 1
    out = {"name": "eager_transformer_block",
           "nocache_ms": round(results["nocache"], 3),
           "cached_ms": round(results["cached"], 3),
           "cache_speedup": round(results["nocache"] / results["cached"],
                                  2),
           "hit": s.get("eager_cache_hit", 0),
           "miss": s.get("eager_cache_miss", 0),
           "uncacheable": s.get("eager_cache_uncacheable", 0),
           "hit_rate": round(s.get("eager_cache_hit", 0) / total, 3)}
    print(json.dumps(out), flush=True)
    return out


def _scan_time(fn, args, reps=30):
    """Time fn amortized inside one jit (host dispatch would otherwise
    dominate): scan reps iterations with a data dependency, fence with a
    device->host fetch."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def many(*args):
        def body(c, _):
            out = fn(args[0] + c, *args[1:])
            first = out[0] if isinstance(out, (tuple, list)) else out
            return c + first.mean().astype(args[0].dtype) * 0, None
        c, _ = jax.lax.scan(body, jnp.zeros((), args[0].dtype), None,
                            length=reps)
        return c

    out = many(*args)
    np.asarray(jax.device_get(out))
    t0 = time.perf_counter()
    out = many(*args)
    np.asarray(jax.device_get(out))
    return (time.perf_counter() - t0) / reps


def fused_adam_bench(n_params=85_000_000):
    """Pallas fused adam vs the XLA expression tree, GPT-2-scale tensor."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import fused_adam

    rng = np.random.default_rng(0)
    shape = (n_params // 1024, 1024)
    p = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    m = jnp.zeros(shape, jnp.float32)
    v = jnp.zeros(shape, jnp.float32)
    kw = dict(lr_t=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, wd_lr=1e-4)

    t_pallas = _scan_time(
        lambda p, g, m, v: fused_adam.fused_adam_update(p, g, m, v, **kw),
        (p, g, m, v), reps=20)
    t_xla = _scan_time(
        lambda p, g, m, v: fused_adam.xla_reference(p, g, m, v, **kw),
        (p, g, m, v), reps=20)
    out = {"name": "fused_adam_85m", "pallas_ms": round(t_pallas * 1e3, 3),
           "xla_ms": round(t_xla * 1e3, 3),
           "speedup": round(t_xla / t_pallas, 3),
           "device": jax.default_backend()}
    print(json.dumps(out), flush=True)
    return out


def fused_ce_bench():
    """Pallas blockwise linear+softmax-CE vs unfused XLA, GPT-2 head shape
    (N=8192 tokens, H=1024, V=50304), fwd+bwd."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import fused_ce

    rng = np.random.default_rng(0)
    N, H, V = 8192, 1024, 50304
    h = jnp.asarray(rng.standard_normal((N, H)) * 0.02, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((V, H)) * 0.02, jnp.bfloat16)
    lab = jnp.asarray(rng.integers(0, 50257, size=(N,)), jnp.int32)

    def g_of(fn):
        return jax.grad(lambda h, w: fn(h, w, lab).mean(), argnums=(0, 1))

    t_pallas = _scan_time(
        lambda h, w: g_of(fused_ce.fused_linear_cross_entropy)(h, w),
        (h, w), reps=20)
    t_xla = _scan_time(
        lambda h, w: g_of(fused_ce.xla_reference)(h, w), (h, w), reps=20)
    out = {"name": "fused_ce_gpt2_head",
           "pallas_ms": round(t_pallas * 1e3, 3),
           "xla_ms": round(t_xla * 1e3, 3),
           "speedup": round(t_xla / t_pallas, 3),
           "device": jax.default_backend()}
    print(json.dumps(out), flush=True)
    return out


def fused_rnn_bench(T=256, B=64, F=512, H=512):
    """The fusion_lstm question (reference operators/fused/
    fusion_lstm_op.cc): does hoisting the input projection out of the
    recurrence matter on TPU?  Times one LSTM layer fwd+bwd with the
    projection (a) pre-computed for all timesteps in one matmul (the
    shipped nn.LSTM path) vs (b) recomputed inside every scan step."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, B, F)), jnp.float32)
    w_ih = jnp.asarray(rng.standard_normal((4 * H, F)) * 0.05, jnp.float32)
    w_hh = jnp.asarray(rng.standard_normal((4 * H, H)) * 0.05, jnp.float32)
    b = jnp.zeros((4 * H,), jnp.float32)

    def cell(z, hp, cp):
        i, f, g, o = jnp.split(z, 4, axis=-1)
        cn = jax.nn.sigmoid(f) * cp + jax.nn.sigmoid(i) * jnp.tanh(g)
        return jax.nn.sigmoid(o) * jnp.tanh(cn), cn

    def lstm_fused(x, w_ih, w_hh):
        gi = x @ w_ih.T + b                          # (T, B, 4H) one matmul

        def body(carry, gi_t):
            hp, cp = carry
            hn, cn = cell(gi_t + hp @ w_hh.T, hp, cp)
            return (hn, cn), hn
        (_, _), ys = jax.lax.scan(
            body, (jnp.zeros((B, H)), jnp.zeros((B, H))), gi)
        return ys

    def lstm_naive(x, w_ih, w_hh):
        def body(carry, x_t):
            hp, cp = carry
            hn, cn = cell(x_t @ w_ih.T + b + hp @ w_hh.T, hp, cp)
            return (hn, cn), hn
        (_, _), ys = jax.lax.scan(
            body, (jnp.zeros((B, H)), jnp.zeros((B, H))), x)
        return ys

    def g_of(fn):
        return jax.grad(lambda x, wi, wh: fn(x, wi, wh).sum(),
                        argnums=(0, 1, 2))

    t_fused = _scan_time(lambda x, wi, wh: g_of(lstm_fused)(x, wi, wh),
                         (x, w_ih, w_hh), reps=10)
    t_naive = _scan_time(lambda x, wi, wh: g_of(lstm_naive)(x, wi, wh),
                         (x, w_ih, w_hh), reps=10)
    out = {"name": f"fused_lstm_T{T}_B{B}_H{H}",
           "preprojected_ms": round(t_fused * 1e3, 3),
           "inloop_ms": round(t_naive * 1e3, 3),
           "speedup": round(t_naive / t_fused, 3),
           "device": jax.default_backend()}
    print(json.dumps(out), flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--eager", action="store_true",
                    help="run the eager-vs-jit dispatch benchmark")
    ap.add_argument("--fused-adam", action="store_true",
                    help="pallas fused adam vs XLA expression tree")
    ap.add_argument("--fused-ce", action="store_true",
                    help="pallas blockwise CE vs unfused XLA")
    ap.add_argument("--fused-rnn", action="store_true",
                    help="pre-projected vs in-loop LSTM input projection")
    ap.add_argument("--eager-transformer", action="store_true",
                    help="eager dispatch cache on a transformer block "
                         "+ hit-rate counters")
    ap.add_argument("--ps-transport", action="store_true",
                    help="PS wire microbench (pull/push/push_pull per "
                         "wire dtype); gates on measured wire_mb, which "
                         "is deterministic — ms is informational")
    ap.add_argument("--zero-collectives", action="store_true",
                    help="ZeRO sharded-update collective bytes "
                         "(reduce-scatter/all-gather per wire dtype at "
                         "dp=2); gates on analytic wire_mb, which is "
                         "deterministic — ms is informational")
    ap.add_argument("--ring-collectives", action="store_true",
                    help="fused quantized ring collective bytes "
                         "(ring reduce-scatter/all-gather per wire "
                         "dtype incl. int4 at dp=2); gates on analytic "
                         "wire_mb plus the per-wire ratio ceiling vs "
                         "f32 — ms is informational")
    ap.add_argument("--parity-probe", action="store_true",
                    help="replica-parity probe overhead (dp=2 mlp1m): "
                         "armed <= 2% of step time at the default "
                         "cadence and analytic hash wire bytes "
                         "(deterministic, gated); disarmed exactly "
                         "zero probe work (in-function gate)")
    ap.add_argument("--config", help="JSON list of op configs")
    ap.add_argument("--save", help="write results JSON here")
    ap.add_argument("--compare", help="baseline JSON to gate against")
    ap.add_argument("--threshold", type=float, default=0.1,
                    help="allowed relative slowdown vs baseline")
    ap.add_argument("--thresholds",
                    help="per-op threshold JSON ({op: allowed_slowdown}, "
                         "sized from a measured run-to-run distribution); "
                         "falls back to --threshold for ops not listed")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timing passes per op; the min is reported "
                         "(robust to host-side spikes)")
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="append an op_bench RunRecord (one leg per "
                         "measured metric) to the run ledger at PATH "
                         "— the perf observatory's producer hook "
                         "(suite / --ps-transport / --zero-collectives "
                         "runs)")
    a = ap.parse_args(argv)

    if a.eager:
        r = eager_vs_jit_bench(iters=a.iters if a.iters != 10 else 30)
        if a.save:
            with open(a.save, "w") as f:
                json.dump([r], f, indent=1)
        return 0
    if a.fused_adam or a.fused_ce or a.fused_rnn or a.eager_transformer:
        rs = []
        if a.fused_adam:
            rs.append(fused_adam_bench())
        if a.fused_ce:
            rs.append(fused_ce_bench())
        if a.fused_rnn:
            rs.append(fused_rnn_bench())
        if a.eager_transformer:
            rs.append(eager_transformer_bench())
        if a.save:
            with open(a.save, "w") as f:
                json.dump(rs, f, indent=1)
        return 0

    if a.ps_transport:
        suite = PS_TRANSPORT_SUITE
        results = ps_transport_bench(repeats=a.repeats)
    elif a.zero_collectives:
        suite = ZERO_COLLECTIVES_SUITE
        results = zero_collectives_bench(repeats=a.repeats)
    elif a.ring_collectives:
        suite = RING_COLLECTIVES_SUITE
        results = ring_collectives_bench(repeats=a.repeats)
    elif a.parity_probe:
        suite = PARITY_PROBE_SUITE
        results = parity_probe_bench(repeats=a.repeats)
    else:
        suite = BUILTIN_SUITE
        if a.config:
            with open(a.config) as f:
                suite = json.load(f)
        results = []
        for cfg in suite:
            try:
                r = run_one(cfg, iters=a.iters, repeats=a.repeats)
            except Exception as e:           # noqa: BLE001
                r = {"name": cfg.get("name", cfg.get("op")),
                     "error": repr(e)}
            results.append(r)
            print(json.dumps(r), flush=True)

    if a.save:
        with open(a.save, "w") as f:
            json.dump(results, f, indent=1)
    if a.ledger:
        # ms per op plus wire_mb where measured; RunLedger.append never
        # raises, so the gate below still runs on a broken ledger disk.
        # Label per suite VARIANT and skip the registry snapshot: the
        # legs are the cross-run series, and a process-cumulative
        # counter snapshot would differ wildly between variants sharing
        # one ledger — a self-flagged "regression" on a healthy machine
        from paddle_tpu.framework import runlog
        variant = "ps_transport" if a.ps_transport else \
            "zero_collectives" if a.zero_collectives else \
            "ring_collectives" if a.ring_collectives else \
            "parity_probe" if a.parity_probe else "suite"
        legs = []
        for r in results:
            if "ms" in r:
                legs.append({"metric": f"{r['name']}_ms",
                             "value": r["ms"], "unit": "ms"})
            if "wire_mb" in r:
                legs.append({"metric": f"{r['name']}_wire_mb",
                             "value": r["wire_mb"], "unit": "MB"})
        runlog.RunLedger(a.ledger).append(
            runlog.capture("op_bench", label=variant, legs=legs,
                           include_snapshot=False))
    if a.compare:
        with open(a.compare) as f:
            base = {r["name"]: r for r in json.load(f) if "ms" in r}
        # transport/parity entries gate on wire_mb or a plain wall
        # clock (no scan estimator involved)
        stale = [n for n, r in base.items()
                 if "scan_len" not in r and "wire_mb" not in r
                 and not n.startswith("parity_probe_")]
        if stale:
            print(f"baseline {a.compare} predates the scan-difference "
                  f"estimator (entries without scan_len: {stale}); "
                  "re-record it with --save on this hardware — comparing "
                  "across estimators would gate nothing", file=sys.stderr)
            return 2
        # key validation up front: a baseline/thresholds file whose keys
        # drift from the registered suite must fail with a NAMED diff,
        # not silently skip ops out of the gate (a gate that compares
        # nothing is a false green).  Threshold keys may name any
        # registered op (builtin or current suite) so one measured
        # thresholds file serves subset runs; baseline must cover every
        # op this run gates.
        suite_names = {c.get("name", c.get("op")) for c in suite}
        known = suite_names | {c["name"] for c in BUILTIN_SUITE} \
            | {c["name"] for c in PS_TRANSPORT_SUITE} \
            | {c["name"] for c in ZERO_COLLECTIVES_SUITE} \
            | {c["name"] for c in RING_COLLECTIVES_SUITE} \
            | {c["name"] for c in PARITY_PROBE_SUITE}
        missing_base = sorted(suite_names - set(base))
        if missing_base:
            print(f"baseline {a.compare} has no entry for suite op(s): "
                  f"{missing_base} (baseline keys: {sorted(base)}) — "
                  "the gate would silently skip them; re-record with "
                  "--save or trim the suite", file=sys.stderr)
            return 2
        per_op = {}
        if a.thresholds:
            with open(a.thresholds) as f:
                per_op = json.load(f)
            unknown_thr = sorted(set(per_op) - known)
            if unknown_thr:
                print(f"thresholds {a.thresholds} names unregistered "
                      f"op(s): {unknown_thr} (registered: "
                      f"{sorted(known)}) — a typo'd key silently falls "
                      "back to --threshold; fix the key or remove it",
                      file=sys.stderr)
                return 2
        # a current run that refused/failed to measure an op the
        # baseline covers is the same false green the key validation
        # above guards against: the op leaves the gate with no signal
        ungated = sorted(r.get("name") for r in results
                         if "ms" not in r and r.get("name") in base)
        if ungated:
            print(f"current run produced no timing for baselined "
                  f"op(s): {ungated} — the gate cannot compare them "
                  "(see the per-op error records above); fix the "
                  "measurement or trim the suite", file=sys.stderr)
            return 2
        failed = []
        for r in results:
            b = base.get(r.get("name"))
            if b is None or "ms" not in r:
                continue
            if b.get("device") and r.get("device") and \
                    b["device"] != r["device"]:
                print(f"SKIP {r['name']}: baseline device "
                      f"{b['device']!r} != current {r['device']!r}",
                      file=sys.stderr)
                continue
            if b["ms"] <= 0:
                # a zero/negative baseline (recorded by a pre-guard
                # version) gates nothing and would ZeroDivisionError
                print(f"SKIP {r['name']}: baseline ms {b['ms']!r} <= 0 — "
                      "re-record the baseline with --save",
                      file=sys.stderr)
                continue
            thr = float(per_op.get(r["name"], a.threshold))
            # transport records gate on measured wire bytes (exact,
            # deterministic — "hold the line on transport bytes"); op
            # timings gate on the scan-difference ms as before
            if "wire_mb" in b and "wire_mb" in r:
                metric, unit = "wire_mb", "MB"
                if b["wire_mb"] <= 0:
                    print(f"SKIP {r['name']}: baseline wire_mb "
                          f"{b['wire_mb']!r} <= 0 — re-record",
                          file=sys.stderr)
                    continue
            else:
                metric, unit = "ms", "ms"
            slowdown = r[metric] / b[metric] - 1.0
            if slowdown > thr:
                failed.append((r["name"], b[metric], r[metric], slowdown,
                               thr, unit))
        for name, bms, rms, s, thr, unit in failed:
            print(f"REGRESSION {name}: {bms}{unit} -> {rms}{unit} "
                  f"(+{s:.0%}, allowed +{thr:.0%})", file=sys.stderr)
        if failed:
            return 1
    return 0


if __name__ == "__main__":
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    sys.exit(main())
