"""Benchmarks for all five BASELINE.md configs — one JSON line each.

The reference publishes no in-repo numbers (SURVEY.md §6); baselines are
the driver-assigned north stars from BASELINE.json:

  #1 MNIST LeNet dygraph       — "e2e trains"; vs_baseline = 1 iff loss falls
  #2 ResNet-50 bf16 AMP        — within 1.2× V100 (≈380 samples/s fp16)
  #3 BERT-base pretrain, DP    — within 1.2× V100 (≈25k tokens/s fp16)
  #4 GPT-2 345M fused kernels  — "e2e trains"; vs_baseline vs ≈6k tok/s V100
  #5 Wide&Deep sparse embedding — "e2e trains"; vs_baseline = 1 iff loss falls

Each line: {"metric", "value", "unit", "vs_baseline"}.  The driver records
the output as BENCH_r{N}.json; keep every line parseable on its own.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

V100_BERT_TOKENS_PER_SEC = 25_000.0
V100_RESNET50_SAMPLES_PER_SEC = 380.0
V100_GPT2_345M_TOKENS_PER_SEC = 6_000.0


def _sync(out):
    """Execution barrier: a device->host fetch of the value, which waits
    on every step queued before it."""
    arr = out._data if hasattr(out, "_data") else out
    np.asarray(arr)
    return out


def _timeit(step_fn, warmup, iters):
    for _ in range(warmup):
        out = step_fn()
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step_fn()
    _sync(out)
    return time.perf_counter() - t0, out


# Full-record artifact: every emitted leg is ALSO persisted to a JSON
# file, rewritten atomically after each leg — a truncated driver tail
# (stdout capture keeps only the last N bytes) can therefore never lose
# legs again; the artifact always holds the complete run so far.
# Override the location with BENCH_ARTIFACT=path.  On top of the
# artifact, every completed leg is appended to the persistent run
# ledger (framework/runlog.py; BENCH_LEDGER overrides the default
# runs/ledger.jsonl next to this file) so the bench trajectory is a
# queryable perf history, not a pile of disconnected snapshots.
_RECORDS = []
_ARTIFACT = os.environ.get(
    "BENCH_ARTIFACT",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "BENCH_artifact.json"))
_LEDGER = os.environ.get(
    "BENCH_LEDGER",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "runs", "ledger.jsonl"))

#: artifact/leg record schema: v2 adds schema_version + leg_s to every
#: record.  leg_s is the MONOTONIC wall clock since the PREVIOUS
#: record: a single-metric leg carries its full measurement time; a
#: bench function that emits several metrics back-to-back attributes
#: the shared measurement window to its FIRST record and ~0.0 to the
#: co-emitted ones (the deltas always sum to the run's total)
BENCH_SCHEMA_VERSION = 2


_META = None


def _run_meta():
    """Run metadata stamped into the artifact (git sha+dirty, host,
    FLAGS overrides, versions) — the shared implementation lives in
    framework/runlog.py now.  The fallback covers a caller that has not
    imported the package."""
    global _META
    if _META is not None:
        return _META
    if "paddle_tpu" in sys.modules:
        try:
            from paddle_tpu.framework.runlog import run_meta
            _META = run_meta()
            return _META
        except Exception:          # noqa: BLE001
            pass
    import platform
    import socket
    import subprocess
    import time as _t
    _META = {"host": socket.gethostname(),
             "platform": platform.platform(),
             "python": platform.python_version(),
             "time": _t.strftime("%Y-%m-%dT%H:%M:%S%z"),
             "argv": sys.argv[1:]}
    # git attribution needs no package import
    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        _META["git_sha"] = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=repo, capture_output=True,
            text=True, timeout=10).stdout.strip() or None
    except Exception:              # noqa: BLE001 — no git, shallow, etc.
        _META["git_sha"] = None
    try:
        _META["git_dirty"] = bool(subprocess.run(
            ["git", "status", "--porcelain"], cwd=repo,
            capture_output=True, text=True, timeout=10).stdout.strip())
    except Exception:              # noqa: BLE001
        _META["git_dirty"] = None
    return _META


def _write_artifact(complete):
    try:
        tmp = _ARTIFACT + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            # default=str: a non-JSON-serializable flag override in the
            # meta must degrade to its repr, not raise mid-bench
            json.dump({"meta": _run_meta(),
                       "schema_version": BENCH_SCHEMA_VERSION,
                       "records": _RECORDS,
                       "complete": complete}, f, indent=1, default=str)
        os.replace(tmp, _ARTIFACT)
    except Exception as e:         # noqa: BLE001
        # the artifact must never fail a bench — but a silent loss is a
        # post-mortem hole: degrade to a flight event when possible
        try:
            if "paddle_tpu" in sys.modules:
                from paddle_tpu.framework.observability import flight
                flight.record("bench.artifact_error", severity="warn",
                              path=_ARTIFACT, error=repr(e))
        except Exception:          # noqa: BLE001
            pass


def _append_ledger(rec):
    """One run-ledger record per completed leg.  Skipped when the
    package is not imported; RunLedger.append itself never raises —
    ledger I/O faults degrade to a flight event + counter, never a
    crashed bench."""
    if "paddle_tpu" not in sys.modules:
        return
    try:
        from paddle_tpu.framework import runlog
        # per-leg records carry the leg only (no registry snapshot):
        # process-cumulative counters ramp WITHIN a multi-leg bench
        # run and would read as cross-run regressions; the cross-run
        # series for bench is the leg metrics themselves
        runlog.RunLedger(_LEDGER).append(
            runlog.capture("bench", label="bench", legs=[rec],
                           include_snapshot=False))
    except Exception:              # noqa: BLE001
        pass


_LEG_T0 = [time.monotonic()]


def _emit(metric, value, unit, vs_baseline):
    now = time.monotonic()
    rec = {"metric": metric, "value": round(float(value), 3),
           "unit": unit, "vs_baseline": round(float(vs_baseline), 3),
           "schema_version": BENCH_SCHEMA_VERSION,
           "leg_s": round(now - _LEG_T0[0], 3)}
    _LEG_T0[0] = now
    print(json.dumps(rec), flush=True)
    _RECORDS.append(rec)
    _write_artifact(complete=False)
    _append_ledger(rec)


def _finalize_artifact():
    _write_artifact(complete=True)


def bench_bert(on_accel):
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import Bert, BertConfig, bert_pretrain_loss

    if on_accel:
        # swept: B=64 no-remat 110k tok/s; B=128 OOMs without remat but
        # remat's recompute buys the batch: 146k tok/s
        B, S = 128, 128
        cfg = BertConfig(max_seq_len=S, remat=True)
    else:
        B, S = 8, 64
        cfg = BertConfig(hidden_size=128, num_layers=2, num_heads=4,
                         vocab_size=8192, max_seq_len=S, remat=False)
    model = Bert(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = TrainStep(model, bert_pretrain_loss, opt, amp_level="O2",
                     amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size,
                                        size=(B, S)).astype(np.int32))
    mlm = paddle.to_tensor(np.where(rng.random((B, S)) < 0.15,
                                    ids.numpy(), -100).astype(np.int32))
    nsp = paddle.to_tensor(rng.integers(0, 2, size=(B,)).astype(np.int32))
    iters = 20 if on_accel else 5
    dt, _ = _timeit(lambda: step(ids, mlm, nsp), 3, iters)
    tps = B * S * iters / dt
    _emit("bert_base_pretrain_tokens_per_sec_per_chip", tps, "tokens/s",
          tps / V100_BERT_TOKENS_PER_SEC)

    # padded-batch variant (VERDICT r2 #1): per-sample lengths as an
    # attention mask; vs_baseline = retention vs the unmasked number.
    # NOTE which path serves it: at this config's S=128 the dispatch gate
    # keeps attention on the (faster-at-short-S) XLA bias path — masked
    # retention ≈0.99 either way; the Pallas masked kernel takes over at
    # S≥1024, where it measured 0.991 retention and 1.13× the XLA path
    # at S=2048 (see ops/pallas/flash_attention.py supported())
    lens = rng.integers(S // 2, S + 1, size=(B,))
    amask = (np.arange(S)[None, :] < lens[:, None])
    mlm_pad = paddle.to_tensor(
        np.where(amask, mlm.numpy(), -100).astype(np.int32))
    amask_t = paddle.to_tensor(amask.astype(np.int32))
    dt_m, _ = _timeit(lambda: step(ids, mlm_pad, nsp, amask_t), 3, iters)
    tps_m = B * S * iters / dt_m
    _emit("bert_padded_mask_tokens_per_sec_per_chip", tps_m, "tokens/s",
          tps_m / tps)


def bench_resnet50(on_accel):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50, resnet18

    if on_accel:
        B, HW = 128, 224        # swept 64/128/256: 128 peaks on one chip
        model = resnet50(num_classes=1000)
    else:
        B, HW = 8, 64
        model = resnet18(num_classes=10)
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())

    def loss_fn(m, x, y):
        return F.cross_entropy(m(x), y).mean()

    step = TrainStep(model, loss_fn, opt, amp_level="O2",
                     amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.standard_normal((B, 3, HW, HW)).astype(np.float32))
    n_cls = 1000 if on_accel else 10
    y = paddle.to_tensor(rng.integers(0, n_cls, size=(B,)).astype(np.int64))
    iters = 20 if on_accel else 3
    dt, _ = _timeit(lambda: step(x, y), 3, iters)
    sps = B * iters / dt
    _RESNET_SYNTH_SPS[0] = sps
    _emit("resnet50_train_samples_per_sec_per_chip_bf16", sps, "samples/s",
          sps / V100_RESNET50_SAMPLES_PER_SEC)


def bench_gpt2_345m(on_accel):
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPT, gpt2_345m, gpt_tiny, gpt_loss

    if on_accel:
        # swept 4/8/16: B=8 peaks on one chip; 345M at B=8 fits HBM
        # without remat (B>=12 doesn't compile) — dropping the replayed
        # forward measured +26% (30.6k -> 38.5k tok/s); full unroll of
        # the layer scan lets XLA schedule across layers
        B, S = 8, 1024
        cfg = gpt2_345m(remat=False, max_seq_len=S, scan_unroll=24)
    else:
        B, S = 2, 128
        cfg = gpt_tiny(num_layers=2, remat=True, max_seq_len=S)
    model = GPT(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = TrainStep(model, gpt_loss, opt, amp_level="O2",
                     amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size,
                                        size=(B, S)).astype(np.int32))
    iters = 10 if on_accel else 3
    # NOT multi_step here: its lax.scan double-buffers the carry (a
    # second live copy of 345M params + adam states), and at B=8
    # no-remat the model already fills HBM — measured 4.6k tok/s of
    # host spill vs 39k+ with per-step dispatch.  The device loop pays
    # off for dispatch-bound models (see bench_lenet), not HBM-bound.
    dt, _ = _timeit(lambda: step(ids, ids), 3, iters)
    tps = B * S * iters / dt
    _emit("gpt2_345m_train_tokens_per_sec_per_chip_bf16", tps, "tokens/s",
          tps / V100_GPT2_345M_TOKENS_PER_SEC)


def bench_gpt2_zero(on_accel):
    """GPT-2 under the ZeRO sharded weight update at dp=2 (simulated
    replicas on CPU, real chips when >= 2 are attached): tokens/s plus
    the measured optimizer-state bytes ONE replica holds vs the
    replicated-baseline bytes (vs_baseline on that metric is the
    sharded/replicated ratio — lower is better, ~0.5 at dp=2), the
    bf16 collective wire bytes vs the f32 leg (~0.5), and a fused
    chunked-ring leg (int4 wire) whose MEASURED per-step collective
    bytes ratio fused/unfused lands well under the bf16 leg's."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models import GPT, gpt_tiny, gpt2_345m, gpt_loss
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.zero import ShardedUpdateTrainStep

    if len(jax.devices()) < 2:
        _emit("gpt2_zero_dp2_SKIPPED_single_device", 0.0, "n/a", 0.0)
        return
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    if on_accel:
        B, S = 8, 1024
        cfg = gpt2_345m(remat=False, max_seq_len=S, scan_unroll=24)
    else:
        B, S = 2, 128
        cfg = gpt_tiny(num_layers=2, remat=True, max_seq_len=S)
    model = GPT(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = ShardedUpdateTrainStep(model, gpt_loss, opt, mesh=mesh,
                                  wire_dtype="bf16", amp_level="O2",
                                  amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size,
                                        size=(B, S)).astype(np.int32))
    iters = 10 if on_accel else 3
    dt, _ = _timeit(lambda: step(ids, ids), 2, iters)
    tps = B * S * iters / dt
    _emit("gpt2_zero_dp2_tokens_per_sec_bf16_wire", tps, "tokens/s",
          tps / V100_GPT2_345M_TOKENS_PER_SEC)

    sharded_bytes = step.opt_state_bytes_per_replica()
    # replicated baseline: every replica holds full-width moments —
    # slot-for-slot the same structure on the UNPADDED leaves
    probe = opt.init_state(jnp.zeros((4,), jnp.float32))
    vec_slots = sum(1 for v in probe.values() if jnp.ndim(v) == 1)
    scalar_bytes = sum(int(jnp.asarray(v).nbytes) for v in probe.values()
                       if jnp.ndim(v) == 0)
    replicated = sum(vec_slots * int(p._data.nbytes) + scalar_bytes
                     for _, p in model.named_parameters())
    _emit("gpt2_zero_opt_state_bytes_per_replica", sharded_bytes,
          "bytes", sharded_bytes / max(replicated, 1))

    wire = step.collective_wire_bytes()
    f32 = step.collective_wire_bytes(wire="f32")   # pure shape math
    bf16_total = wire["reduce_scatter"] + wire["all_gather"]
    f32_total = f32["reduce_scatter"] + f32["all_gather"]
    _emit("gpt2_zero_bf16_collective_bytes_per_step", bf16_total,
          "bytes", bf16_total / max(f32_total, 1))

    # fused chunked-ring leg (parallel/ring.py, int4 wire): same model
    # and step shape, the collectives ride the quantize-while-permute
    # ring schedule.  Bytes are MEASURED off the step's own per-step
    # stat (not shape math), and vs_baseline is fused/unfused — the
    # ring's wire against the bf16 leg this bench just measured
    from paddle_tpu.framework import monitor
    unfused_bytes = float(monitor.get_stat(
        "zero_collective_bytes_per_step") or bf16_total)
    model_r = GPT(cfg)
    opt_r = optimizer.AdamW(learning_rate=1e-4,
                            parameters=model_r.parameters())
    ring_step = ShardedUpdateTrainStep(model_r, gpt_loss, opt_r,
                                       mesh=mesh, wire_dtype="int4",
                                       ring=True, amp_level="O2",
                                       amp_dtype="bfloat16")
    dt, _ = _timeit(lambda: ring_step(ids, ids), 2, iters)
    tps_r = B * S * iters / dt
    _emit("gpt2_zero_ring_int4_tokens_per_sec", tps_r, "tokens/s",
          tps_r / max(tps, 1e-9))
    ring_bytes = float(monitor.get_stat(
        "zero_collective_bytes_per_step") or 0.0)
    _emit("gpt2_zero_ring_int4_collective_bytes_per_step", ring_bytes,
          "bytes", ring_bytes / max(unfused_bytes, 1))


def bench_widedeep(on_accel):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import WideDeep

    if on_accel:
        B, feats = 4096, 1_000_000
    else:
        B, feats = 256, 10_000
    model = WideDeep(num_features=feats, embedding_dim=16, num_fields=26,
                     dense_dim=13)
    opt = optimizer.Adam(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(m, ids, x, y):
        return F.binary_cross_entropy_with_logits(m(ids, x), y).mean()

    step = TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, feats,
                                        size=(B, 26)).astype(np.int32))
    x = paddle.to_tensor(rng.standard_normal((B, 13)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 2, size=(B, 1)).astype(np.float32))
    first = float(step(ids, x, y))
    iters = 20 if on_accel else 3
    dt, last = _timeit(lambda: step(ids, x, y), 2, iters)
    eps = B * iters / dt
    trains = float(last) < first
    _emit("widedeep_sparse_train_examples_per_sec_per_chip", eps,
          "examples/s", 1.0 if trains else 0.0)


def bench_widedeep_ps(on_accel, extra_legs=True):
    """The sparse tier benched THROUGH the sparse tier (VERDICT r2 #3):
    a 100M-id × 65 host-RAM table (26 GB + adagrad state — cannot live in
    HBM next to model/activations) trained via PSTrainStep: host pull →
    one fused XLA dense step (fwd+bwd+dense-update+row grads) → async
    push with host-side adagrad.  vs_baseline = 1 iff loss falls.
    Reference: distributed/table/common_sparse_table.cc +
    service/communicator.cc + DownpourWorker (device_worker.h:271)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.distributed.ps import (AsyncCommunicator,
                                           DistributedEmbedding,
                                           HostEmbeddingTable, PSTrainStep)
    from paddle_tpu.models import WideDeepHost

    if on_accel:
        # B swept 1k..32k before PR 1: knee at 16k —
        # pulls stay <0.5% of the step throughout; beyond 16k the dense
        # leg + host unique prep dominate and throughput falls
        B, V, E = 16384, 100_000_000, 64
    else:
        B, V, E = 256, 50_000, 8
    fields, dense_dim = 26, 13
    emb = DistributedEmbedding(V, E + 1, optimizer="adagrad",
                               learning_rate=0.05, mode="async")
    model = WideDeepHost(embedding_dim=E, num_fields=fields,
                         dense_dim=dense_dim)
    opt = optimizer.Adam(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(m, rows, x, y):
        return F.binary_cross_entropy_with_logits(m(rows, x), y).mean()

    step = PSTrainStep(model, loss_fn, opt, emb)
    rng = np.random.default_rng(0)
    # Zipf-ish id draw: realistic PS workloads hit a hot head + long tail
    ids = (rng.zipf(1.3, size=(B, fields)) % V).astype(np.int64)
    x = paddle.to_tensor(rng.standard_normal((B, dense_dim))
                         .astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 2, size=(B, 1)).astype(np.float32))
    first = float(step(ids, x, y))
    iters = 20 if on_accel else 3
    dt, last = _timeit(lambda: step(ids, x, y), 2, iters)
    step.flush()                    # drain async pushes before judging
    eps = B * iters / dt
    trains = float(last) < first
    _emit("widedeep_ps_host_table_100M_examples_per_sec", eps,
          "examples/s", 1.0 if trains else 0.0)
    if not extra_legs:      # variance study re-measures only this leg
        return

    # --- file-fed leg (VERDICT r3 #1): the same PSTrainStep fed from the
    # reference slot-text protocol through the native C++ datafeed engine
    # (ops/native/datafeed.cpp, the data_feed.cc role), ingest inside the
    # timed region ------------------------------------------------------
    from paddle_tpu.ops.native import MultiSlotDataFeed, native_available
    if not native_available():
        return
    n_ex = B * 6 if on_accel else B * 3
    root = f"/tmp/paddle_tpu_bench_slots_{n_ex}_{fields}"
    _gen_slot_dataset(root, n_ex, fields, dense_dim, V)
    files = sorted(os.path.join(root, f) for f in os.listdir(root)
                   if f.endswith(".txt"))
    slot_bytes = sum(os.path.getsize(f) for f in files)
    slots = [(f"c{i}", "u", 1) for i in range(fields)] + \
        [("dense", "f", dense_dim), ("label", "f", 1)]

    # 1) standalone datafeed drain: parse+batch rate with no training
    feed = MultiSlotDataFeed(slots, B, files=files, nthreads=4)
    n_p = 0
    t0 = time.perf_counter()
    for b in feed:
        n_p += len(b["label"])
    dt_p = time.perf_counter() - t0
    _emit("datafeed_ingest_examples_per_sec", n_p / dt_p, "examples/s", 1.0)
    _emit("datafeed_ingest_mb_per_sec", slot_bytes / dt_p / 1e6, "MB/s", 1.0)

    # 2) file-fed PS training: parse -> assemble -> pull/push + dense step
    def batches():
        feed = MultiSlotDataFeed(slots, B, files=files, nthreads=4)
        for b in feed:
            rows = len(b["label"])
            if rows != B:
                continue            # PSTrainStep compiled for B
            ids_b = np.stack([b[f"c{i}"][0] for i in range(fields)],
                             axis=1)
            yield (ids_b, paddle.to_tensor(b["dense"]),
                   paddle.to_tensor(b["label"]))

    for ids_b, x_b, y_b in batches():      # warm (compile already done)
        loss = step(ids_b, x_b, y_b)
        break
    _sync(loss)
    n_t = 0
    t0 = time.perf_counter()
    for ids_b, x_b, y_b in batches():
        loss = step(ids_b, x_b, y_b)
        n_t += B
    _sync(loss)
    step.flush()
    dt_t = time.perf_counter() - t0
    eps_f = n_t / dt_t
    _emit("widedeep_ps_filefed_examples_per_sec", eps_f, "examples/s",
          eps_f / eps)

    # --- remote-transport leg (VERDICT r3 #3): the same table size served
    # from a SECOND PROCESS over localhost TCP (ps/service.py — the brpc
    # pull/push role), trained through RemoteEmbeddingTable +
    # AsyncCommunicator.  vs_baseline = remote/in-process ratio. ---------
    import subprocess
    import sys as _sys
    from paddle_tpu.distributed.ps.service import (SERVER_BOOT, PsClient,
                                                   RemoteEmbeddingTable)
    srv = subprocess.Popen(
        [_sys.executable, "-c", SERVER_BOOT,
         "--port", "0", "--table", f"emb:{V}:{E + 1}:adagrad:0.05",
         "--n-workers", "1"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    try:
        line = srv.stdout.readline()        # "PS_READY host:port"
        if not line.startswith("PS_READY"):
            err = srv.stderr.read() if srv.poll() is not None else ""
            raise RuntimeError(
                f"PS server failed to start: {line!r} {err[-500:]}")
        ep = line.strip().split()[1]
        client = PsClient([ep])    # wire dtype: FLAGS_ps_wire_dtype (bf16)
        emb_r = DistributedEmbedding(
            V, E + 1, mode="async",
            table=RemoteEmbeddingTable(client, "emb", E + 1))
        model_r = WideDeepHost(embedding_dim=E, num_fields=fields,
                               dense_dim=dense_dim)
        opt_r = optimizer.Adam(learning_rate=1e-3,
                               parameters=model_r.parameters())
        step_r = PSTrainStep(model_r, loss_fn, opt_r, emb_r)
        first_r = float(step_r(ids, x, y))

        # pipelined loop: announce the next batch before every step so
        # the shard fan-out (pull + coalesced previous push, one RPC
        # round-trip per shard) overlaps the device computation
        def piped():
            step_r.prefetch(ids)
            return step_r(ids, x, y)

        step_r.flush()     # drain the warm step's queued async push so
        snap0 = client.transport_stats()       # it lands OUTSIDE the window
        step_r.prefetch(ids)                   # prime the double buffer
        dt_r, last_r = _timeit(piped, 2, iters)
        step_r.flush()     # drain in-flight prefetch + deferred push so
        snap1 = client.transport_stats()       # the byte window is complete
        eps_r = B * iters / dt_r
        # MEASURED wire MB/step (client byte counters across the timed
        # region, warmup included); vs_baseline = measured / the f32
        # analytic formula this leg used to report (ids up + f32 rows
        # down + id+grad rows up at the bucketed unique count), so the
        # quantized wire's saving is the ratio
        uniq = len(np.unique(ids))
        cap = max(256, 1 << int(np.ceil(np.log2(uniq))))
        analytic_f32_mb = cap * (8 + 2 * (E + 1) * 4 + 8) / 1e6
        n_steps = 2 + iters                    # warmup rides the counters
        wire_mb = ((snap1["bytes_sent"] - snap0["bytes_sent"]) +
                   (snap1["bytes_recv"] - snap0["bytes_recv"])) \
            / n_steps / 1e6
        _emit("widedeep_ps_remote_examples_per_sec", eps_r, "examples/s",
              eps_r / eps if float(last_r) < first_r else 0.0)
        _emit("widedeep_ps_remote_wire_mb_per_step", wire_mb, "MB",
              wire_mb / analytic_f32_mb)
        client.bye()
    finally:
        srv.terminate()


def bench_widedeep_device(on_accel):
    """The heter-PS device tier (VERDICT r4 #2): a 10M-row x 64 table
    RESIDENT IN HBM, range-sharded over the mesh, trained through
    DeviceEmbeddingTrainStep — dedup + collective exchange + touched-
    rows adagrad, all inside one XLA step, nothing crossing the host
    boundary.  On the single bench chip the exchange degenerates to
    K=1 (sharding correctness is held by tests/test_device_table.py
    and the driver dryrun); the measured number is the device-resident
    pull->train->push cycle against the SAME W&D shape the 100M host-
    table leg runs, so the two tiers are directly comparable.
    Reference: framework/fleet/heter_ps/hashtable.h, ps_gpu_wrapper.cc."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.distributed.ps import (DeviceEmbeddingTrainStep,
                                           MeshShardedEmbedding)
    from paddle_tpu.models import WideDeepHost
    from paddle_tpu.parallel import get_mesh

    if on_accel:
        B, V, E = 16384, 10_000_000, 64
    else:
        B, V, E = 256, 50_000, 8
    fields, dense_dim = 26, 13
    emb = MeshShardedEmbedding(V, E + 1, mesh_axis="dp", seed=0)
    model = WideDeepHost(embedding_dim=E, num_fields=fields,
                         dense_dim=dense_dim)
    opt = optimizer.Adam(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(m, rows, x, y):
        return F.binary_cross_entropy_with_logits(m(rows, x), y).mean()

    step = DeviceEmbeddingTrainStep(model, loss_fn, opt, emb,
                                    mesh=get_mesh(), table_lr=0.05)
    rng = np.random.default_rng(0)
    ids = (rng.zipf(1.3, size=(B, fields)) % V).astype(np.int32)
    x = paddle.to_tensor(rng.standard_normal((B, dense_dim))
                         .astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 2, size=(B, 1)).astype(np.float32))
    first = float(step(ids, x, y))
    iters = 20 if on_accel else 3
    dt, last = _timeit(lambda: step(ids, x, y), 2, iters)
    eps = B * iters / dt
    trains = float(last) < first
    _emit("widedeep_device_sharded_10M_examples_per_sec", eps,
          "examples/s", 1.0 if trains else 0.0)


def bench_int8_resnet18(on_accel):
    """Int8 inference vs bf16 on ResNet-18 (VERDICT r4 #6): the PTQ
    deploy pass (convert_to_int8_inference) swaps every conv/linear for
    the s8 x s8 -> s32 MXU path; vs_baseline = int8/bf16 throughput
    ratio, and the top-1 agreement with the float model is asserted
    before timing so a broken quantization can't post a fast number.
    Reference: contrib/slim + inference/api/mkldnn_quantizer.cc."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import to_static
    from paddle_tpu.quantization import convert_to_int8_inference
    from paddle_tpu.vision.models import resnet18

    B, hw = (128, 224) if on_accel else (8, 32)
    # two SEPARATE instances with identical weights: to_static returns
    # the same Layer object and convert_to_int8_inference mutates in
    # place, so one instance would make the "bf16 baseline" time int8
    paddle.seed(0)
    net = resnet18(num_classes=1000)
    net.eval()
    paddle.seed(0)
    net_q = resnet18(num_classes=1000)
    net_q.eval()
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (B, 3, hw, hw)).astype(np.float32))

    f32 = to_static(net)
    ref = np.asarray(f32(x)._data)
    qnet = convert_to_int8_inference(net_q)
    q = to_static(qnet)
    got = np.asarray(q(x)._data)
    agree = float((got.argmax(1) == ref.argmax(1)).mean())
    iters = 20 if on_accel else 3
    dt_f, _ = _timeit(lambda: f32(x), 2, iters)
    dt_q, _ = _timeit(lambda: q(x), 2, iters)
    ips = B * iters / dt_q
    _emit("resnet18_int8_infer_images_per_sec", ips, "images/s",
          (dt_f / dt_q) if agree >= 0.7 else 0.0)
    _emit("resnet18_int8_top1_agreement", agree, "fraction", agree)


def _gen_image_dataset(root, n_images, size, classes):
    """Directory-per-class JPEG tree (generated once, cached on disk) —
    the file-fed ResNet leg's input.  Deterministic content."""
    import io as _io

    from PIL import Image

    done = os.path.join(root, ".done")
    if os.path.exists(done):
        return
    rng = np.random.default_rng(7)
    for c in range(classes):
        os.makedirs(os.path.join(root, f"class_{c:02d}"), exist_ok=True)
    for i in range(n_images):
        c = i % classes
        arr = rng.integers(0, 256, size=(size, size, 3), dtype=np.uint8)
        img = Image.fromarray(arr)
        img.save(os.path.join(root, f"class_{c:02d}", f"{i:05d}.jpg"),
                 quality=85)
    with open(done, "w") as f:
        f.write(str(n_images))


def _gen_slot_dataset(root, n_examples, fields, dense_dim, vocab, n_files=4):
    """MultiSlotDataFeed text files (the reference's slot protocol):
    26 one-id sparse slots + a 13-float dense slot + a 1-float label."""
    done = os.path.join(root, ".done")
    if os.path.exists(done):
        return
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(11)
    per = n_examples // n_files
    for fi in range(n_files):
        ids = (rng.zipf(1.3, size=(per, fields)) % vocab).astype(np.int64)
        dense = rng.standard_normal((per, dense_dim)).astype(np.float32)
        y = rng.integers(0, 2, size=(per,))
        with open(os.path.join(root, f"part-{fi:03d}.txt"), "w") as f:
            for r in range(per):
                parts = [f"1 {v}" for v in ids[r]]
                parts.append(f"{dense_dim} " + " ".join(
                    f"{v:.4f}" for v in dense[r]))
                parts.append(f"1 {y[r]}")
                f.write(" ".join(parts) + "\n")
    with open(done, "w") as f:
        f.write(str(n_examples))


_RESNET_SYNTH_SPS = [None]   # set by bench_resnet50, read by the filefed leg


_FF_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
_FF_STD = np.array([0.229, 0.224, 0.225], np.float32)


def _pil_loader(path):
    # module-level so a spawned DataLoader worker can unpickle the
    # DatasetFolder that references it
    from PIL import Image
    with Image.open(path) as im:
        return np.asarray(im.convert("RGB"))


def _filefed_collate(batch):
    """Batch-granularity normalize + NCHW + device-free stack: the
    per-sample pipeline stays uint8 HWC (decode + augment only), so one
    vectorized numpy pass here replaces B per-sample normalizes and the
    transfer stage ships ONE contiguous array per field."""
    imgs = np.stack([s[0] for s in batch]).astype(np.float32) / 255.0
    imgs = (imgs - _FF_MEAN) / _FF_STD
    x = np.ascontiguousarray(imgs.transpose(0, 3, 1, 2))
    y = np.asarray([s[1] for s in batch], np.int64)
    return x, y


def bench_resnet50_filefed(on_accel):
    """The dense file-fed path through the streaming ingest plane
    (io/pipeline.py): JPEG decode + uint8 augment per sample,
    batch-granularity normalize at collate, double-buffered device
    transfer, and a decoded-sample cache for epoch >= 2.

    Legs and metrics:

    1. pipelined ingest drain, cache OFF (`..._ingest_examples_per_sec`,
       `..._ingest_mb_per_sec`) — the epoch-1 rate; must not regress
       vs the pre-pipeline number;
    2. worker-pool drain (`..._worker_ingest_examples_per_sec`, timed
       from the first batch so child-spawn cost is excluded) —
       vs_baseline IS the measured num_workers efficiency factor;
    3. cached-epoch drain (`..._cached_ingest_examples_per_sec`,
       vs_baseline = cache speedup over the epoch-1 rate) — epoch 1
       records augmented uint8 tensors, epoch 2 skips JPEG decode
       entirely (cached-augmentation tradeoff: live augmentation stays
       available via CachedDataset(transform=...), not benched here);
    4. cached-epoch TRAINING (`..._train_samples_per_sec` vs the
       synthetic leg, plus `..._input_stall_pct` measured by the
       pipeline itself: wait / (wait + step) — the gate target is
       < 10% with the cache hot).
    """
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.io import DataLoader
    from paddle_tpu.io.pipeline import (CachedDataset, IngestPipeline,
                                        SampleCache)
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision import transforms as T
    from paddle_tpu.vision.datasets import DatasetFolder
    from paddle_tpu.vision.models import resnet18, resnet50

    if on_accel:
        B, HW, n_img = 128, 224, 768
        model = resnet50(num_classes=1000)
    else:
        B, HW, n_img = 8, 64, 64
        model = resnet18(num_classes=10)
    root = f"/tmp/paddle_tpu_bench_images_{HW}_{n_img}"
    _gen_image_dataset(root, n_img, HW + 32, 10)
    jpeg_bytes = sum(
        os.path.getsize(os.path.join(d, f))
        for d, _, fs in os.walk(root) for f in fs if f.endswith(".jpg"))

    # per-sample pipeline: decode + augment only, uint8 HWC end to end
    # (normalize/transpose happen vectorized in _filefed_collate; a
    # per-sample device tensor costs one host->device transfer per image)
    aug = T.Compose([T.RandomResizedCrop(HW), T.RandomHorizontalFlip()])

    ds = DatasetFolder(root, loader=_pil_loader, extensions=(".jpg",),
                       transform=aug)

    def drain(pipe, from_first_batch=False):
        n, t0 = 0, time.perf_counter()
        for xb, yb in pipe:
            if from_first_batch and n == 0:
                t0 = time.perf_counter()   # exclude worker spawn
            n += int(xb.shape[0])
        dt = time.perf_counter() - t0
        if from_first_batch:
            n -= B                         # first batch not in the window
        return n, max(dt, 1e-9)            # n == 0: caller falls back

    # 1) pipelined ingest drain, cache off: epoch-1 decode+augment rate
    loader = DataLoader(ds, batch_size=B, shuffle=True, drop_last=True,
                        collate_fn=_filefed_collate)
    n_ing, dt_ing = drain(IngestPipeline(loader))
    rate_e1 = n_ing / dt_ing
    _emit("resnet50_filefed_ingest_examples_per_sec", rate_e1,
          "examples/s", 1.0)
    _emit("resnet50_filefed_ingest_mb_per_sec",
          jpeg_bytes / dt_ing / 1e6 * (n_ing / len(ds)), "MB/s", 1.0)

    # 2) process-worker pool with in-worker collate: vs = the measured
    # per-worker efficiency (perf/worker_scaling.py's slope)
    wloader = DataLoader(ds, batch_size=B, shuffle=True, drop_last=True,
                         collate_fn=_filefed_collate, num_workers=1,
                         use_process_workers=True, collate_in_worker=True)
    n_w, dt_w = drain(IngestPipeline(wloader), from_first_batch=True)
    rate_w = n_w / dt_w if n_w > 0 else rate_e1
    _emit("resnet50_filefed_worker_ingest_examples_per_sec", rate_w,
          "examples/s", rate_w / rate_e1)

    # 3) decoded-sample cache: epoch 1 records, epoch 2 skips decode
    cache = SampleCache(mode="memory", max_bytes=2 << 30)
    cds = CachedDataset(ds, cache)

    def cached_loader():
        return DataLoader(cds, batch_size=B, shuffle=True,
                          drop_last=True, collate_fn=_filefed_collate)

    drain(IngestPipeline(cached_loader()))            # epoch 1: record
    n_c, dt_c = drain(IngestPipeline(cached_loader()))  # epoch 2: hits
    rate_cached = n_c / dt_c
    _emit("resnet50_filefed_cached_ingest_examples_per_sec", rate_cached,
          "examples/s", rate_cached / rate_e1)

    # 4) cached-epoch training: pipeline-measured stall is the gate
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())

    def loss_fn(m, x, y):
        return F.cross_entropy(m(x), y).mean()

    step = TrainStep(model, loss_fn, opt, amp_level="O2",
                     amp_dtype="bfloat16")
    for xb, yb in IngestPipeline(cached_loader()):
        loss = step(xb, yb)                # compile + warm one batch
        break
    _sync(loss)
    pipe = IngestPipeline(cached_loader())
    n_tr = 0
    t0 = time.perf_counter()
    for xb, yb in pipe:
        loss = step(xb, yb)
        n_tr += int(xb.shape[0])
    _sync(loss)
    dt_tr = time.perf_counter() - t0
    sps = n_tr / dt_tr
    synth = _RESNET_SYNTH_SPS[0]
    _emit("resnet50_filefed_train_samples_per_sec", sps, "samples/s",
          sps / synth if synth else 1.0)
    _emit("resnet50_filefed_input_stall_pct", pipe.input_stall_pct,
          "%", 1.0)


def bench_lenet(on_accel):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import LeNet

    B = 256 if on_accel else 64
    model = LeNet(num_classes=10)
    opt = optimizer.Adam(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(m, x, y):
        return F.cross_entropy(m(x), y).mean()

    step = TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.standard_normal((B, 1, 28, 28)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, size=(B,)).astype(np.int64))
    first = float(step(x, y))
    iters = 50 if on_accel else 5
    dt, last = _timeit(lambda: step(x, y), 2, iters)
    sps = B * iters / dt
    trains = float(last) < first
    _emit("lenet_mnist_train_samples_per_sec", sps, "samples/s",
          1.0 if trains else 0.0)


def bench_longseq_flash(on_accel):
    """Long-sequence *training* with the Pallas flash-attention fwd+bwd
    kernels — the config whose naive S×S backward would exhaust HBM
    (S=8192: scores alone are 8k×8k×nh×B ≈ 8 GiB fp32 per layer).
    vs_baseline is the raw throughput retention tokens/s(S=8k) /
    tokens/s(S=2k): attention FLOPs/token grow ~4× over that range, so
    anything ≥ ~0.5 means no quadratic-memory cliff; >1 happens when the
    short-sequence config underutilises the chip (B=1, S=2k)."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPT, gpt_tiny, gpt_loss

    if on_accel:
        B, S_long, S_ref = 1, 8192, 2048
        layers, width = 4, 1024
    else:
        B, S_long, S_ref = 1, 512, 128
        layers, width = 2, 128
    rng = np.random.default_rng(0)

    def tokens_per_sec(S, iters):
        cfg = gpt_tiny(num_layers=layers, hidden_size=width,
                       num_heads=max(8, width // 128),
                       vocab_size=8192, max_seq_len=S, remat=True)
        model = GPT(cfg)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        step = TrainStep(model, gpt_loss, opt, amp_level="O2",
                         amp_dtype="bfloat16")
        ids = paddle.to_tensor(rng.integers(
            0, cfg.vocab_size, size=(B, S)).astype(np.int32))
        dt, _ = _timeit(lambda: step(ids, ids), 2, iters)
        return B * S * iters / dt

    tps_ref = tokens_per_sec(S_ref, 6 if on_accel else 2)
    tps_long = tokens_per_sec(S_long, 3 if on_accel else 2)
    _emit("gpt_longseq8k_flashattn_train_tokens_per_sec", tps_long,
          "tokens/s", tps_long / tps_ref)


def bench_masked_flash(on_accel):
    """Round-3 weak item 5: the bert_padded_mask headline measures XLA's
    masked attention (supported() routes non-causal S<1024 there — the
    right dispatch), so no number isolated the masked PALLAS kernel's
    overhead at the lengths it serves.  This leg times the kernel
    fwd+bwd at S=2048 with a padding bias vs without: vs_baseline is
    the masked/unmasked retention of the kernel itself."""
    if not on_accel:
        return
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    B, S, H, D = 4, 2048, 16, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    lens = rng.integers(S // 2, S + 1, size=(B,))
    bias = jnp.asarray(
        np.where(np.arange(S)[None, :] < lens[:, None], 0.0, -1e30)
        .astype(np.float32)[:, None, None, :])
    assert fa.supported(q.shape, k.shape, bias_shape=bias.shape)
    reps = 20

    def timed(masked):
        @jax.jit
        def many(q, k, v):
            g = jax.grad(lambda q, k, v: fa.flash_attention(
                q, k, v, bias=bias if masked else None,
                bias_grad=False).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))

            def body(c, _):
                dq, _, _ = g(q + c, k, v)
                return c + dq.mean().astype(q.dtype) * 0, None
            c, _ = jax.lax.scan(body, jnp.zeros((), q.dtype), None,
                                length=reps)
            return c
        out = many(q, k, v)
        np.asarray(jax.device_get(out))
        t0 = time.perf_counter()
        out = many(q, k, v)
        np.asarray(jax.device_get(out))
        return (time.perf_counter() - t0) / reps

    t_plain = timed(False)
    t_masked = timed(True)
    tps = B * S / t_masked
    _emit("masked_flash_kernel_s2048_tokens_per_sec", tps, "tokens/s",
          t_plain / t_masked)


def main():
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py: refusing to run on backend "
            f"{jax.default_backend()!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}): every metric here is a "
            f"device metric and is measured on a TPU only")

    import paddle_tpu as paddle
    from paddle_tpu.parallel import make_mesh, set_mesh
    from paddle_tpu.framework.autopilot import maybe_apply_tuned_profile

    paddle.device.use_compile_cache()

    # FLAGS_autotune_profile (tools/autotune.py output) retargets the
    # wire/prefetch knobs before any bench constructs a train step
    maybe_apply_tuned_profile(source="bench")

    # always True past the backend check above; the toy presets behind
    # on_accel=False are unreachable and go when ROADMAP S0 rewrites this
    on_accel = paddle.is_compiled_with_tpu()
    set_mesh(make_mesh({"dp": 1}, devices=jax.devices()[:1]))

    failed = []
    for bench in (bench_bert, bench_resnet50, bench_gpt2_345m,
                  bench_gpt2_zero, bench_widedeep, bench_widedeep_ps,
                  bench_widedeep_device, bench_int8_resnet18,
                  bench_resnet50_filefed, bench_lenet,
                  bench_longseq_flash, bench_masked_flash):
        try:
            bench(on_accel)
        except Exception as e:     # noqa: BLE001 — the remaining legs
            # still run; the failure reaches the exit code below
            failed.append(bench.__name__)
            _emit(bench.__name__ + "_FAILED", 0.0, repr(e)[:120], 0.0)
    _finalize_artifact()
    if failed:
        raise SystemExit(f"bench.py: {len(failed)} leg(s) failed: "
                         + ", ".join(failed))


if __name__ == "__main__":
    main()
