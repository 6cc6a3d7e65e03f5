"""PS-tier knee analysis: ONE shared 100M-row host table, per-B steps."""
import gc, time
import numpy as np
import paddle_tpu as paddle
paddle.device.use_compile_cache()
import paddle_tpu.nn.functional as F
from paddle_tpu import optimizer
from paddle_tpu.distributed.ps import DistributedEmbedding, PSTrainStep
from paddle_tpu.models import WideDeepHost

V, E, fields, dense_dim = 100_000_000, 64, 26, 13
rng = np.random.default_rng(0)
emb = DistributedEmbedding(V, E + 1, optimizer="adagrad",
                           learning_rate=0.05, mode="async")
model = WideDeepHost(embedding_dim=E, num_fields=fields,
                     dense_dim=dense_dim)
opt = optimizer.Adam(learning_rate=1e-3, parameters=model.parameters())
def loss_fn(m, rows, x, y):
    return F.binary_cross_entropy_with_logits(m(rows, x), y).mean()

for B in (1024, 2048, 4096, 8192, 16384, 32768):
    step = PSTrainStep(model, loss_fn, opt, emb)
    ids = (rng.zipf(1.3, size=(B, fields)) % V).astype(np.int64)
    x = paddle.to_tensor(rng.standard_normal((B, dense_dim)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 2, size=(B, 1)).astype(np.float32))
    for _ in range(3):
        step(ids, x, y)
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        step(ids, x, y)
    step.flush()
    total = (time.perf_counter() - t0) / iters
    uniq = np.unique(ids.reshape(-1))
    t0 = time.perf_counter()
    for _ in range(iters):
        emb.table.pull(uniq)
    pull = (time.perf_counter() - t0) / iters
    print(f"B={B:6d} uniq={len(uniq):7d} total={total*1e3:8.1f} ms "
          f"pull={pull*1e3:7.1f} ms ({100*pull/total:4.1f}%) "
          f"eps={B/total:9.0f}", flush=True)
    gc.collect()
