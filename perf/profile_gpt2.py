import numpy as np
import paddle_tpu as paddle
paddle.device.use_compile_cache()
from paddle_tpu import optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import GPT, gpt2_345m, gpt_loss
import jax
import time


def fence(t):
    np.asarray(t._data if hasattr(t, "_data") else t)


B, S = 8, 1024
cfg = gpt2_345m(remat=False, max_seq_len=S, scan_unroll=24)
model = GPT(cfg)
opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
step = TrainStep(model, gpt_loss, opt, amp_level="O2", amp_dtype="bfloat16")
rng = np.random.default_rng(0)
ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size,
                                    size=(B, S)).astype(np.int32))
for _ in range(3):
    loss = step(ids, ids)
fence(loss)
t0 = time.perf_counter()
for _ in range(10):
    loss = step(ids, ids)
fence(loss)
dt = time.perf_counter() - t0
print(f"step={dt/10*1000:.1f}ms tok/s={B*S*10/dt:.0f}")
with jax.profiler.trace("/tmp/gpttrace"):
    for _ in range(5):
        loss = step(ids, ids)
    fence(loss)
print("trace captured")
import subprocess
print(subprocess.run(["find", "/tmp/gpttrace", "-type", "f"],
                     capture_output=True, text=True).stdout)
