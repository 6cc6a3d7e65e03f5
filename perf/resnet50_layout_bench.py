import time
import numpy as np
import paddle_tpu as paddle
paddle.device.use_compile_cache()
import paddle_tpu.nn.functional as F
from paddle_tpu import optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.vision.models import resnet50

def fence(t):
    np.asarray(t._data if hasattr(t, "_data") else t)

B, HW = 128, 224
rng = np.random.default_rng(0)
x_nchw = rng.standard_normal((B, 3, HW, HW)).astype(np.float32)
y = paddle.to_tensor(rng.integers(0, 1000, size=(B,)).astype(np.int64))

def bench(data_format):
    model = resnet50(num_classes=1000, data_format=data_format)
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())
    def loss_fn(m, xb, yb):
        return F.cross_entropy(m(xb), yb).mean()
    step = TrainStep(model, loss_fn, opt, amp_level="O2",
                     amp_dtype="bfloat16")
    xin = x_nchw if data_format == "NCHW" else \
        np.ascontiguousarray(x_nchw.transpose(0, 2, 3, 1))
    xt = paddle.to_tensor(xin)
    for _ in range(3):
        loss = step(xt, y)
    fence(loss)
    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(xt, y)
    fence(loss)
    dt = (time.perf_counter() - t0) / iters
    sps = B / dt
    print(f"{data_format}: {dt*1e3:.1f} ms/step  {sps:.0f} samples/s")
    return sps

s1 = bench("NCHW")
s2 = bench("NHWC")
print(f"NHWC speedup: {s2/s1:.2f}x")
